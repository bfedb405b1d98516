"""Architecture configs and input-shape specs for the assigned model pool.

:class:`ArchConfig` is the single config type all 10 assigned architectures
instantiate (repro/configs/<id>.py).  It drives

  * the pure-JAX model definition (repro.models.lm),
  * the planner's analytic description (:meth:`to_model_desc`),
  * the dry-run input specs (:meth:`input_specs`).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Literal, Sequence

import jax
import jax.numpy as jnp

from repro.core.opgraph import ModelDesc

BlockKind = Literal["attn", "mamba", "mlstm", "slstm", "shared_attn",
                    "mamba2", "zamba2_hybrid"]

# Zamba2's published constants (Zyphra/Zamba2-7B config.json): B and C of
# the Mamba2 mixers in 2 groups (``mamba_ngroups``), 2 shared transformer
# blocks used in turn (``num_mem_blocks``), a LoRA adapter of rank 128 on
# the shared MLP at each hybrid layer (``adapter_rank``).
ZAMBA2_SSM_GROUPS = 2
ZAMBA2_SHARED_SETS = 2
ZAMBA2_ADAPTER_RANK = 128


@dataclass(frozen=True)
class ShapeSpec:
    """One assigned input shape (arch x shape = one dry-run cell)."""

    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


# The four LM shapes from the assignment.
TRAIN_4K = ShapeSpec("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeSpec("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeSpec("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeSpec("long_500k", 524288, 1, "decode")
ALL_SHAPES: tuple[ShapeSpec, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K,
                                     LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


@dataclass(frozen=True)
class Zamba2Share:
    """What one chip holds of each Zamba2 layer (:attr:`ArchConfig.zamba2`)
    when tensor parallelism splits the layer over ``ranks`` chips: the
    Mamba2 mixer's heads and groups of B and C, the shared MLP's columns,
    and the shared weight sets its hybrid layers use."""

    ranks: int
    ssm_heads: int
    ssm_groups: int
    mlp: int
    sets: int


@dataclass(frozen=True)
class ArchConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None

    # attention details
    qkv_bias: bool = False            # qwen2
    qk_norm: bool = False             # qwen3
    rope_theta: float = 10000.0
    causal: bool = True

    # ffn details
    ffn_kind: Literal["swiglu", "geglu", "gelu"] = "swiglu"

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_capacity_factor: float = 1.25
    # group-local dispatch: token groups aligned to the data shards (the
    # launcher sets this to the mesh's dp extent; 1 = single-group/CPU)
    moe_groups: int = 1

    # hybrid / recurrent
    block_pattern: tuple[BlockKind, ...] = ()   # cycle; empty => all "attn"
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4

    # enc-dec (whisper): encoder depth; frontend is a stub — inputs are
    # precomputed frame embeddings of length ``audio_seq``.
    encoder_layers: int = 0
    audio_seq: int = 1500

    # VLM: cross-attention to precomputed image patch embeddings every
    # ``cross_attn_every`` layers; ``vision_seq`` patch tokens at d_model.
    cross_attn_every: int = 0
    vision_seq: int = 1601

    # misc
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    logit_softcap: float = 0.0        # gemma-style final-logit softcap
    scale_embed: bool = False         # gemma multiplies embed by sqrt(d)
    attn_q_chunk: int = 2048          # flash-style query chunk (memory bound)
    dtype: str = "bfloat16"
    # which archs can run long_500k (sub-quadratic path)
    subquadratic: bool = False
    # attention window for hybrid long-context shared attention (0 = full)
    attn_window: int = 0

    # ------------------------------------------------------------------

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def pattern(self) -> tuple[BlockKind, ...]:
        return self.block_pattern or ("attn",)

    @property
    def cycle_len(self) -> int:
        return len(self.pattern)

    @property
    def n_cycles(self) -> int:
        assert self.n_layers % self.cycle_len == 0, \
            f"{self.name}: n_layers {self.n_layers} % cycle {self.cycle_len}"
        return self.n_layers // self.cycle_len

    def block_kind(self, i: int) -> BlockKind:
        return self.pattern[i % self.cycle_len]

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def zamba2(self) -> Zamba2Share:
        """The chip's share of a Zamba2 layer (kinds ``mamba2`` and
        ``zamba2_hybrid``).  The fields state Zamba2's published widths,
        and ``n_heads`` the attention heads this chip holds.  The shared
        attention's heads span the 2d-wide concat(x, embedding) whole
        (Zamba2-7B: 32 heads of 224 = 2 x 3584), so they give the ranks a
        layer is split over, and each rank holds as much of every part:
        1/ranks of the Mamba2 heads (``ssm_expand * d_model /
        ssm_head_dim``) and of its groups, and of the MLP's ``d_ff``
        columns.  A pipeline stage holds the shared sets its hybrid layers
        use: its j-th uses set j mod 2."""
        ranks, rest = divmod(2 * self.d_model, self.n_heads * self.hd)
        heads = self.ssm_expand * self.d_model // self.ssm_head_dim
        if rest or ZAMBA2_SSM_GROUPS % ranks or heads % ranks \
                or self.d_ff % ranks:
            raise ValueError(
                f"{self.name}: {self.n_heads} heads of {self.hd} are no "
                f"share of a Zamba2 layer of width 2 x {self.d_model}")
        return Zamba2Share(
            ranks=ranks, ssm_heads=heads // ranks,
            ssm_groups=ZAMBA2_SSM_GROUPS // ranks, mlp=self.d_ff // ranks,
            sets=min(ZAMBA2_SHARED_SETS, self.n_cycles))

    # -- planner bridge -------------------------------------------------------

    def to_model_desc(self) -> ModelDesc:
        # Zamba2's hybrid layer is priced as its Mamba2 layer alone: the
        # shared block over concat(x, embedding) is not priced yet
        # (ROADMAP 2.2)
        pattern = tuple("mamba" if b in ("mamba", "mamba2", "zamba2_hybrid")
                        else ("mlstm" if b in ("mlstm", "slstm") else "attn")
                        for b in self.pattern) if self.block_pattern else ()
        return ModelDesc(
            name=self.name, n_layers=self.n_layers, d_model=self.d_model,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads, d_ff=self.d_ff,
            vocab=self.vocab, head_dim=self.head_dim,
            n_experts=self.n_experts, top_k=self.top_k,
            ssm_state=self.ssm_state, block_pattern=pattern,
            ffn_kind=self.ffn_kind, cross_attn_every=self.cross_attn_every,
            encoder_layers=self.encoder_layers,
            dtype_bytes=jnp.dtype(self.dtype).itemsize)

    # -- reduced config for CPU smoke tests ------------------------------------

    def reduced(self, **overrides) -> "ArchConfig":
        """Small same-family config: few layers, narrow width, tiny vocab."""
        cyc = self.cycle_len
        base = dict(
            n_layers=max(cyc, 2 * cyc if self.n_layers >= 2 * cyc else cyc),
            d_model=128,
            n_heads=max(2, min(4, self.n_heads)),
            n_kv_heads=1 if self.n_kv_heads == 1 else 2,
            head_dim=32 if self.head_dim else None,
            d_ff=256 if self.d_ff else 0,
            vocab=512,
            n_experts=8 if self.n_experts else 0,
            top_k=min(2, self.top_k) if self.n_experts else 0,
            encoder_layers=2 if self.encoder_layers else 0,
            audio_seq=24,
            cross_attn_every=2 if self.cross_attn_every else 0,
            vision_seq=16,
            ssm_state=16 if self.ssm_state else 0,
            ssm_head_dim=32 if self.ssm_state else 64,
            attn_q_chunk=64,
            attn_window=16 if self.attn_window else 0,
            dtype="float32",
        )
        base.update(overrides)
        # keep heads consistent with d_model when head_dim not pinned
        if base.get("head_dim") is None and not self.head_dim:
            base["head_dim"] = None
            base["n_heads"] = max(2, base["d_model"] // 32)
            base["n_kv_heads"] = 1 if self.n_kv_heads == 1 else 2
            # d_model/n_heads must be integral
            while base["d_model"] % base["n_heads"]:
                base["n_heads"] -= 1
        return replace(self, name=self.name + "-smoke", **base)

    # -- shapes ----------------------------------------------------------------

    def shapes(self) -> list[ShapeSpec]:
        """The assigned shapes this arch runs (skips documented in DESIGN.md)."""
        out = [TRAIN_4K, PREFILL_32K, DECODE_32K]
        if self.subquadratic:
            out.append(LONG_500K)
        return out

    def skipped_shapes(self) -> list[tuple[ShapeSpec, str]]:
        if self.subquadratic:
            return []
        return [(LONG_500K, "pure full-attention arch: 500k needs "
                            "sub-quadratic attention (DESIGN.md §5)")]

    # -- dry-run input specs (ShapeDtypeStruct, no allocation) -----------------

    def input_specs(self, shape: ShapeSpec) -> dict[str, jax.ShapeDtypeStruct]:
        """Abstract model inputs for one cell.  Modality frontends are stubs:
        audio/vision entries are precomputed embeddings."""
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = self.jnp_dtype
        if shape.kind == "train":
            specs = {
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
        elif shape.kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        else:  # decode: one new token against a cache of length S
            specs = {
                "tokens": jax.ShapeDtypeStruct((B, 1), i32),
                "pos": jax.ShapeDtypeStruct((B,), i32),
            }
        if self.encoder_layers:
            specs["audio_embed"] = jax.ShapeDtypeStruct(
                (B, self.audio_seq, self.d_model), dt)
        if self.cross_attn_every:
            specs["vision_embed"] = jax.ShapeDtypeStruct(
                (B, self.vision_seq, self.d_model), dt)
        return specs
