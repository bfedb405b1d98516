"""Composable LM covering all 10 assigned architectures.

One :class:`LM` consumes an :class:`repro.models.config.ArchConfig` and
provides ``init / forward / loss / prefill / decode_step / init_cache``.
Layers are stacked per *pattern position* and executed with ``lax.scan`` over
cycles (compile-time O(1) in depth — essential for the 88-layer dry-runs).

Block kinds (config.block_pattern):
  attn        — self-attention + FFN (or MoE when cfg.n_experts)
  cross_attn  — self-attention + cross-attention to a memory + FFN
                (whisper decoder, llama-3.2-vision image layers)
  mamba       — Mamba2 SSD block
  mlstm/slstm — xLSTM blocks
  shared_attn — zamba2-style shared transformer block (one weight set reused
                at every occurrence, per-occurrence input adapter)
  mamba2      — Zamba2's Mamba2 layer: grouped B and C, the convolution over
                x, B and C with bias, the gated norm per group
  zamba2_hybrid — Zamba2's hybrid layer: one of the shared blocks (picked by
                the cycle's index) over concat(x, embedding), its LoRA
                adapter and output projection, then its Mamba2 layer

The Zamba2 kinds are training paths: they have no decode cache.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.config import ArchConfig
from repro.parallel.axes import shard

Pytree = Any

# block kinds with no decode cache: prefill and decode refuse them
TRAINING_ONLY = frozenset({"mamba2", "zamba2_hybrid"})


def _tree_index(tree: Pytree, i) -> Pytree:
    return jax.tree.map(lambda x: x[i], tree)


@dataclass(frozen=True)
class LM:
    cfg: ArchConfig
    # Unroll the layer stack into straight-line HLO instead of lax.scan.
    # Used by the dry-run cost probes: XLA's cost_analysis reports ZERO
    # flops for while-loop bodies, so probes lower 1-2 unrolled cycles.
    unroll: bool = False

    def _scan(self, body, init, xs):
        if not self.unroll:
            return lax.scan(body, init, xs)
        carry = init
        ys = []
        n = jax.tree.leaves(xs)[0].shape[0]
        for c in range(n):
            carry, y = body(carry, _tree_index(xs, c))
            ys.append(y)
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys) if ys and \
            jax.tree.leaves(ys[0]) else ys[0] if ys else ()
        return carry, stacked

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def block_defs(self, kind: str) -> dict:
        cfg = self.cfg
        if kind == "attn":
            d = {"attn": L.attn_defs(cfg)}
            d["moe" if cfg.n_experts else "ffn"] = \
                L.moe_defs(cfg) if cfg.n_experts else L.ffn_defs(cfg)
            return d
        if kind == "cross_attn":
            return {"attn": L.attn_defs(cfg),
                    "cross": L.cross_attn_defs(cfg),
                    "ffn": L.ffn_defs(cfg)}
        if kind == "mamba":
            return {"mamba": L.mamba_defs(cfg)}
        if kind == "mlstm":
            return {"mlstm": L.mlstm_defs(cfg)}
        if kind == "slstm":
            return {"slstm": L.slstm_defs(cfg)}
        if kind == "shared_attn":
            return {"in_proj": L.ParamDef(
                (cfg.d_model, cfg.d_model), ("fsdp", "embed"), scale=0.02)}
        if kind == "mamba2":
            z = cfg.zamba2
            return {"mamba": L.mamba_defs(cfg, heads=z.ssm_heads,
                                          groups=z.ssm_groups)}
        if kind == "zamba2_hybrid":
            return L.zamba2_hybrid_defs(cfg)
        raise ValueError(f"unknown block kind {kind}")

    def param_defs(self) -> dict:
        cfg = self.cfg
        defs: dict = {
            "embed": L.embed_defs(cfg),
            "final_norm": L.ParamDef((cfg.d_model,), ("embed",), init="ones"),
        }
        for p, kind in enumerate(cfg.pattern):
            defs[f"pos{p}"] = L.stack_defs(self.block_defs(kind),
                                           cfg.n_cycles)
        if "shared_attn" in cfg.pattern:
            defs["shared"] = {"attn": L.attn_defs(cfg),
                              "ffn": L.ffn_defs(cfg)}
        if "zamba2_hybrid" in cfg.pattern:
            defs["shared"] = L.stack_defs(L.zamba2_shared_defs(cfg),
                                          cfg.zamba2.sets)
        if cfg.encoder_layers:
            defs["encoder"] = L.stack_defs(
                {"attn": L.attn_defs(cfg), "ffn": L.ffn_defs(cfg)},
                cfg.encoder_layers)
            defs["enc_norm"] = L.ParamDef((cfg.d_model,), ("embed",),
                                          init="ones")
        return defs

    def init(self, key: jax.Array) -> Pytree:
        return L.materialize(self.param_defs(), key, self.cfg.jnp_dtype)

    def abstract_params(self) -> Pytree:
        return L.abstract(self.param_defs(), self.cfg.jnp_dtype)

    def param_axes(self) -> Pytree:
        return L.logical_tree(self.param_defs())

    def n_params(self) -> int:
        return sum(math.prod(d.shape) for d in jax.tree.leaves(
            self.param_defs(), is_leaf=lambda x: isinstance(x, L.ParamDef)))

    # ------------------------------------------------------------------
    # Encoder / memory (whisper audio stub, vision stub)
    # ------------------------------------------------------------------

    def encode(self, params: Pytree, audio_embed: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = shard(audio_embed, "batch", "seq", "embed")
        pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])

        def body(h, lp):
            h = L.attn_block(lp["attn"], cfg, h, pos, causal=False,
                             unroll=self.unroll)
            h = L.ffn_block(lp["ffn"], cfg, h)
            return h, ()

        x, _ = self._scan(body, x, params["encoder"])
        return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def _memory(self, params, audio_embed, vision_embed):
        if self.cfg.encoder_layers:
            assert audio_embed is not None, "whisper needs audio_embed"
            return self.encode(params, audio_embed)
        if self.cfg.cross_attn_every:
            assert vision_embed is not None, "VLM needs vision_embed"
            return shard(vision_embed, "batch", "seq", "embed")
        return None

    # ------------------------------------------------------------------
    # Forward (training / prefill)
    # ------------------------------------------------------------------

    def forward(self, params: Pytree, tokens: jax.Array, *,
                audio_embed: jax.Array | None = None,
                vision_embed: jax.Array | None = None,
                remat: str = "none",
                return_cache: bool = False):
        """Full-sequence forward.  Returns final hidden (B,S,d), and the
        decode cache when ``return_cache`` (prefill path)."""
        cfg = self.cfg
        B, S = tokens.shape
        hybrid = "zamba2_hybrid" in cfg.pattern
        if return_cache:
            self._check_decode()
        with jax.named_scope("head"):
            x = L.embed(params["embed"], cfg, tokens)
        x0 = x
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        memory = self._memory(params, audio_embed, vision_embed)
        shared = params.get("shared")

        def cycle(carry, cyc_params):
            # a hybrid pattern carries the cycle's index beside x, to pick
            # its shared set: set c mod sets
            x, c = carry if hybrid else (carry, None)
            cache_out = []
            for p, kind in enumerate(cfg.pattern):
                bp = cyc_params[f"pos{p}"]
                with jax.named_scope(kind):
                    if kind in ("attn", "cross_attn"):
                        h = L.rms_norm(x, bp["attn"]["ln"], cfg.norm_eps)
                        q, k, v = L._qkv(bp["attn"], cfg, h, positions)
                        o = L.mha(q, k, v, causal=cfg.causal,
                                  q_chunk=cfg.attn_q_chunk, unroll=self.unroll)
                        o = jnp.einsum("bshk,hkd->bsd", o, bp["attn"]["wo"])
                        x = x + shard(o, "batch", "seq", "embed")
                        if return_cache:
                            cache_out.append({"k": k, "v": v})
                        if kind == "cross_attn":
                            x = L.cross_attn_block(bp["cross"], cfg, x, memory,
                                                   unroll=self.unroll)
                        x = (L.moe_block(bp["moe"], cfg, x) if cfg.n_experts
                             else L.ffn_block(bp["ffn"] if "ffn" in bp else
                                              bp["moe"], cfg, x))
                    elif kind == "shared_attn":
                        h = jnp.einsum("bsd,de->bse", x, bp["in_proj"])
                        hn = L.rms_norm(h, shared["attn"]["ln"], cfg.norm_eps)
                        q, k, v = L._qkv(shared["attn"], cfg, hn, positions)
                        o = L.mha(q, k, v, causal=True, window=cfg.attn_window,
                                  q_chunk=cfg.attn_q_chunk, unroll=self.unroll)
                        o = jnp.einsum("bshk,hkd->bsd", o,
                                       shared["attn"]["wo"])
                        h = h + o
                        h = L.ffn_block(shared["ffn"], cfg, h)
                        x = x + h
                        if return_cache:
                            # ring-buffer layout: last W tokens at slots
                            # pos % W
                            W = cfg.attn_window or S
                            kc, vc = (t[:, -W:] if S >= W else
                                      jnp.pad(t, ((0, 0), (0, W - S),
                                                  (0, 0), (0, 0)))
                                      for t in (k, v))
                            cache_out.append({"k": kc, "v": vc})
                    elif kind == "mamba":
                        x, st, conv = L.mamba_block(bp["mamba"], cfg, x,
                                                    return_state=True,
                                                    unroll=self.unroll)
                        if return_cache:
                            cache_out.append({"ssm": st, "conv": conv})
                    elif kind == "mamba2":
                        x = L.mamba_block(bp["mamba"], cfg, x,
                                          unroll=self.unroll)
                    elif kind == "zamba2_hybrid":
                        x = L.zamba2_hybrid_block(
                            bp, self._shared_set(shared, c), cfg, x, x0,
                            positions, unroll=self.unroll)
                    elif kind == "mlstm":
                        x, st = L.mlstm_block(bp["mlstm"], cfg, x,
                                              return_state=True,
                                              unroll=self.unroll)
                        if return_cache:
                            cache_out.append({"state": st})
                    elif kind == "slstm":
                        x, st = L.slstm_block(bp["slstm"], cfg, x,
                                              return_state=True)
                        if return_cache:
                            cache_out.append({"state": st})
            return ((x, c + 1) if hybrid else x), tuple(cache_out)

        body = cycle
        if remat == "full":
            body = jax.checkpoint(cycle,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        elif remat == "selective":
            body = jax.checkpoint(
                cycle, policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)

        stacks = {f"pos{p}": params[f"pos{p}"]
                  for p in range(len(cfg.pattern))}
        init = (x, jnp.zeros((), jnp.int32)) if hybrid else x
        out, caches = self._scan(body, init, stacks)
        x = out[0] if hybrid else out
        with jax.named_scope("head"):
            x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        if return_cache:
            return x, caches
        return x

    def _shared_set(self, shared: Pytree, c: jax.Array) -> Pytree:
        """The shared weight set of cycle ``c``: set c mod sets."""
        sets = self.cfg.zamba2.sets
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, c % sets, keepdims=False),
            shared)

    # ------------------------------------------------------------------
    # Losses / serving entry points
    # ------------------------------------------------------------------

    def loss(self, params: Pytree, tokens: jax.Array, labels: jax.Array,
             *, remat: str = "none", **mods) -> jax.Array:
        x = self.forward(params, tokens, remat=remat, **mods)
        with jax.named_scope("head"):
            return L.xent_loss(x, params["embed"]["tok"], labels, self.cfg)

    def prefill(self, params: Pytree, tokens: jax.Array, **mods):
        """Serving prefill: returns (last-token logits, decode cache)."""
        x, cache = self.forward(params, tokens, return_cache=True, **mods)
        last = x[:, -1:]
        logits = L.logits_chunked(last, params["embed"]["tok"], self.cfg)
        return logits[:, 0], cache

    # -- decode ---------------------------------------------------------
    #
    # The decode cache is a FLAT tuple with one entry per layer (not stacked
    # per pattern position): each entry is an independent buffer, so XLA
    # aliases the donated input cache in place — no double-buffering through
    # a scan's ys.  decode_step unrolls the (cheap per-layer) decode HLO.

    def _check_decode(self) -> None:
        untrained = sorted(set(self.cfg.pattern) & TRAINING_ONLY)
        if untrained:
            raise NotImplementedError(
                f"{self.cfg.name}: block kinds {untrained} have no decode "
                f"cache (training only)")

    def _cache_entry(self, kind: str, batch: int, max_len: int, mk):
        cfg = self.cfg
        dt = cfg.jnp_dtype
        e = cfg.ssm_expand * cfg.d_model
        nh = e // cfg.ssm_head_dim
        H = cfg.n_heads
        if kind in ("attn", "cross_attn"):
            kvs = (batch, max_len, cfg.n_kv_heads, cfg.hd)
            return {"k": mk(kvs, dt), "v": mk(kvs, dt)}
        if kind == "shared_attn":
            W = min(cfg.attn_window or max_len, max_len)
            kvs = (batch, W, cfg.n_kv_heads, cfg.hd)
            return {"k": mk(kvs, dt), "v": mk(kvs, dt)}
        if kind == "mamba":
            return {"ssm": mk((batch, nh, cfg.ssm_head_dim, cfg.ssm_state),
                              jnp.float32),
                    "conv": mk((batch, cfg.ssm_conv_width - 1, e), dt)}
        if kind == "mlstm":
            hde = 2 * cfg.d_model // H
            return {"state": (mk((batch, H, hde, hde), jnp.float32),
                              mk((batch, H, hde), jnp.float32),
                              mk((batch, H), jnp.float32, -1e30))}
        if kind == "slstm":
            hds = cfg.d_model // H
            return {"state": (mk((batch, H, hds), jnp.float32),
                              mk((batch, H, hds), jnp.float32),
                              mk((batch, H, hds), dt),
                              mk((batch, H), jnp.float32, -1e30))}
        raise ValueError(kind)

    def init_cache(self, batch: int, max_len: int, *,
                   abstract: bool = False) -> Pytree:
        """Zeroed (or abstract) flat per-layer decode cache.  The xLSTM
        max-stabilizer states start at -1e30 (matching the blocks' internal
        init), everything else at zero."""
        self._check_decode()
        mk = (lambda s, d, fill=0.0: jax.ShapeDtypeStruct(s, d)) if abstract \
            else (lambda s, d, fill=0.0: jnp.full(s, fill, d))
        return tuple(self._cache_entry(self.cfg.block_kind(i), batch,
                                       max_len, mk)
                     for i in range(self.cfg.n_layers))

    def cache_axes(self) -> Pytree:
        """Logical-axis tree matching :meth:`init_cache` (for sharding)."""
        cfg = self.cfg
        kv = ("batch", "kv_seq", "kv_heads", "head_dim")

        def entry(kind):
            if kind in ("attn", "cross_attn", "shared_attn"):
                return {"k": kv, "v": kv}
            if kind == "mamba":
                return {"ssm": ("batch", "heads", "head_dim", "state"),
                        "conv": ("batch", "conv", "mlp")}
            if kind == "mlstm":
                return {"state": (("batch", "heads", "head_dim", "head_dim"),
                                  ("batch", "heads", "head_dim"),
                                  ("batch", "heads"))}
            if kind == "slstm":
                h3 = ("batch", "heads", "head_dim")
                return {"state": (h3, h3, h3, ("batch", "heads"))}
            raise ValueError(kind)

        return tuple(entry(cfg.block_kind(i)) for i in range(cfg.n_layers))

    def stacked_cache_axes(self):
        """Logical axes for the PREFILL cache (stacked per pattern position,
        leading n_cycles dim) — used to pin prefill out_shardings."""
        cfg = self.cfg
        kv = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")

        def entry(kind):
            if kind in ("attn", "cross_attn", "shared_attn"):
                return {"k": kv, "v": kv}
            if kind == "mamba":
                return {"ssm": ("layers", "batch", "heads", "head_dim",
                                "state"),
                        "conv": ("layers", "batch", "conv", "mlp")}
            if kind == "mlstm":
                return {"state": (("layers", "batch", "heads", "head_dim",
                                   "head_dim"),
                                  ("layers", "batch", "heads", "head_dim"),
                                  ("layers", "batch", "heads"))}
            if kind == "slstm":
                h3 = ("layers", "batch", "heads", "head_dim")
                return {"state": (h3, h3, h3, ("layers", "batch", "heads"))}
            raise ValueError(kind)

        return tuple(entry(kind) for kind in cfg.pattern)

    def unstack_cache(self, stacked: Pytree) -> Pytree:
        """Convert a prefill cache (stacked per pattern position, the scan's
        ys layout) into the flat per-layer decode layout."""
        cfg = self.cfg
        flat = []
        for i in range(cfg.n_layers):
            c, p = divmod(i, cfg.cycle_len)
            flat.append(_tree_index(stacked[p], c))
        return tuple(flat)

    def decode_step(self, params: Pytree, cache: Pytree, tokens: jax.Array,
                    pos: jax.Array, *,
                    audio_embed: jax.Array | None = None,
                    vision_embed: jax.Array | None = None):
        """One decode step: tokens (B,1), pos (B,).  Returns (logits, cache).

        ``cache`` is the flat per-layer tuple; pass it donated so every
        layer's k/v/state updates alias in place.
        """
        self._check_decode()
        cfg = self.cfg
        x = L.embed(params["embed"], cfg, tokens)
        memory = self._memory(params, audio_embed, vision_embed)
        shared = params.get("shared")
        new_cache: list = []
        for i in range(cfg.n_layers):
            c, p = divmod(i, cfg.cycle_len)
            kind = cfg.block_kind(i)
            bp = _tree_index(params[f"pos{p}"], c)
            cc = cache[i]
            if kind in ("attn", "cross_attn"):
                x, nk, nv = L.attn_decode(bp["attn"], cfg, x,
                                          cc["k"], cc["v"], pos)
                new_cache.append({"k": nk, "v": nv})
                if kind == "cross_attn":
                    x = L.cross_attn_block(bp["cross"], cfg, x, memory)
                x = (L.moe_block(bp["moe"], cfg, x) if cfg.n_experts
                     else L.ffn_block(bp["ffn"], cfg, x))
            elif kind == "shared_attn":
                h = jnp.einsum("bsd,de->bse", x, bp["in_proj"])
                h, nk, nv = L.attn_decode(shared["attn"], cfg, h,
                                          cc["k"], cc["v"], pos,
                                          window=cfg.attn_window)
                new_cache.append({"k": nk, "v": nv})
                h = L.ffn_block(shared["ffn"], cfg, h)
                x = x + h
            elif kind == "mamba":
                x, st, conv = L.mamba_block(
                    bp["mamba"], cfg, x, state=cc["ssm"],
                    conv_state=cc["conv"], return_state=True)
                new_cache.append({"ssm": st, "conv": conv})
            elif kind == "mlstm":
                x, st = L.mlstm_block(bp["mlstm"], cfg, x,
                                      state=cc["state"], return_state=True)
                new_cache.append({"state": st})
            elif kind == "slstm":
                x, st = L.slstm_block(bp["slstm"], cfg, x,
                                      state=cc["state"], return_state=True)
                new_cache.append({"state": st})
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.logits_chunked(x, params["embed"]["tok"], cfg)
        return logits[:, 0], tuple(new_cache)
