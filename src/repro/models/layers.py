"""Shared layer primitives for all 10 assigned architectures (pure JAX).

Every parameter is declared as a :class:`ParamDef` carrying its shape and
*logical* sharding axes; ``materialize``/``logical_tree`` turn a def-tree into
an initialized pytree and its axis-annotation tree.  Activations are
annotated through :func:`repro.parallel.axes.shard` so the same model code
runs unsharded on CPU (smoke tests) and GSPMD-sharded on the production mesh
(dry-run) without modification.

Attention is implemented memory-efficiently (query-chunked online softmax —
the jnp analogue of the Pallas flash kernel in ``repro.kernels``) so the
32k-prefill cells lower without materializing S×S score matrices.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ZAMBA2_ADAPTER_RANK
from repro.parallel.axes import shard

Axes = tuple[str | None, ...]


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: Axes
    scale: float | None = None       # None => 1/sqrt(fan_in) (first dim)
    init: str = "normal"             # normal | zeros | ones


def materialize(defs, key: jax.Array, dtype) -> Any:
    """Initialize a def-tree into a parameter pytree (deterministic)."""
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    out = []
    for i, d in enumerate(leaves):
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, dtype))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, dtype))
        else:
            k = jax.random.fold_in(key, i)
            scale = d.scale if d.scale is not None else \
                1.0 / math.sqrt(max(d.shape[0], 1))
            out.append((jax.random.normal(k, d.shape, jnp.float32)
                        * scale).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def abstract(defs, dtype) -> Any:
    """ShapeDtypeStruct tree (for dry-run lowering, no allocation)."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def logical_tree(defs) -> Any:
    return jax.tree.map(lambda d: d.axes, defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def stack_defs(defs, n: int) -> Any:
    """Prefix every def with a stacked layer dim (for lax.scan over layers)."""
    return jax.tree.map(
        lambda d: ParamDef((n, *d.shape), ("layers", *d.axes), d.scale, d.init),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


# ---------------------------------------------------------------------------
# Norms / rotary / activations
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
             *, gemma_style: bool = False) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    w = w.astype(jnp.float32)
    y = y * (1.0 + w) if gemma_style else y * w
    return y.astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., :, None, :]   # (..., S, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _act(kind: str, x: jax.Array) -> jax.Array:
    if kind == "swiglu":
        return jax.nn.silu(x)
    if kind == "geglu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# Attention (GQA, rope, qk-norm, optional window) — chunked online softmax
# ---------------------------------------------------------------------------


def attn_defs(cfg) -> dict:
    H, KV, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model
    defs = {
        "ln": ParamDef((d,), ("embed",), init="ones"),
        "wq": ParamDef((d, H, hd), ("fsdp", "heads", "head_dim")),
        "wk": ParamDef((d, KV, hd), ("fsdp", "kv_heads", "head_dim")),
        "wv": ParamDef((d, KV, hd), ("fsdp", "kv_heads", "head_dim")),
        "wo": ParamDef((H, hd, d), ("heads", "head_dim", "fsdp")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H, hd), ("heads", "head_dim"), init="zeros")
        defs["bk"] = ParamDef((KV, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = ParamDef((KV, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), ("head_dim",), init="ones")
        defs["k_norm"] = ParamDef((hd,), ("head_dim",), init="ones")
    return defs


def _qkv(p, cfg, x, positions):
    """Project + rope.  Returns q:(B,S,KV,G,hd) grouped, k,v:(B,S,KV,hd)."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    G = H // KV
    q = q.reshape(*q.shape[:2], KV, G, hd)
    return q, k, v


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
        q_positions: jax.Array | None = None,
        kv_positions: jax.Array | None = None,
        window: int = 0, q_chunk: int = 1024,
        softcap: float = 0.0, unroll: bool = False,
        scale: float | None = None) -> jax.Array:
    """Grouped-query attention, chunked over queries (bounded memory).

    q: (B, Sq, KV, G, hd);  k, v: (B, Skv, KV, hd).  Returns (B, Sq, KV*G, hd).
    Masks: causal by position, optional sliding ``window``.  Scores are
    scaled by ``scale``, 1/sqrt(hd) unless given.
    """
    B, Sq, KV, G, hd = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    if q_positions is None:
        q_positions = jnp.arange(Sq)[None, :] + (Skv - Sq)
        q_positions = jnp.broadcast_to(q_positions, (B, Sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(Skv)[None, :], (B, Skv))

    # GQA via explicit KV repeat to full head width: the repeated k/v are
    # transient and shard cleanly over "heads" (H = KV*G divides the model
    # axis for 9/10 archs), whereas a grouped (KV, G) einsum loses the head
    # sharding through the reshape and GSPMD replicates the score tensor
    # (measured 42 GB temp on granite MQA prefill).
    q = q.reshape(B, Sq, KV * G, hd)
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "heads", "head_dim")
    v = shard(v, "batch", "seq", "heads", "head_dim")

    qc = min(q_chunk, Sq)
    while Sq % qc:
        qc -= 1
    n_chunks = Sq // qc
    # Causal self-attention with KV slicing per chunk skips fully-masked
    # blocks (the flash-kernel behaviour; halves attention FLOPs).  The
    # python-unrolled form is used by the cost probes (XLA counts it) and
    # matches the Pallas kernel's compute; the runtime jnp fallback uses a
    # sequential lax.map over chunks (ONE score block live — the unrolled
    # chunks otherwise peak at the full S^2/2 matrix; measured 30 GB on
    # granite prefill) at the cost of computing masked blocks.
    causal_slice = causal and Sq == Skv and n_chunks > 1 and unroll

    def one_chunk(i, k=k, v=v, kvp=kv_positions):
        qs = lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
        qp = lax.dynamic_slice_in_dim(q_positions, i * qc, qc, axis=1)
        s = jnp.einsum("bqhk,bshk->bhqs", qs, k).astype(jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = qp[:, :, None] >= kvp[:, None, :] if causal else \
            jnp.ones((B, qc, k.shape[1]), bool)
        if window:
            mask &= qp[:, :, None] - kvp[:, None, :] < window
        s = jnp.where(mask[:, None], s, -1e30)
        o = jnp.einsum("bhqs,bshk->bqhk",
                       jax.nn.softmax(s, axis=-1).astype(q.dtype), v)
        return o

    if n_chunks == 1:
        out = one_chunk(0)
    elif causal_slice:
        outs = []
        for i in range(n_chunks):
            hi = (i + 1) * qc
            lo = 0
            if window:
                lo = max(0, (i - math.ceil(window / qc)) * qc)
            outs.append(one_chunk(i, k=k[:, lo:hi], v=v[:, lo:hi],
                                  kvp=kv_positions[:, lo:hi]))
        out = jnp.concatenate(outs, axis=1)
    else:
        outs = lax.map(one_chunk, jnp.arange(n_chunks))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, KV * G, hd)
    return out


def attn_block(p, cfg, x, positions, *, window: int = 0,
               causal: bool | None = None,
               unroll: bool = False) -> jax.Array:
    """Pre-norm self-attention residual block (no FFN)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h, positions)
    o = mha(q, k, v, causal=cfg.causal if causal is None else causal,
            window=window, q_chunk=cfg.attn_q_chunk, unroll=unroll)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return x + shard(o, "batch", "seq", "embed")


def attn_decode(p, cfg, x, cache_k, cache_v, pos, *, window: int = 0):
    """One-token decode: update the cache at ``pos``, attend to it.

    x: (B, 1, d); cache_k/v: (B, S, KV, hd); pos: (B,) int32.
    Returns (out (B,1,d), new_k, new_v).
    """
    B, S = cache_k.shape[0], cache_k.shape[1]
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h, pos[:, None])
    wpos = pos % S if window else pos   # ring buffer for windowed attention
    upd = jax.vmap(lambda c, n, i: lax.dynamic_update_slice(
        c, n, (i, 0, 0)))(cache_k, k, wpos)
    updv = jax.vmap(lambda c, n, i: lax.dynamic_update_slice(
        c, n, (i, 0, 0)))(cache_v, v, wpos)
    kv_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if window:
        # ring buffer: slot stores token (pos - ((wpos - slot) mod S));
        # never-written slots have kv_pos < 0 -> pushed out of the window.
        kv_pos = pos[:, None] - ((wpos[:, None] - kv_pos) % S)
        kv_pos = jnp.where(kv_pos >= 0, kv_pos, -(jnp.int32(1) << 30))
    else:
        # slots beyond pos are future/unwritten -> masked by the causal rule
        pass
    o = mha(q, upd, updv, causal=True, q_positions=pos[:, None],
            kv_positions=kv_pos, window=window, q_chunk=1)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return x + o, upd, updv


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------


def ffn_defs(cfg, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    defs = {"ln": ParamDef((d,), ("embed",), init="ones"),
            "w_up": ParamDef((d, f), ("fsdp", "mlp")),
            "w_down": ParamDef((f, d), ("mlp", "fsdp"))}
    if cfg.ffn_kind in ("swiglu", "geglu"):
        defs["w_gate"] = ParamDef((d, f), ("fsdp", "mlp"))
    return defs


def ffn_block(p, cfg, x) -> jax.Array:
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    up = jnp.einsum("bsd,df->bsf", h, p["w_up"])
    if "w_gate" in p:
        up = up * _act(cfg.ffn_kind,
                       jnp.einsum("bsd,df->bsf", h, p["w_gate"]))
    else:
        up = _act(cfg.ffn_kind, up)
    up = shard(up, "batch", "seq", "mlp")
    out = jnp.einsum("bsf,fd->bsd", up, p["w_down"])
    return x + shard(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-gather dispatch, static shapes)
# ---------------------------------------------------------------------------


def moe_defs(cfg) -> dict:
    """Expert weights use 2-D TP: experts over "model", d_ff over
    "expert_mlp" (mapped to "data" by the profile).  Unlike FSDP on the
    data axis this never re-gathers the (dominant) expert parameters — the
    data-axis traffic becomes activation-sized reduce/gathers, token-
    proportional instead of M×params (measured 79 s -> sub-second on
    dbrx-132b train_4k)."""
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "ln": ParamDef((d,), ("embed",), init="ones"),
        "router": ParamDef((d, E), ("fsdp", "experts")),
        "w_gate": ParamDef((E, d, f), ("experts", "expert_in", "expert_mlp")),
        "w_up": ParamDef((E, d, f), ("experts", "expert_in", "expert_mlp")),
        "w_down": ParamDef((E, f, d), ("experts", "expert_mlp", "expert_in")),
    }


def moe_block(p, cfg, x) -> jax.Array:
    """Top-k MoE with GROUP-LOCAL capacity dispatch (expert parallelism).

    Tokens are split into ``cfg.moe_groups`` groups aligned with the data
    shards; the expert sort/rank/capacity bookkeeping is *per group* — a
    global argsort would force GSPMD to all-gather every token to every
    device (measured: 557 GB temp for ONE layer on the 256-chip mesh).
    The only cross-shard movement is the (G, E, C, d) -> (E, G·C, d)
    transpose feeding the expert einsum: a structured all-to-all from
    token-sharding to expert-sharding, exactly the EP dispatch collective.
    Static shapes throughout; tokens beyond the per-group capacity
    C = K·t_g·cf/E drop to a zero bin (standard capacity semantics).
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    t = B * S
    G = max(cfg.moe_groups, 1)
    if t % G:
        G = 1
    tg = t // G
    ht = h.reshape(G, tg, d)
    ht = shard(ht, "batch", None, "embed")
    logits = jnp.einsum("gtd,de->gte", ht,
                        p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = lax.top_k(probs, K)                      # (G, tg, K)
    gate = (gate / jnp.sum(gate, -1, keepdims=True)).astype(x.dtype)

    C = max(int(K * tg * cfg.moe_capacity_factor / E), 1)
    C = min(C, tg)
    # flatten (token, k) pairs per group; sort by expert id (group-local!)
    flat_e = idx.reshape(G, tg * K)
    flat_tok = jnp.broadcast_to(
        jnp.repeat(jnp.arange(tg), K)[None], (G, tg * K))
    flat_g = gate.reshape(G, tg * K)
    order = jnp.argsort(flat_e, axis=1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=1)
    stok = jnp.take_along_axis(flat_tok, order, axis=1)
    sg = jnp.take_along_axis(flat_g, order, axis=1)
    # position of each pair within its expert's per-group queue
    first = jax.vmap(lambda s: jnp.searchsorted(s, jnp.arange(E)))(se)
    rank_in_e = jnp.arange(tg * K)[None] - jnp.take_along_axis(first, se,
                                                               axis=1)
    keep = rank_in_e < C
    slot = jnp.where(keep, se * C + rank_in_e, E * C)    # E*C = drop bin

    # gather tokens into per-group (E*C+1, d) buffers, then expose the
    # expert dim for the sharded expert einsum (this transpose is the a2a).
    # vmap'd 1-D gather/scatter keeps XLA's index operands at (tgK, 1) —
    # take_along_axis/2-level .at[] broadcast u32 index grids to the full
    # (G, tgK, d) value shape (measured 68-86 GB EACH on the 256-chip mesh).
    vals = jax.vmap(lambda h, i: h[i])(ht, stok)
    buf = jax.vmap(lambda s, v: jnp.zeros((E * C + 1, d),
                                          x.dtype).at[s].set(v))(slot, vals)
    # (E, G, C, d): experts sharded over "model", groups over "data" — a
    # 2-D-sharded expert einsum.  Collapsing (G, C) would replicate the
    # capacity dim across the data axis (measured 16x expert FLOPs).
    xe = jnp.moveaxis(buf[:, :-1].reshape(G, E, C, d), 1, 0)
    xe = shard(xe, "experts", "batch", None, "embed")
    a = _act(cfg.ffn_kind, jnp.einsum("egcd,edf->egcf", xe, p["w_gate"]))
    up = jnp.einsum("egcd,edf->egcf", xe, p["w_up"]) * a
    ye = jnp.einsum("egcf,efd->egcd", up, p["w_down"])
    ye = shard(ye, "experts", "batch", None, "embed")

    # combine: back to token sharding (reverse a2a), weighted scatter-add
    yg = jnp.moveaxis(ye, 0, 1).reshape(G, E * C, d)
    yg = shard(yg, "batch", None, "embed")
    yg = jnp.concatenate([yg, jnp.zeros((G, 1, d), x.dtype)], axis=1)
    contrib = jax.vmap(lambda y, s: y[s])(yg, slot) * sg[..., None]
    out = jax.vmap(lambda c, i: jnp.zeros((tg, d), x.dtype).at[i].add(c))(
        contrib, stok)
    return x + shard(out.reshape(B, S, d), "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Chunked time scan (recurrent blocks)
#
# Differentiating a plain S-step lax.scan saves every step's inputs —
# measured 34 GB for xlstm train_4k.  Scanning chunks of ``chunk`` steps
# with a rematerialized inner scan stores only the per-chunk carries
# (S/chunk × state) and recomputes inside the chunk on the backward pass.
# ---------------------------------------------------------------------------

TIME_SCAN_CHUNK = 256


def chunked_time_scan(step, carry, xs, *, chunk: int = TIME_SCAN_CHUNK):
    """lax.scan(step, carry, xs) with per-chunk remat.  xs: time-major."""
    S = jax.tree.leaves(xs)[0].shape[0]
    if S <= chunk or S % chunk:
        return lax.scan(step, carry, xs)
    n = S // chunk
    xs_c = jax.tree.map(lambda x: x.reshape(n, chunk, *x.shape[1:]), xs)
    inner = jax.checkpoint(lambda c, x: lax.scan(step, c, x),
                           policy=jax.checkpoint_policies.nothing_saveable)
    carry, ys = lax.scan(inner, carry, xs_c)
    ys = jax.tree.map(lambda y: y.reshape(n * chunk, *y.shape[2:]), ys)
    return carry, ys


# ---------------------------------------------------------------------------
# Mamba2 block (SSD recurrence, time scan)
# ---------------------------------------------------------------------------


def mamba_defs(cfg, *, heads: int | None = None, groups: int = 0) -> dict:
    """Weights of a Mamba2 block.

    ``groups`` 0 is the repo's first Mamba block: ``ssm_expand * d_model /
    ssm_head_dim`` heads, one B and one C for all of them, the causal
    convolution over x alone and without bias, one norm over all channels.
    ``groups`` G >= 1 is Mamba2 as published: ``heads`` heads, B and C per
    group (head h reads group h // (heads / G)), the convolution, with
    bias, over x, B and C, and the gated norm per group of e / G channels.
    """
    d = cfg.d_model
    nh = heads or cfg.ssm_expand * d // cfg.ssm_head_dim
    e = nh * cfg.ssm_head_dim
    N, W = cfg.ssm_state, cfg.ssm_conv_width
    bc = groups * N if groups else N
    defs = {
        "ln": ParamDef((d,), ("embed",), init="ones"),
        "w_z": ParamDef((d, e), ("fsdp", "mlp")),
        "w_x": ParamDef((d, e), ("fsdp", "mlp")),
        "w_B": ParamDef((d, bc), ("fsdp", "state")),
        "w_C": ParamDef((d, bc), ("fsdp", "state")),
        "w_dt": ParamDef((d, nh), ("fsdp", "heads")),
        "conv_w": ParamDef((W, e + 2 * bc if groups else e), ("conv", "mlp"),
                           scale=0.5),
        "A_log": ParamDef((nh,), ("heads",), init="zeros"),
        "D": ParamDef((nh,), ("heads",), init="ones"),
        "dt_bias": ParamDef((nh,), ("heads",), init="zeros"),
        "gn": ParamDef((e,), ("mlp",), init="ones"),
        "w_out": ParamDef((e, d), ("mlp", "fsdp")),
    }
    if groups:
        defs["conv_b"] = ParamDef((e + 2 * bc,), ("mlp",), init="zeros")
    return defs


def _per_head(t: jax.Array, nh: int) -> jax.Array:
    """B or C as (B, S, nh, N): (B, S, N) is one group for every head;
    of (B, S, G, N), head h reads group h // (nh / G)."""
    t = t[:, :, None] if t.ndim == 3 else t
    return jnp.repeat(t, nh // t.shape[2], axis=2)


def _mamba_scan_seq(x, B_in, C_in, dt, A_log, D, hd, *, h0=None):
    """Sequential SSD recurrence (reference / decode path).

    h_t = exp(A*dt_t) h_{t-1} + dt_t * x_t B_t^T ;  y_t = h_t C_t + D x_t
    B_in, C_in: (B,S,N) for all heads, or (B,S,G,N) per group.
    Returns (y (B,S,nh,hd), h_final (B,nh,hd,N)).
    """
    Bb, S, nh, _ = x.shape
    B_in, C_in = _per_head(B_in, nh), _per_head(C_in, nh)
    N = B_in.shape[-1]
    A = -jnp.exp(A_log.astype(jnp.float32))              # (nh,) negative

    def step(h, inp):
        xt, Bt, Ct, dtt = inp                            # (B,nh,hd),(B,nh,N),(B,nh,N),(B,nh)
        decay = jnp.exp(A[None] * dtt)                   # (B,nh)
        dx = (dtt[..., None] * xt).astype(jnp.float32)   # (B,nh,hd)
        h = h * decay[..., None, None] + dx[..., None] * Bt[:, :, None, :]
        y = jnp.einsum("bhdn,bhn->bhd", h, Ct.astype(jnp.float32))
        return h, y.astype(x.dtype)

    if h0 is None:
        h0 = jnp.zeros((Bb, nh, hd, N), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(B_in, 1, 0),
          jnp.moveaxis(C_in, 1, 0), jnp.moveaxis(dt, 1, 0))
    h_fin, ys = chunked_time_scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + D[None, None, :, None] * x
    return y, h_fin


MAMBA_CHUNK = 128


def _mamba_scan(x, B_in, C_in, dt, A_log, D, hd, *, h0=None,
                chunk: int = MAMBA_CHUNK, unroll: bool = False):
    """Chunkwise-parallel SSD (the Mamba2 paper's algorithm, TPU-adapted),
    under the named scope ``mamba_ssd``.

    A step-by-step scan round-trips the (B, nh, hd, N) fp32 state through
    HBM every token (memory-bound: ~7 s/step terms on the dry-run) and runs
    on the VPU.  The chunked form materializes the state once per ``chunk``
    tokens and turns intra-chunk work into MXU matmuls:

      y_intra[t] = sum_{s<=t} exp(logP_t - logP_s) (C_t.B_s) u_s
      y_cross[t] = exp(logP_t) C_t . h_in
      h_out      = exp(logP_c) h_in + sum_t exp(logP_c - logP_t) u_t (x) B_t

    All decay ratios are exp of non-positive numbers — stable in log space.
    B_in, C_in are (B,S,N) for all heads, or (B,S,G,N) per group, head h
    reading group h // (nh/G); the heads are laid out as (G, nh/G) inside.
    """
    with jax.named_scope("mamba_ssd"):
        Bb, S, nh, _ = x.shape
        if S % chunk or S <= chunk:
            return _mamba_scan_seq(x, B_in, C_in, dt, A_log, D, hd, h0=h0)
        if B_in.ndim == 3:
            B_in, C_in = B_in[:, :, None], C_in[:, :, None]
        G, N = B_in.shape[2:]
        R = nh // G
        A = -jnp.exp(A_log.astype(jnp.float32)).reshape(G, R)
        n = S // chunk
        f32 = jnp.float32

        def reshape_c(t):
            return t.reshape(Bb, n, chunk, *t.shape[2:])

        xc = reshape_c(x).reshape(Bb, n, chunk, G, R, hd)
        Bc = reshape_c(B_in).astype(f32)                 # (B,n,c,G,N)
        Cc = reshape_c(C_in).astype(f32)
        dtc = reshape_c(dt).astype(f32).reshape(Bb, n, chunk, G, R)
        u = dtc[..., None] * xc.astype(f32)              # (B,n,c,G,R,hd)
        loga = A * dtc                                   # (B,n,c,G,R) <= 0
        logP = jnp.cumsum(loga, axis=2)                  # (B,n,c,G,R)
        logPc = logP[:, :, -1]                           # (B,n,G,R)

        # intra-chunk: (C_t.B_s) * exp(logP_t - logP_s), masked s <= t
        cb = jnp.einsum("bntgk,bnsgk->bngts", Cc, Bc)    # (B,n,G,c,c)
        ratio = logP[:, :, :, None] - logP[:, :, None]   # (B,n,t,s,G,R)
        mask = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
        ratio = jnp.where(mask[None, None, :, :, None, None], ratio, -1e30)
        y_intra = jnp.einsum("bngts,bntsgr,bnsgrd->bntgrd", cb,
                             jnp.exp(ratio), u)

        # chunk-boundary states via an outer scan over n chunks
        contrib = jnp.einsum("bntgr,bntgrd,bntgk->bngrdk",
                             jnp.exp(logPc[:, :, None] - logP), u, Bc)

        h0 = (jnp.zeros((Bb, G, R, hd, N), f32) if h0 is None
              else h0.reshape(Bb, G, R, hd, N))

        def chunk_step(h, inp):
            lpc, contr, Ct, lP = inp
            y_cross = jnp.einsum("btgr,btgk,bgrdk->btgrd", jnp.exp(lP), Ct,
                                 h)
            h_new = h * jnp.exp(lpc)[..., None, None] + contr
            return h_new, y_cross

        xs = (jnp.moveaxis(logPc, 1, 0), jnp.moveaxis(contrib, 1, 0),
              jnp.moveaxis(Cc, 1, 0), jnp.moveaxis(logP, 1, 0))
        if unroll:
            h, ys = h0, []
            for i in range(n):
                h, yc = chunk_step(h, jax.tree.map(lambda t: t[i], xs))
                ys.append(yc)
            y_cross = jnp.stack(ys, axis=1)
            h_fin = h
        else:
            h_fin, ys = lax.scan(chunk_step, h0, xs)
            y_cross = jnp.moveaxis(ys, 0, 1)

        y = (y_intra + y_cross).reshape(Bb, S, nh, hd).astype(x.dtype)
        return y + D[None, None, :, None] * x, h_fin.reshape(Bb, nh, hd, N)


def mamba_block(p, cfg, x, *, state=None, conv_state=None,
                return_state=False, unroll: bool = False, extra=None):
    """Mamba2 residual block, x + mixer(rms_norm(x)).  Training/prefill
    path (full sequence, chunkwise-parallel SSD) or, with
    ``state``/``conv_state``, single-token decode (sequential step).

    The layout of ``p`` (:func:`mamba_defs`) says which mixer: with
    ``conv_b``, the published grouped one.  ``extra``, where given, is
    added to the mixer's input and not to the residual (Zamba2's hybrid
    layer feeds its shared block's output in so)."""
    Bb, S, d = x.shape
    e = p["w_x"].shape[-1]
    hd = cfg.ssm_head_dim
    nh = e // hd
    W = cfg.ssm_conv_width
    grouped = "conv_b" in p
    G = p["w_B"].shape[-1] // cfg.ssm_state if grouped else 1
    h = rms_norm(x if extra is None else x + extra, p["ln"], cfg.norm_eps)
    z = jnp.einsum("bsd,de->bse", h, p["w_z"])
    xin = jnp.einsum("bsd,de->bse", h, p["w_x"])
    B_in = jnp.einsum("bsd,dn->bsn", h, p["w_B"])
    C_in = jnp.einsum("bsd,dn->bsn", h, p["w_C"])
    if grouped:                                          # conv over x, B, C
        xin = jnp.concatenate([xin, B_in, C_in], axis=-1)
    xin = shard(xin, "batch", "seq", "mlp")
    c = xin.shape[-1]
    # causal depthwise conv
    if conv_state is not None:                           # decode: (B, W-1, c)
        window = jnp.concatenate([conv_state, xin], axis=1)   # (B, W, c)
        new_conv = window[:, 1:]
        xc = jnp.einsum("bwe,we->be", window, p["conv_w"])[:, None]
    else:
        pad = jnp.zeros((Bb, W - 1, c), xin.dtype)
        win = jnp.concatenate([pad, xin], axis=1)
        xc = sum(win[:, i:i + S] * p["conv_w"][i] for i in range(W))
        new_conv = win[:, S:]                            # last W-1 inputs
    if grouped:
        xc = xc + p["conv_b"]
    xc = jax.nn.silu(xc)
    if grouped:
        xc, B_in, C_in = jnp.split(xc, [e, e + G * cfg.ssm_state], axis=-1)
        B_in = B_in.reshape(*B_in.shape[:2], G, -1)
        C_in = C_in.reshape(*C_in.shape[:2], G, -1)
    dt = jax.nn.softplus(jnp.einsum("bsd,dh->bsh", h, p["w_dt"])
                         + p["dt_bias"])
    y, h_fin = _mamba_scan(xc.reshape(Bb, -1, nh, hd), B_in, C_in, dt,
                           p["A_log"], p["D"], hd, h0=state, unroll=unroll)
    y = (y.reshape(Bb, -1, e) * jax.nn.silu(z)).reshape(Bb, -1, G, e // G)
    y = rms_norm(y, p["gn"].reshape(G, e // G), cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y.reshape(Bb, -1, e), p["w_out"])
    out = x + shard(out, "batch", "seq", "embed")
    if return_state:
        return out, h_fin, new_conv
    return out


# ---------------------------------------------------------------------------
# Zamba2 hybrid layer: a shared transformer block over concat(x, embedding)
# (arXiv:2411.15242), its adapter, and the Mamba2 layer it feeds
# ---------------------------------------------------------------------------


def zamba2_shared_defs(cfg) -> dict:
    """One weight set of Zamba2's shared block: attention from the 2d-wide
    concat(x, embedding) to the heads this chip holds, back to d; the
    gated-GELU MLP of the columns it holds, gate and up side by side."""
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    f = cfg.zamba2.mlp
    return {
        "attn": {"ln": ParamDef((2 * d,), ("embed",), init="ones"),
                 "wq": ParamDef((2 * d, H, hd), ("fsdp", "heads", "head_dim")),
                 "wk": ParamDef((2 * d, KV, hd),
                                ("fsdp", "kv_heads", "head_dim")),
                 "wv": ParamDef((2 * d, KV, hd),
                                ("fsdp", "kv_heads", "head_dim")),
                 "wo": ParamDef((H, hd, d), ("heads", "head_dim", "fsdp"))},
        "mlp": {"ln": ParamDef((d,), ("embed",), init="ones"),
                "w_gu": ParamDef((d, 2 * f), ("fsdp", "mlp")),
                "w_down": ParamDef((f, d), ("mlp", "fsdp"))},
    }


def zamba2_hybrid_defs(cfg) -> dict:
    """A hybrid layer's own weights: the LoRA adapter on the shared MLP's
    gate and up projection, the projection of the shared block's output
    into the mixer's input, and its Mamba2 layer."""
    z, d, r = cfg.zamba2, cfg.d_model, ZAMBA2_ADAPTER_RANK
    return {"mamba": mamba_defs(cfg, heads=z.ssm_heads, groups=z.ssm_groups),
            "lora_A": ParamDef((d, r), ("fsdp", None)),
            "lora_B": ParamDef((r, 2 * z.mlp), (None, "mlp")),
            "w_lin": ParamDef((d, d), ("fsdp", "embed"))}


def zamba2_attention(at, cfg, g, positions, *,
                     unroll: bool = False) -> jax.Array:
    """The shared block's attention of the normed concat ``g`` (B, S, 2d):
    rotary, causal, scores scaled by (hd/2)^-1/2.  Returns (B, S, d)."""
    q, k, v = _qkv(at, cfg, g, positions)
    o = mha(q, k, v, causal=True, q_chunk=cfg.attn_q_chunk, unroll=unroll,
            scale=1.0 / math.sqrt(cfg.hd / 2))
    return jnp.einsum("bshk,hkd->bsd", o, at["wo"])


def zamba2_mlp(mp, p, m) -> jax.Array:
    """The shared gated-GELU MLP of the normed ``m`` with the layer's LoRA
    adapter on its gate and up projection:
    T = (gelu(G) * U) W_down,  [G|U] = m W_gu + (m A) B."""
    lora = jnp.einsum("bsd,dr->bsr", m, p["lora_A"])
    gu = (jnp.einsum("bsd,df->bsf", m, mp["w_gu"])
          + jnp.einsum("bsr,rf->bsf", lora, p["lora_B"]))
    gate, up = jnp.split(shard(gu, "batch", "seq", "mlp"), 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd",
                      jax.nn.gelu(gate, approximate=False) * up, mp["w_down"])


def zamba2_hybrid_block(p, shared, cfg, x, x0, positions, *,
                        unroll: bool = False) -> jax.Array:
    """Zamba2's hybrid layer, with one weight set ``shared`` of the shared
    block and the layer's own ``p``:

        g = rms_norm(concat(x, x0));  m = rms_norm(Attn(g))
        out = x + Mamba2(rms_norm(x + T W_lin)),  T = zamba2_mlp(m)

    The Mamba2 layer runs under the named scope ``mamba2``."""
    g = rms_norm(jnp.concatenate([x, x0], axis=-1), shared["attn"]["ln"],
                 cfg.norm_eps)
    m = rms_norm(zamba2_attention(shared["attn"], cfg, g, positions,
                                  unroll=unroll),
                 shared["mlp"]["ln"], cfg.norm_eps)
    t = jnp.einsum("bsd,de->bse", zamba2_mlp(shared["mlp"], p, m),
                   p["w_lin"])
    with jax.named_scope("mamba2"):
        return mamba_block(p["mamba"], cfg, x, extra=t, unroll=unroll)


# ---------------------------------------------------------------------------
# xLSTM blocks
# ---------------------------------------------------------------------------


def mlstm_defs(cfg) -> dict:
    d = cfg.d_model
    e = 2 * d
    H = cfg.n_heads
    return {
        "ln": ParamDef((d,), ("embed",), init="ones"),
        "w_up": ParamDef((d, e), ("fsdp", "mlp")),      # pre up-projection
        "wq": ParamDef((e, e), ("mlp", "mlp")),
        "wk": ParamDef((e, e), ("mlp", "mlp")),
        "wv": ParamDef((e, e), ("mlp", "mlp")),
        "w_i": ParamDef((e, H), ("mlp", "heads")),
        "w_f": ParamDef((e, H), ("mlp", "heads")),
        "w_o": ParamDef((e, e), ("mlp", "mlp")),
        "w_down": ParamDef((e, d), ("mlp", "fsdp")),
    }


def _mlstm_chunkwise(q, k, v, it, ft, state, *, chunk: int,
                     unroll: bool = False):
    """Chunkwise-parallel mLSTM (stabilized linear attention).

    Sequential form: m_t = max(logf_t + m_{t-1}, i_t);
      C_t = e^{logf_t+m_{t-1}-m_t} C_{t-1} + e^{i_t-m_t} k_t v_t^T
      h_t = C_t q_t / max(|n_t q_t|, 1)
    With F_t = cumsum(logf) the stabilizer is m_t = max(F_t + M_in,
    F_t + cummax_s(i_s - F_s)) — computable in parallel per chunk, so the
    intra-chunk part is a masked matmul A_ts = (q_t.k_s) e^{F_t-F_s+i_s-m_t}
    (all exponents <= 0 by construction) and the carried state contributes
    e^{F_t + M_in - m_t} (S_in q_t).  State materializes once per chunk and
    the MXU does the rest — same shape as the chunkwise SSD (Mamba2) path.

    q,k,v: (B,S,H,hd) in their own dtype (products with the f32 state and
    intra-chunk terms are f32, and so are q.k and k v^T);
    it,ft: (B,S,H) f32 raw gates.  state = (C, n, m), f32.
    Returns (y (B,S,H,hd) in v's dtype, new_state).
    """
    with jax.named_scope("mlstm_cell"):
        Bb, S, H, hd = q.shape
        n = S // chunk
        f32 = jnp.float32
        qc = q.reshape(Bb, n, chunk, H, hd)
        kc = k.reshape(Bb, n, chunk, H, hd)
        vc = v.reshape(Bb, n, chunk, H, hd)
        ic = it.reshape(Bb, n, chunk, H)
        logf = -jax.nn.softplus(-ft).reshape(Bb, n, chunk, H)
        F = jnp.cumsum(logf, axis=2)                          # (B,n,c,H)
        Gmax = jax.lax.cummax(ic - F, axis=2)             # cummax(i_s - F_s)

        C_in, n_in, m_in = state

        def chunk_step(carry, inp):
            C, nv, M = carry                      # (B,H,hd,hd),(B,H,hd),(B,H)
            qt, kt, vt, i_t, F_t, Gm = inp        # k pre-scaled by 1/sqrt(hd)
            # stabilizer per position: m_t = F_t + max(M_in, cummax_s(i_s-F_s))
            m = F_t + jnp.maximum(M[:, None], Gm)             # (B,c,H)
            # intra-chunk masked scores A_ts = (q_t.k_s) e^{F_t-F_s+i_s-m_t}
            ratio = F_t[:, :, None] - F_t[:, None, :] + i_t[:, None, :] \
                - m[:, :, None]                               # (B,t,s,H)
            tri = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
            ratio = jnp.where(tri[None, :, :, None], ratio, -1e30)
            a = jnp.einsum("bthd,bshd->bhts", qt, kt,
                           preferred_element_type=f32)
            A = a * jnp.moveaxis(jnp.exp(ratio), 3, 1)        # (B,H,t,s)
            num_intra = jnp.einsum("bhts,bshd->bthd", A, vt)
            den_intra = jnp.moveaxis(jnp.sum(A, axis=3), 1, 2)  # (B,t,H)
            # cross-chunk contribution, decayed from the carried state
            w_in = jnp.exp(F_t + M[:, None] - m)              # (B,c,H)
            num_cross = jnp.einsum("bhkv,bthk->bthv", C, qt) * w_in[..., None]
            den_cross = jnp.einsum("bhk,bthk->bth", nv, qt) * w_in
            num = num_intra + num_cross
            den = jnp.abs(den_intra + den_cross)
            y = num / jnp.maximum(den, 1.0)[..., None]
            # state update to chunk end
            m_out = m[:, -1]                                  # (B,H)
            Fc = F_t[:, -1]                                   # (B,H)
            wS = jnp.exp(Fc + M - m_out)
            wk = jnp.exp(Fc[:, None] - F_t + i_t - m_out[:, None])  # (B,c,H)
            C_new = C * wS[..., None, None] + jnp.einsum(
                "bshk,bshv,bsh->bhkv", kt, vt, wk, preferred_element_type=f32)
            n_new = nv * wS[..., None] + jnp.einsum("bshk,bsh->bhk", kt, wk)
            return (C_new, n_new, m_out), y.astype(vt.dtype)

        xs = (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(kc, 1, 0),
              jnp.moveaxis(vc, 1, 0), jnp.moveaxis(ic, 1, 0),
              jnp.moveaxis(F, 1, 0), jnp.moveaxis(Gmax, 1, 0))
        if unroll and n <= 128:   # probe path; longer sequences would blow up
            carry, ys = (C_in, n_in, m_in), []   # the unrolled HLO
            for i in range(n):
                carry, y = chunk_step(carry, jax.tree.map(lambda t: t[i], xs))
                ys.append(y)
            y = jnp.stack(ys, axis=1)
        else:
            carry, ys = lax.scan(chunk_step, (C_in, n_in, m_in), xs)
            y = jnp.moveaxis(ys, 0, 1)
        return y.reshape(Bb, S, H, hd), carry


def mlstm_chunk(S: int, hd: int) -> int | None:
    """Chunk length of :func:`_mlstm_chunkwise` for ``S`` tokens of head
    dim ``hd``, or None where the sequential scan runs (decode, odd lengths).

    Each chunk reads and writes the (hd, hd) f32 state once, against c·hd
    values per head of q, k and v, so the chunk grows with the head: the
    smallest power of two >= hd/2 within [64, 256], halved until it
    divides S with S > c."""
    c = 64
    while c < hd / 2 and c < 256:
        c *= 2
    while c >= 64:
        if S % c == 0 and S > c:
            return c
        c //= 2
    return None


def mlstm_block(p, cfg, x, *, state=None, return_state=False,
                unroll: bool = False):
    """mLSTM: matrix-memory recurrent block (xLSTM).

    Training/prefill uses the chunkwise-parallel stabilized linear-attention
    form (:func:`_mlstm_chunkwise`, state materialized once per chunk, MXU
    matmuls); decode/odd lengths fall back to the sequential scan."""
    Bb, S, d = x.shape
    H = cfg.n_heads
    e = p["w_up"].shape[1]
    hd = e // H
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    u = jax.nn.silu(jnp.einsum("bsd,de->bse", h, p["w_up"]))
    q = jnp.einsum("bse,ef->bsf", u, p["wq"]).reshape(Bb, S, H, hd)
    k = jnp.einsum("bse,ef->bsf", u, p["wk"]).reshape(Bb, S, H, hd) \
        / math.sqrt(hd)
    v = jnp.einsum("bse,ef->bsf", u, p["wv"]).reshape(Bb, S, H, hd)
    it = jnp.einsum("bse,eh->bsh", u, p["w_i"]).astype(jnp.float32)
    ft = jnp.einsum("bse,eh->bsh", u, p["w_f"]).astype(jnp.float32)

    def step(carry, inp):
        C, n, m = carry                                  # (B,H,hd,hd),(B,H,hd),(B,H)
        qt, kt, vt, i_t, f_t = inp
        logf = -jax.nn.softplus(-f_t)                    # log sigmoid(f)
        m_new = jnp.maximum(logf + m, i_t)
        fg = jnp.exp(logf + m - m_new)[..., None]
        ig = jnp.exp(i_t - m_new)[..., None]
        C = C * fg[..., None] + ig[..., None] * \
            (kt[..., :, None] * vt[..., None, :]).astype(jnp.float32)
        n = n * fg + ig * kt.astype(jnp.float32)
        num = jnp.einsum("bhkv,bhk->bhv", C, qt.astype(jnp.float32))
        den = jnp.abs(jnp.einsum("bhk,bhk->bh", n, qt.astype(jnp.float32)))
        y = num / jnp.maximum(den, 1.0)[..., None]
        return (C, n, m_new), y.astype(x.dtype)

    if state is None:
        state = (jnp.zeros((Bb, H, hd, hd), jnp.float32),
                 jnp.zeros((Bb, H, hd), jnp.float32),
                 jnp.full((Bb, H), -1e30, jnp.float32))
    chunk = mlstm_chunk(S, hd)
    if chunk:
        ys, state = _mlstm_chunkwise(q, k, v, it, ft, state,
                                     chunk=chunk, unroll=unroll)
        y = ys.astype(x.dtype).reshape(Bb, S, e)
    else:
        xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, it, ft))
        state, ys = chunked_time_scan(step, state, xs)
        y = jnp.moveaxis(ys, 0, 1).reshape(Bb, S, e)
    y = y * jax.nn.silu(jnp.einsum("bse,ef->bsf", u, p["w_o"]))
    out = x + jnp.einsum("bse,ed->bsd", y, p["w_down"])
    if return_state:
        return out, state
    return out


def slstm_defs(cfg) -> dict:
    d = cfg.d_model
    H = cfg.n_heads
    hd = d // H
    f = int(4 * d / 3 / 64) * 64 or 64
    return {
        "ln": ParamDef((d,), ("embed",), init="ones"),
        "w_zifo": ParamDef((d, 4 * d), ("fsdp", "mlp")),
        "r_zifo": ParamDef((H, hd, 4 * hd), ("heads", "head_dim", None),
                           scale=0.1),
        "gn": ParamDef((d,), ("embed",), init="ones"),
        "w_up": ParamDef((d, 2 * f), ("fsdp", "mlp")),
        "w_down": ParamDef((f, d), ("mlp", "fsdp")),
    }


def slstm_block(p, cfg, x, *, state=None, return_state=False):
    """sLSTM: scalar-memory recurrent block with block-diagonal recurrence
    and exponential gating, followed by a gated up/down MLP (xLSTM)."""
    Bb, S, d = x.shape
    H = cfg.n_heads
    hd = d // H
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    zifo = jnp.einsum("bsd,df->bsf", h, p["w_zifo"])     # (B,S,4d)

    def step(carry, inp):
        c, n, hprev, m = carry                           # (B,H,hd)x3,(B,H)
        g = inp.reshape(Bb, H, 4 * hd) + jnp.einsum(
            "bhk,hkf->bhf", hprev, p["r_zifo"])
        zt, it, ft, ot = jnp.split(g.astype(jnp.float32), 4, axis=-1)
        it, ft = it.mean(-1), ft.mean(-1)                # scalar gates per head
        logf = -jax.nn.softplus(-ft)
        m_new = jnp.maximum(logf + m, it)
        fg = jnp.exp(logf + m - m_new)[..., None]
        ig = jnp.exp(it - m_new)[..., None]
        c = c * fg + ig * jnp.tanh(zt)
        n = n * fg + ig
        hn = jax.nn.sigmoid(ot) * c / jnp.maximum(n, 1.0)
        return (c, n, hn.astype(x.dtype), m_new), hn.astype(x.dtype)

    if state is None:
        z32 = lambda: jnp.zeros((Bb, H, hd), jnp.float32)
        state = (z32(), z32(), jnp.zeros((Bb, H, hd), x.dtype),
                 jnp.full((Bb, H), -1e30, jnp.float32))
    with jax.named_scope("slstm_scan"):
        state, ys = chunked_time_scan(step, state,
                                      jnp.moveaxis(zifo, 1, 0))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bb, S, d)
    y = rms_norm(y, p["gn"], cfg.norm_eps)
    up, gate = jnp.split(jnp.einsum("bsd,df->bsf", y, p["w_up"]), 2, -1)
    y2 = jnp.einsum("bsf,fd->bsd", up * jax.nn.gelu(gate, approximate=True),
                    p["w_down"])
    out = x + y2
    if return_state:
        return out, state
    return out


# ---------------------------------------------------------------------------
# Cross-attention block (VLM / whisper decoder)
# ---------------------------------------------------------------------------


def cross_attn_defs(cfg) -> dict:
    H, KV, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model
    return {
        "ln": ParamDef((d,), ("embed",), init="ones"),
        "wq": ParamDef((d, H, hd), ("fsdp", "heads", "head_dim")),
        "wk": ParamDef((d, KV, hd), ("fsdp", "kv_heads", "head_dim")),
        "wv": ParamDef((d, KV, hd), ("fsdp", "kv_heads", "head_dim")),
        "wo": ParamDef((H, hd, d), ("heads", "head_dim", "fsdp")),
        "gate": ParamDef((1,), (None,), init="zeros"),   # llama-vision tanh gate
    }


def cross_attn_block(p, cfg, x, memory, *, unroll: bool = False) -> jax.Array:
    """Attend from x to an encoder/vision memory sequence (not causal)."""
    B, S, d = x.shape
    KV, hd, H = cfg.n_kv_heads, cfg.hd, cfg.n_heads
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"]).reshape(B, S, KV, H // KV, hd)
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"])
    o = mha(q, k, v, causal=False, q_chunk=cfg.attn_q_chunk, unroll=unroll)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return x + jnp.tanh(p["gate"].astype(x.dtype)) * o


# ---------------------------------------------------------------------------
# Embedding / unembedding / loss
# ---------------------------------------------------------------------------


def embed_defs(cfg) -> dict:
    return {"tok": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                            scale=0.02)}


def embed(p, cfg, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0).astype(cfg.jnp_dtype)
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    return shard(x, "batch", "seq", "embed")


def logits_chunked(x: jax.Array, emb: jax.Array, cfg,
                   chunk: int = 512) -> jax.Array:
    """(B,S,d) @ (V,d)^T in seq chunks; full logits only for small V use."""
    logits = jnp.einsum("bsd,vd->bsv", x, emb.astype(x.dtype))
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return shard(logits, "batch", "seq", "vocab")


def xent_loss(x: jax.Array, emb: jax.Array, labels: jax.Array, cfg,
              chunk: int = 256) -> jax.Array:
    """Chunked cross-entropy: never materializes (B,S,V) at once.

    x: (B,S,d) final hidden; emb: (V,d) tied unembedding; labels: (B,S).
    Label -100 entries are masked out.
    """
    B, S, d = x.shape
    cs = min(chunk, S)
    while S % cs:
        cs -= 1

    def one(i):
        xs = lax.dynamic_slice_in_dim(x, i * cs, cs, axis=1)
        ls = lax.dynamic_slice_in_dim(labels, i * cs, cs, axis=1)
        lg = jnp.einsum("bsd,vd->bsv", xs, emb.astype(xs.dtype))
        if cfg.logit_softcap:
            lg = jnp.tanh(lg / cfg.logit_softcap) * cfg.logit_softcap
        lg = shard(lg, "batch", "seq", "vocab").astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        pick = jnp.take_along_axis(
            lg, jnp.maximum(ls, 0)[..., None], axis=-1)[..., 0]
        mask = (ls >= 0).astype(jnp.float32)
        return jnp.sum((lse - pick) * mask), jnp.sum(mask)

    tot, cnt = jnp.zeros(()), jnp.zeros(())
    for i in range(S // cs):     # static python loop: cs chosen so few chunks
        a, b = one(i)
        tot, cnt = tot + a, cnt + b
    return tot / jnp.maximum(cnt, 1.0)
