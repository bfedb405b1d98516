"""Zamba2's hybrid layer (arXiv:2411.15242; Zyphra/Zamba2-7B's config.json
and the transformers ``Zamba2HybridLayer``), at the share of the layer that
one chip holds.  ``x0`` is the token embedding, carried unchanged through
the model; the layer in cycle ``c`` uses shared weight set c mod sets.

    g = rms_norm_2d(concat(x, x0))                         width 2d
    a = Attn(g)     q, k, v: 2d -> heads of head_dim, rotary (theta
                    rope_theta), causal softmax, scale (head_dim / 2)^-1/2;
                    o: heads -> d
    m = rms_norm(a)
    T = (gelu(G) * U) W_down,   [G | U] = m W_gu + (m A) B   (LoRA, rank 128)
    out = x + Mamba2(rms_norm(x + T W_lin))       (``mamba2.py``; T enters
                                                   the mixer's input only)

GELU is exact (erf).  No linear layer has a bias.  The attention runs one
block of queries after another, and the MLP one block of positions after
another (``by_blocks``), each recomputed in the backward pass, so that the
reference fits in the chip's memory beside its optimizer state.

Tensor parallelism splits the attention by heads and the MLP by columns
(gate and up alike, and the adapter's B with them); the chip holds
``n_heads`` heads and 1/ranks of the ``d_ff`` columns, so its output is
its rank's partial sum, as the program's is.
A pipeline stage holds the shared sets its hybrid layers use (its j-th
uses set j mod 2): as many as it has cycles, at most 2.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import mamba2
from .common import F32, rms_norm

KEY = None          # the adapter, W_lin and the Mamba2 layer sit in the slot
INPUTS = ("x0", "cycle")
SETS = 2            # num_mem_blocks of the published model
RANK = 128          # adapter_rank of the published model
BLOCK = 512         # queries, and positions of the MLP, at a time


def mlp_columns(cfg) -> int:
    return cfg["d_ff"] // mamba2.share(cfg)["ranks"]


def param_shapes(cfg):
    d, f = cfg["d_model"], mlp_columns(cfg)
    return {"mamba": mamba2.param_shapes(cfg),
            "lora_A": ((d, RANK), None), "lora_B": ((RANK, 2 * f), None),
            "w_lin": ((d, d), None)}


def shared_shapes(cfg):
    """The shared sets, stacked: each weight with its own fan-in's
    scale."""
    d, H, KV, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    f = mlp_columns(cfg)
    sets = min(SETS, cfg["n_layers"] // len(cfg["block_pattern"]))

    def w(shape, fan_in):
        return ((sets, *shape), 1.0 / math.sqrt(fan_in))

    return {"attn": {"ln": ((sets, 2 * d), "ones"),
                     "wq": w((2 * d, H, hd), 2 * d),
                     "wk": w((2 * d, KV, hd), 2 * d),
                     "wv": w((2 * d, KV, hd), 2 * d),
                     "wo": w((H, hd, d), H * hd)},
            "mlp": {"ln": ((sets, d), "ones"), "w_gu": w((d, 2 * f), d),
                    "w_down": w((f, d), f)}}


def rope(x, theta):
    """Rotary embedding over the last axis of (B, S, H, hd): the first
    half of each head pairs with the second half."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def by_blocks(f, *xs):
    """``f`` over blocks of ``BLOCK`` positions (axis 1) of each of ``xs``,
    one block after another, each recomputed in the backward pass; the
    results joined along positions."""
    B, S = xs[0].shape[:2]
    n = S // min(BLOCK, S)
    blocks = [jnp.moveaxis(x.reshape(B, n, S // n, *x.shape[2:]), 1, 0)
              for x in xs]
    out = jax.lax.map(jax.checkpoint(lambda a: f(*a)), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, *out.shape[3:])


def attention(p, g, cfg, mm):
    B, S = g.shape[:2]
    scale = 1.0 / math.sqrt(cfg["head_dim"] / 2)
    q = rope(mm("bsd,dhk->bshk", g, p["wq"]), cfg["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", g, p["wk"]), cfg["rope_theta"])
    v = mm("bsd,dhk->bshk", g, p["wv"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    pos = jnp.arange(S)

    def rows(q_blk, qp):
        s = mm("bqhk,bshk->bhqs", q_blk, k) * scale
        s = jnp.where(qp[0, :, None] >= pos[None], s, -jnp.inf)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return mm("bhqs,bshk->bqhk", w / jnp.sum(w, axis=-1, keepdims=True),
                  v)

    o = by_blocks(rows, q, jnp.broadcast_to(pos, (B, S)))
    return mm("bshk,hkd->bsd", o, p["wo"])


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def mlp(s, p, m, mm):
    """T: the shared MLP of set ``s`` with the layer's adapter."""

    def rows(m_blk):
        gu = mm("bsd,df->bsf", m_blk, s["w_gu"]) + mm(
            "bsr,rf->bsf", mm("bsd,dr->bsr", m_blk, p["lora_A"]), p["lora_B"])
        gate, up = jnp.split(gu, 2, axis=-1)
        return mm("bsf,fd->bsd", gelu(gate) * up, s["w_down"])

    return by_blocks(rows, m)


def block(p, x, cfg, mm, shared=None, *, x0, cycle):
    s = jax.tree.map(lambda a: a[cycle % a.shape[0]], shared)
    eps = cfg["norm_eps"]
    g = rms_norm(jnp.concatenate([x, x0], axis=-1), s["attn"]["ln"], eps)
    m = rms_norm(attention(s["attn"], g, cfg, mm), s["mlp"]["ln"], eps)
    t = mm("bsd,de->bse", mlp(s["mlp"], p, m, mm), p["w_lin"])
    return mamba2.block(p["mamba"], x, cfg, mm, extra=t)
