"""Zamba2's shared transformer block (arXiv:2411.15242, section 2), at one
of its occurrences.

    a = x A_occ                                  (the occurrence's adapter)
    a = a + MHA(rms_norm(a))                     (shared weights, rotary,
                                                  causal, sliding window)
    a = a + (silu(g W_gate) * (g W_up)) W_down,  g = rms_norm(a)
    out = x + a

Departures from the published model, as the repo's model has them: the
shared block follows every third layer where Zamba2-2.7B has it every
sixth; one shared weight set where the model alternates two; the adapter
is a full d x d matrix where the model uses LoRA adapters; the block sees
x alone where the model concatenates x with the original embedding.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, rms_norm, silu

Q_BLOCK = 1024


KEY = None          # the occurrence's adapter sits directly in its slot


def param_shapes(cfg):
    d = cfg["d_model"]
    return {"in_proj": ((d, d), 0.02)}


def shared_shapes(cfg):
    d, H, KV, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                   cfg["d_ff"])
    hd = cfg["head_dim"] or d // H
    return {"attn": {"ln": ((d,), "ones"), "wq": ((d, H, hd), None),
                     "wk": ((d, KV, hd), None), "wv": ((d, KV, hd), None),
                     "wo": ((H, hd, d), None)},
            "ffn": {"ln": ((d,), "ones"), "w_up": ((d, f), None),
                    "w_down": ((f, d), None), "w_gate": ((d, f), None)}}


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


def attention(p, x, cfg, mm):
    """Causal softmax attention, one block of queries at a time (each
    recomputed in the backward pass), so that the scores of a whole
    sequence never sit in memory at once."""
    B, S, _ = x.shape
    hd = cfg["head_dim"] or cfg["d_model"] // cfg["n_heads"]
    q = rope(mm("bsd,dhk->bshk", x, p["wq"]), cfg["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", x, p["wk"]), cfg["rope_theta"])
    v = mm("bsd,dhk->bshk", x, p["wv"])
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    window = cfg["attn_window"] or S
    pos = jnp.arange(S)

    @jax.checkpoint
    def rows(q_blk, qp):
        s = mm("bqhk,bshk->bhqs", q_blk, k) / math.sqrt(hd)
        keep = (qp[:, None] >= pos[None]) & (qp[:, None] - pos[None] < window)
        s = jnp.where(keep, s, -jnp.inf)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return mm("bhqs,bshk->bqhk", w / jnp.sum(w, axis=-1, keepdims=True),
                  v)

    outs = [rows(q[:, lo:lo + Q_BLOCK], pos[lo:lo + Q_BLOCK])
            for lo in range(0, S, min(Q_BLOCK, S))]
    return mm("bshk,hkd->bsd", jnp.concatenate(outs, axis=1), p["wo"])


def ffn(p, x, cfg, mm):
    g = rms_norm(x, p["ln"], cfg["norm_eps"])
    up = mm("bsd,df->bsf", g, p["w_up"]) * silu(mm("bsd,df->bsf", g,
                                                   p["w_gate"]))
    return x + mm("bsf,fd->bsd", up, p["w_down"])


def block(p, x, cfg, mm, shared=None):
    a = mm("bsd,de->bse", x, p["in_proj"])
    a = a + attention(shared["attn"], rms_norm(a, shared["attn"]["ln"],
                                               cfg["norm_eps"]), cfg, mm)
    return x + ffn(shared["ffn"], a, cfg, mm)
