"""Token embedding and the tied output projection with cross-entropy.

    x_0 = E[tokens];   loss = sum_t (logsumexp(x_t E^T) - (x_t E^T)[label_t])

The sum runs over the rows given; the caller divides by the batch's
token count.  Logits are formed a block of positions at a time so that a
(rows, positions, vocab) array never sits in memory whole.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32

BLOCK = 512


def param_shapes(cfg):
    d = cfg["d_model"]
    return {"embed": {"tok": ((cfg["vocab"], d), 0.02)},
            "final_norm": ((d,), "ones")}


def embed(p, tokens):
    return jnp.take(p["tok"].astype(F32), tokens, axis=0)


def xent_sum(p, x, labels, mm):
    S = x.shape[1]

    @jax.checkpoint
    def part(x_blk, lab_blk):
        logits = mm("bsd,vd->bsv", x_blk, p["tok"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, lab_blk[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - pick)

    total = jnp.zeros((), F32)
    for lo in range(0, S, min(BLOCK, S)):
        total = total + part(x[:, lo:lo + BLOCK], labels[:, lo:lo + BLOCK])
    return total
