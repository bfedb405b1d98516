"""Token embedding and the output projection with cross-entropy.

    x_0 = E[tokens];   loss = sum_t (logsumexp(x_t W^T) - (x_t W^T)[label_t])

W is the embedding E itself where the configuration ties them
(``tie_embeddings`` true or not given, as ``ArchConfig`` defaults), and
otherwise a head of its own, ``embed.head`` of shape (vocab, d_model): the
program must lay an untied head out at ``params["embed"]["head"]``.  The
sum runs over the rows given; the caller divides by the batch's token
count.  Logits are formed a block of positions at a time so that a (rows,
positions, vocab) array never sits in memory whole.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32

BLOCK = 512


def param_shapes(cfg):
    d = cfg["d_model"]
    emb = {"tok": ((cfg["vocab"], d), 0.02)}
    if not cfg.get("tie_embeddings", True):
        emb["head"] = ((cfg["vocab"], d), 0.02)
    return {"embed": emb, "final_norm": ((d,), "ones")}


def embed(p, tokens):
    return jnp.take(p["tok"].astype(F32), tokens, axis=0)


def xent_sum(p, x, labels, mm):
    S = x.shape[1]
    w = p.get("head", p["tok"])

    @jax.checkpoint
    def part(x_blk, lab_blk):
        logits = mm("bsd,vd->bsv", x_blk, w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, lab_blk[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - pick)

    total = jnp.zeros((), F32)
    for lo in range(0, S, min(BLOCK, S)):
        total = total + part(x[:, lo:lo + BLOCK], labels[:, lo:lo + BLOCK])
    return total
