"""AdamW (Loshchilov and Hutter, arXiv:1711.05101) with clipping of the
global gradient norm and a linear warm-up into a cosine decay, as the
configuration's ``opt`` states it.  Moments are float32; parameters are
kept in the configuration's dtype after each update.

    g <- g * min(1, clip / |g|)
    m <- b1 m + (1 - b1) g;   v <- b2 v + (1 - b2) g^2
    p <- p - lr_t ((m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps) + wd p)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32


def learning_rate(opt: dict, t: int) -> float:
    peak = opt["peak_lr"]
    if t < opt["warmup_steps"]:
        return peak * t / max(opt["warmup_steps"], 1)
    prog = min(max((t - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    lo = opt["min_lr_frac"] * peak
    return lo + (peak - lo) * 0.5 * (1 + math.cos(math.pi * prog))


def clipped(opt: dict, grads):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g.astype(F32) * scale, grads)


def coefficients(opt: dict, t: int) -> tuple[float, float, float]:
    """Learning rate and the two bias corrections of step ``t`` (from 1)."""
    return (learning_rate(opt, t), 1 - opt["b1"] ** t, 1 - opt["b2"] ** t)


def update(opt: dict, coef, params, grads, m, v):
    """One step on clipped ``grads``; ``coef`` is :func:`coefficients`."""
    lr, c1, c2 = coef
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def step(p, a, b):
        p32 = p.astype(F32)
        delta = (a / c1) / (jnp.sqrt(b / c2) + opt["eps"]) \
            + opt["weight_decay"] * p32
        return (p32 - lr * delta).astype(p.dtype)

    return jax.tree.map(step, params, m, v), m, v
