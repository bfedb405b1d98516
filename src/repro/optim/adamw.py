"""AdamW with cosine schedule, global-norm clipping and ZeRO-1 sharding.

Functional, pytree-based (no optax dependency).  First/second moments are
kept in fp32; parameters may be bf16.  ZeRO-1: the optimizer-state sharding
tree returned by :func:`repro.parallel.sharding.opt_state_shardings` shards
moments over the data axis even when parameters are not — GSPMD then emits
exactly the reduce-scatter + all-gather decomposition of the gradient
all-reduce that the paper's Fig. 3 advocates.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

Pytree = Any


@dataclass(frozen=True)
class AdamWConfig:
    peak_lr: float = 3e-4
    min_lr_frac: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def cosine_lr(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = cfg.peak_lr * step / max(cfg.warmup_steps, 1)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.min_lr_frac * cfg.peak_lr + \
        (1 - cfg.min_lr_frac) * cfg.peak_lr * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < cfg.warmup_steps, warm, cos)


class OptState(NamedTuple):
    m: Pytree
    v: Pytree
    step: jax.Array


def init_opt_state(params: Pytree) -> OptState:
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return OptState(m=jax.tree.map(z, params), v=jax.tree.map(z, params),
                    step=jnp.zeros((), jnp.int32))


def abstract_opt_state(params: Pytree) -> OptState:
    z = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return OptState(m=jax.tree.map(z, params), v=jax.tree.map(z, params),
                    step=jax.ShapeDtypeStruct((), jnp.int32))


def global_norm(tree: Pytree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def adamw_update(params: Pytree, grads: Pytree, state: OptState,
                 cfg: AdamWConfig) -> tuple[Pytree, OptState, dict]:
    """One AdamW step.  Returns (new_params, new_state, metrics)."""
    with jax.named_scope("adamw"):
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
        step = state.step + 1
        lr = cosine_lr(cfg, step)
        b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
        b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * scale
            m = cfg.b1 * m + (1 - cfg.b1) * g
            v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
            mhat = m / b1c
            vhat = v / b2c
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps) \
                + cfg.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_m = jax.tree.leaves(state.m)
        flat_v = jax.tree.leaves(state.v)
        out = [upd(p, g, m, v) for p, g, m, v in
               zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = jax.tree.unflatten(tdef, [o[0] for o in out])
        new_m = jax.tree.unflatten(tdef, [o[1] for o in out])
        new_v = jax.tree.unflatten(tdef, [o[2] for o in out])
        metrics = {"grad_norm": gnorm, "lr": lr}
        return new_p, OptState(new_m, new_v, step), metrics
