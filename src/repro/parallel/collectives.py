"""Explicit collective schedules (paper §2.3 Fig. 3) + gradient compression.

The paper's decomposition argument — all-reduce = reduce-scatter +
all-gather removes the single-root bottleneck — maps 1:1 onto
``lax.psum_scatter`` + ``lax.all_gather`` inside ``shard_map``.  The main
train step gets this implicitly through ZeRO-1 sharding (GSPMD emits RS+AG
when optimizer moments are sharded over "data"); these explicit versions are
used by the benchmark reproducing Fig. 3 and by the gradient-compression
path (int8 + error feedback, a beyond-paper extension for the slow DCI
inter-pod edge).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


# -- inside-shard_map primitives --------------------------------------------


def allreduce_naive(x: jax.Array, axis: str) -> jax.Array:
    """Single fused all-reduce (the baseline schedule)."""
    return lax.psum(x, axis)


def allreduce_decomposed(x: jax.Array, axis: str) -> jax.Array:
    """reduce-scatter + all-gather over the leading dim (Fig. 3 right).

    Requires dim0 % axis_size == 0 — the caller pads (see
    :func:`sync_grads`)."""
    s = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    return lax.all_gather(s, axis, axis=0, tiled=True)


def _quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def allreduce_int8(x: jax.Array, axis: str,
                   err: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Int8-compressed all-reduce with error feedback.

    Wire volume drops 4x (modeled in the planner's cost model; on the
    emulated mesh we keep numerics faithful: quantize locally, sum the
    dequantized values, and fold the quantization residual into ``err`` so
    it is re-applied next step — convergence-neutral in expectation)."""
    g = x + err
    q, scale = _quantize_int8(g)
    deq = q.astype(x.dtype) * scale
    new_err = g - deq
    return lax.psum(deq, axis), new_err


# -- pytree-level gradient sync ---------------------------------------------


def sync_grads(grads: Pytree, mesh: Mesh, axis: str = "data", *,
               schedule: str = "rs_ag",
               err: Pytree | None = None) -> tuple[Pytree, Pytree | None]:
    """Mean-reduce grads across ``axis`` with an explicit schedule.

    schedule: "allreduce" | "rs_ag" | "int8".  Returns (grads, new_err);
    ``err`` must be a zeros-like tree for "int8" (error feedback state).
    """
    n = mesh.shape[axis]

    def one(g, e):
        def inner(gl, el):
            if schedule == "allreduce":
                return allreduce_naive(gl, axis) / n, el
            if schedule == "rs_ag":
                flat = gl.reshape(-1)
                pad = (-flat.shape[0]) % n
                flat = jnp.pad(flat, (0, pad))
                out = allreduce_decomposed(flat, axis) / n
                return out[:flat.shape[0] - pad].reshape(gl.shape) \
                    if pad else out.reshape(gl.shape), el
            if schedule == "int8":
                s, ne = allreduce_int8(gl, axis, el)
                return s / n, ne
            raise ValueError(schedule)

        spec = P()  # grads replicated across the sync axis
        f = shard_map(inner, mesh=mesh, in_specs=(spec, spec),
                      out_specs=(spec, spec), check_vma=False)
        return f(g, e)

    es = err if err is not None else jax.tree.map(jnp.zeros_like, grads)
    pairs = jax.tree.map(one, grads, es)
    synced = jax.tree.map(lambda p: p[0], pairs,
                          is_leaf=lambda x: isinstance(x, tuple)
                          and len(x) == 2 and isinstance(x[0], jax.Array))
    new_err = jax.tree.map(lambda p: p[1], pairs,
                           is_leaf=lambda x: isinstance(x, tuple)
                           and len(x) == 2 and isinstance(x[0], jax.Array))
    return synced, (new_err if schedule == "int8" else None)
