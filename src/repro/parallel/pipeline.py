"""shard_map pipeline parallelism with uneven (planner-chosen) stages.

The planner assigns *contiguous layer counts per stage* (possibly uneven —
its heterogeneity mechanism, paper §4.1).  All pipeline ranks run the same
program under ``shard_map`` over a "pipe" mesh axis, so uneven stages are
expressed by padding every stage to ``max_layers`` and masking the padding
layers to identity:

  stage_params: pytree with leading (n_stages, max_layers, ...) sharded over
  "pipe"; layer_mask: (n_stages, max_layers) bool.

Schedule: GPipe-style microbatch loop over ``lax.ppermute`` — activations
flow stage→stage+1; JAX autodiff transposes ppermute to the reverse
permutation, so one ``jax.grad`` of :func:`pipeline_forward` yields the
backward pipeline for free.  (The simulator models 1F1B for *timing*; the
numerics here are schedule-independent.)
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


def pad_stages(per_layer_params: Pytree, sizes: list[int]) -> tuple[Pytree,
                                                                    jax.Array]:
    """Regroup a per-layer stacked pytree (L, ...) into padded stages.

    Returns (stage_params (S, Lmax, ...), layer_mask (S, Lmax))."""
    S = len(sizes)
    Lmax = max(sizes)
    starts = [sum(sizes[:i]) for i in range(S)]

    def regroup(x):
        out = []
        for s in range(S):
            sl = x[starts[s]:starts[s] + sizes[s]]
            pad = jnp.zeros((Lmax - sizes[s], *x.shape[1:]), x.dtype)
            out.append(jnp.concatenate([sl, pad], axis=0))
        return jnp.stack(out)

    mask = jnp.stack([jnp.arange(Lmax) < s for s in sizes])
    return jax.tree.map(regroup, per_layer_params), mask


def pipeline_forward(layer_fn: Callable, stage_params: Pytree,
                     layer_mask: jax.Array, x_mb: jax.Array, *,
                     mesh: Mesh, axis: str = "pipe") -> jax.Array:
    """Run microbatches through the pipeline.

    x_mb: (M, mb, ...) microbatched inputs (replicated across pipe ranks —
    only stage 0 reads them).  Returns (M, mb, ...) outputs (valid on the
    last rank; replicated back for convenience).
    """
    S = mesh.shape[axis]
    M = x_mb.shape[0]

    def stage_apply(params, mask, h):
        def body(carry, inp):
            p_l, m_l = inp
            out = layer_fn(p_l, carry)
            return jnp.where(m_l, out, carry), ()
        h, _ = lax.scan(body, h, (params, mask))
        return h

    def per_rank(params, mask, xs):
        sid = lax.axis_index(axis)
        params = jax.tree.map(lambda a: a[0], params)   # local (Lmax, ...)
        mask = mask[0]
        perm_fwd = [(i, i + 1) for i in range(S - 1)]
        state = jnp.zeros(xs.shape[1:], xs.dtype)
        outs = jnp.zeros_like(xs)
        # tick t: rank s computes microbatch m = t - s (garbage flows through
        # warmup/drain ticks but is never stored)
        for t in range(M + S - 1):
            h = jnp.where(sid == 0, xs[min(t, M - 1)], state)
            h = stage_apply(params, mask, h)
            out_idx = t - (S - 1)
            ok = (sid == S - 1) & (0 <= out_idx) & (out_idx < M)
            ci = min(max(out_idx, 0), M - 1)
            outs = outs.at[ci].set(jnp.where(ok, h, outs[ci]))
            if S > 1:
                state = lax.ppermute(h, axis, perm_fwd)
        # deliver collected outputs from the last rank to all ranks
        outs = lax.psum(jnp.where(sid == S - 1, outs,
                                  jnp.zeros_like(outs)), axis)
        return outs

    f = shard_map(per_rank, mesh=mesh,
                  in_specs=(P(axis), P(axis), P()),
                  out_specs=P(), check_vma=False)
    return f(stage_params, layer_mask, x_mb)
