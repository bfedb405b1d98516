"""Pallas TPU fused RMSNorm: one HBM read, normalize+scale in VMEM.

Grid tiles rows (tokens); the feature dim rides the 128-lane minor axis in
one VMEM block (d_model ≤ a few K fits comfortably).  fp32 accumulation for
the mean-square reduction regardless of input dtype.  The row block is the
whole row count or a multiple of 8 (the TPU sublane tiling); a row count it
does not divide gets a partial last block, whose out-of-range rows are never
written back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                  # (rows, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: (..., d); w: (d,).  Returns same shape/dtype as x."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = rows if rows <= block_rows else max(8, block_rows // 8 * 8)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, w)
    return out.reshape(shape)
