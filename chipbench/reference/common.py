"""Plain float32 pieces shared by the reference blocks.

Every reference block takes a matmul ``mm(spec, a, b)``.  ``exact_mm``
computes in float32 at the highest precision; ``fp8_mm`` first rounds each
operand to float8 (e4m3, scaled per tensor to its largest magnitude), which
is the control: the reference one precision below the bfloat16 that the
configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def exact_mm(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST,
                      preferred_element_type=F32)


def _fp8(x: jax.Array) -> jax.Array:
    x = x.astype(F32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    # the rounding is the control's error; its gradient passes straight
    return x + lax.stop_gradient(q - x)


def fp8_mm(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return exact_mm(spec, _fp8(a), _fp8(b))


MATMULS = {"float32": exact_mm, "fp8": fp8_mm}


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x = x.astype(F32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def silu(x: jax.Array) -> jax.Array:
    return x / (1.0 + jnp.exp(-x))


def log_sigmoid(x: jax.Array) -> jax.Array:
    # log(1 / (1 + e^-x)), written so that neither branch overflows
    return jnp.minimum(x, 0.0) - jnp.log1p(jnp.exp(-jnp.abs(x)))


def gelu_tanh(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def softplus(x: jax.Array) -> jax.Array:
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def time_scan(step, carry, xs, *, block: int = 64):
    """``lax.scan(step, carry, xs)`` over time-major ``xs``, storing only
    every ``block``-th carry for the backward pass (each block of steps is
    recomputed there), so that a 4096-step recurrence fits in memory."""
    S = jax.tree.leaves(xs)[0].shape[0]
    if S % block or S <= block:
        return lax.scan(step, carry, xs)
    n = S // block
    blocks = jax.tree.map(lambda x: x.reshape(n, block, *x.shape[1:]), xs)
    inner = jax.checkpoint(lambda c, x: lax.scan(step, c, x))
    carry, ys = lax.scan(inner, carry, blocks)
    return carry, jax.tree.map(lambda y: y.reshape(S, *y.shape[2:]), ys)
