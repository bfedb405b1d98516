"""Pallas TPU flash attention (fused, online-softmax) with GQA/causal/window.

TPU-native adaptation of the paper's fusion example (§2.3, FlashAttention):
instead of a CUDA warp-level design, tiling follows the TPU memory hierarchy:

  * the call takes the model's (B, S, H, hd) layout and runs the kernel on
    head-major (B, H, S, hd) views, so every block is (1, 1, rows, hd):
    its last two axes are a multiple-of-8 sequence block and the full
    head_dim, which is what the TPU lowering accepts,
  * grid = (batch, q_heads, q_blocks, kv_blocks); the minor-most kv_blocks
    dimension iterates sequentially on a TensorCore, so fp32 running
    (acc, m, l) live in 2-D VMEM scratch across kv steps,
  * GQA is free: the kv BlockSpec index_map sends q-head h to kv-head
    h // (H // KV) — no repeated-KV materialization,
  * the S×S score matrix never touches HBM (the whole point).

Numerics follow the standard stable online softmax; the causal/window mask
is applied per tile from block-relative iotas.  Validated on CPU with
``interpret=True`` against ``ref.mha_reference`` (tests/test_kernels.py) and
compiled for a described TPU v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_kv: int, seq_q: int, seq_kv: int,
                  softcap: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    # positions: queries offset by (seq_kv - seq_q) (decode-style alignment)
    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + (seq_kv - seq_q)
    kpos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    masked = s
    if causal:
        masked = jnp.where(qpos >= kpos, masked, NEG_INF)
    if window:
        masked = jnp.where(qpos - kpos < window, masked, NEG_INF)
    s = masked

    m_prev = m_ref[...]                                 # (bq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)   # fully-masked rows stay zero
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_diff(q, k, v, causal, window, softcap, block_q, block_kv,
                interpret):
    return _flash_fwd_kernel_call(q, k, v, causal=causal, window=window,
                                  softcap=softcap, block_q=block_q,
                                  block_kv=block_kv, interpret=interpret)


def _flash_vjp_fwd(q, k, v, causal, window, softcap, block_q, block_kv,
                   interpret):
    o = _flash_fwd_kernel_call(q, k, v, causal=causal, window=window,
                               softcap=softcap, block_q=block_q,
                               block_kv=block_kv, interpret=interpret)
    return o, (q, k, v)


def _flash_vjp_bwd(causal, window, softcap, block_q, block_kv, interpret,
                   res, g):
    """Backward through the exact attention math (recompute-from-inputs).

    The forward runs the fused Pallas kernel; the backward recomputes with
    the reference formula and lets XLA differentiate it — the standard
    fwd-kernel + analytic-bwd split (a dedicated bwd Pallas kernel is the
    further TPU optimization, EXPERIMENTS.md §Perf)."""
    from repro.kernels.ref import mha_reference
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: mha_reference(
        q, k, v, causal=causal, window=window, softcap=softcap), q, k, v)
    return vjp(g)


_flash_diff.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                              "block_kv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0,
                    block_q: int = 256, block_kv: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd).  Returns (B, Sq, H, hd)."""
    return _flash_diff(q, k, v, causal, window, softcap, block_q, block_kv,
                       interpret)


def _flash_fwd_kernel_call(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool, window: int, softcap: float,
                           block_q: int, block_kv: int,
                           interpret: bool) -> jax.Array:
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    assert H % KV == 0, "GQA requires H % KV == 0"
    G = H // KV
    bq = min(block_q, Sq)
    while Sq % bq:
        bq //= 2
    bkv = min(block_kv, Skv)
    while Skv % bkv:
        bkv //= 2
    bq, bkv = max(bq, 1), max(bkv, 1)
    grid = (B, H, Sq // bq, Skv // bkv)
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_kv=bkv, seq_q=Sq, seq_kv=Skv, softcap=softcap)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)
