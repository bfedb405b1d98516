"""Equivalence tests for the optimized execution paths (§Perf changes).

Every beyond-paper optimization must match its reference implementation:
group-local MoE dispatch, chunkwise-parallel SSD, chunked time scans,
sharding-rule fallbacks.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models.lm import LM

# reference-vs-optimized numerical equivalence sweeps (several jit compiles
# each) — covered by the slow suite, not the tier-1 CI gate
pytestmark = pytest.mark.slow


def test_moe_grouped_dispatch_matches_single_group():
    """With ample capacity (no drops) group-local dispatch == global."""
    cfg = get_config("qwen3_moe_30b_a3b").reduced()
    cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)  # dropless
    key = jax.random.PRNGKey(0)
    p = L.materialize(L.moe_defs(cfg), key, jnp.float32)
    x = jax.random.normal(key, (4, 16, cfg.d_model)) * 0.5
    y1 = L.moe_block(p, dataclasses.replace(cfg, moe_groups=1), x)
    y4 = L.moe_block(p, dataclasses.replace(cfg, moe_groups=4), x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4),
                               atol=1e-5, rtol=1e-5)


def test_moe_group_fallback_when_not_divisible():
    cfg = get_config("dbrx_132b").reduced()
    cfg = dataclasses.replace(cfg, moe_groups=7)   # 2*16 % 7 != 0 -> G=1
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    out = model.forward(params, tokens)
    assert bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("chunk,h0", [(64, False), (128, True), (32, True)])
def test_chunkwise_ssd_matches_sequential(chunk, h0):
    key = jax.random.PRNGKey(1)
    B, S, nh, hd, N = 2, 256, 4, 16, 8
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, nh, hd))
    B_in = jax.random.normal(ks[1], (B, S, N)) * 0.5
    C_in = jax.random.normal(ks[2], (B, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, nh)))
    A_log = jax.random.normal(ks[4], (nh,)) * 0.3
    D = jnp.ones((nh,))
    state = jax.random.normal(key, (B, nh, hd, N)) if h0 else None
    y1, h1 = L._mamba_scan_seq(x, B_in, C_in, dt, A_log, D, hd, h0=state)
    y2, h2 = L._mamba_scan(x, B_in, C_in, dt, A_log, D, hd, h0=state,
                           chunk=chunk)
    np.testing.assert_allclose(y1, y2, atol=5e-4, rtol=5e-3)
    np.testing.assert_allclose(h1, h2, atol=5e-4, rtol=5e-3)


def test_chunkwise_ssd_gradients_match():
    key = jax.random.PRNGKey(2)
    B, S, nh, hd, N = 1, 128, 2, 8, 4
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, nh, hd))
    B_in = jax.random.normal(ks[1], (B, S, N)) * 0.5
    C_in = jax.random.normal(ks[2], (B, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, nh)))
    A_log = jnp.zeros((nh,))
    D = jnp.ones((nh,))

    def f_seq(x):
        return jnp.sum(L._mamba_scan_seq(x, B_in, C_in, dt, A_log, D,
                                         hd)[0] ** 2)

    def f_chk(x):
        return jnp.sum(L._mamba_scan(x, B_in, C_in, dt, A_log, D, hd,
                                     chunk=32)[0] ** 2)

    np.testing.assert_allclose(jax.grad(f_seq)(x), jax.grad(f_chk)(x),
                               atol=2e-3, rtol=2e-2)


@pytest.mark.parametrize("groups", [1, 2])
def test_grouped_chunkwise_ssd_matches_sequential(groups):
    """B and C per group, head h reading group h // (nh / G): the chunkwise
    SSD against the step-by-step recurrence with each head's B and C
    written out, values and gradients."""
    B, S, nh, hd, N = 2, 256, 4, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, S, nh, hd))
    B_in = jax.random.normal(ks[1], (B, S, groups, N)) * 0.5
    C_in = jax.random.normal(ks[2], (B, S, groups, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, nh)))
    A_log = jax.random.normal(ks[4], (nh,)) * 0.3
    D = jnp.linspace(0.5, 1.5, nh)
    per_head = [h * groups // nh for h in range(nh)]

    def by_head(x):
        ys = [L._mamba_scan_seq(x[:, :, h:h + 1], B_in[:, :, g],
                                C_in[:, :, g], dt[:, :, h:h + 1],
                                A_log[h:h + 1], D[h:h + 1], hd)[0]
              for h, g in enumerate(per_head)]
        return jnp.concatenate(ys, axis=2)

    def chunked(x):
        return L._mamba_scan(x, B_in, C_in, dt, A_log, D, hd, chunk=64)[0]

    np.testing.assert_allclose(chunked(x), by_head(x), atol=5e-4, rtol=5e-3)
    np.testing.assert_allclose(
        jax.grad(lambda x: jnp.sum(chunked(x) ** 2))(x),
        jax.grad(lambda x: jnp.sum(by_head(x) ** 2))(x), atol=2e-3,
        rtol=2e-2)


def test_chunked_time_scan_matches_plain():
    def step(c, x):
        c = 0.9 * c + x
        return c, jnp.tanh(c)

    xs = jax.random.normal(jax.random.PRNGKey(3), (512, 8))
    c0 = jnp.zeros((8,))
    c1, y1 = jax.lax.scan(step, c0, xs)
    c2, y2 = L.chunked_time_scan(step, c0, xs, chunk=128)
    np.testing.assert_allclose(c1, c2, atol=1e-6)
    np.testing.assert_allclose(y1, y2, atol=1e-6)
    # gradient path (the whole point: per-chunk remat)
    g1 = jax.grad(lambda xs: jnp.sum(jax.lax.scan(step, c0, xs)[1]))(xs)
    g2 = jax.grad(lambda xs: jnp.sum(
        L.chunked_time_scan(step, c0, xs, chunk=128)[1]))(xs)
    np.testing.assert_allclose(g1, g2, atol=1e-5)


def test_axis_rules_divisibility_fallback():
    import os
    from repro.parallel.axes import AxisRules
    from jax.sharding import PartitionSpec as P
    rules = AxisRules()
    # no mesh: raw specs
    assert rules.spec(("batch", None, "heads")) == \
        P(("pod", "data"), None, ("model",))
    # pseudo-mesh via shape checks happens in sharding tests (multidev)


def test_pad_heads_exactness():
    """Padded q heads with zero wo rows leave the function unchanged."""
    import dataclasses as dc
    cfg = get_config("qwen2_7b").reduced(n_layers=2, d_model=64, vocab=128,
                                         d_ff=128, n_heads=3, n_kv_heads=1,
                                         head_dim=16)
    model = LM(cfg)
    key = jax.random.PRNGKey(4)
    params = model.init(key)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    ref = model.forward(params, tokens)
    # pad 3 -> 4 heads; extra head rows: wq random junk, wo rows ZERO
    cfg_p = dc.replace(cfg, n_heads=4)
    model_p = LM(cfg_p)
    params_p = model_p.init(jax.random.PRNGKey(99))

    def pad_tree(src, dst):
        for pos in ("pos0",):
            for name in ("wq",):
                dst[pos]["attn"][name] = dst[pos]["attn"][name].at[
                    :, :, :3].set(src[pos]["attn"][name])
        return dst

    import copy
    pp = jax.tree.map(lambda x: x, params_p)
    pp["embed"] = params["embed"]
    pp["final_norm"] = params["final_norm"]
    a_src, a_dst = params["pos0"]["attn"], pp["pos0"]["attn"]
    a_dst["ln"] = a_src["ln"]
    a_dst["wk"], a_dst["wv"] = a_src["wk"], a_src["wv"]
    a_dst["bk"], a_dst["bv"] = a_src["bk"], a_src["bv"]
    a_dst["wq"] = a_dst["wq"].at[:, :, :3].set(a_src["wq"])
    a_dst["bq"] = a_dst["bq"].at[:, :3].set(a_src["bq"])
    a_dst["wo"] = jnp.zeros_like(a_dst["wo"]).at[:, :3].set(a_src["wo"])
    pp["pos0"]["ffn"] = params["pos0"]["ffn"]
    out = model_p.forward(pp, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               atol=2e-5, rtol=2e-5)


def _mlstm_inputs(B, S, H, hd, seed=7, dtype=jnp.float32):
    import math
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd)) / math.sqrt(hd)
    v = jax.random.normal(ks[2], (B, S, H, hd))
    it = (jax.random.normal(ks[3], (B, S, H)) * 2).astype(jnp.float32)
    ft = (jax.random.normal(ks[4], (B, S, H)) * 2 + 1).astype(jnp.float32)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), it, ft)


def _mlstm_state0(B, H, hd):
    return (jnp.zeros((B, H, hd, hd)), jnp.zeros((B, H, hd)),
            jnp.full((B, H), -1e30))


@jax.jit
def _mlstm_sequential(q, k, v, it, ft):
    """The mLSTM recurrence one token at a time, in float32: y and the
    final (C, n, m)."""
    B, S, H, hd = q.shape

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, i_t, f_t = inp
        logf = -jax.nn.softplus(-f_t)
        m_new = jnp.maximum(logf + m, i_t)
        fg = jnp.exp(logf + m - m_new)[..., None]
        ig = jnp.exp(i_t - m_new)[..., None]
        C = C * fg[..., None] + ig[..., None] * (kt[..., :, None]
                                                 * vt[..., None, :])
        n = n * fg + ig * kt
        num = jnp.einsum("bhkv,bhk->bhv", C, qt,
                         precision=jax.lax.Precision.HIGHEST)
        den = jnp.abs(jnp.einsum("bhk,bhk->bh", n, qt,
                                 precision=jax.lax.Precision.HIGHEST))
        return (C, n, m_new), num / jnp.maximum(den, 1.0)[..., None]

    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
               for t in (q, k, v, it, ft))
    state, ys = jax.lax.scan(step, _mlstm_state0(B, H, hd), xs)
    return jnp.moveaxis(ys, 0, 1), state


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (2, 192, 3, 16, 64),
    (1, 512, 2, 128, 64),
    (1, 512, 2, 128, 128),
    (1, 512, 2, 128, 256),
])
def test_chunkwise_mlstm_matches_sequential(B, S, H, hd, chunk):
    q, k, v, it, ft = _mlstm_inputs(B, S, H, hd)
    y_ref, (C, n, m) = _mlstm_sequential(q, k, v, it, ft)
    y_chk, (C_c, n_c, m_c) = L._mlstm_chunkwise(
        q, k, v, it, ft, _mlstm_state0(B, H, hd), chunk=chunk)
    np.testing.assert_allclose(y_ref, y_chk, atol=3e-4, rtol=3e-3)
    np.testing.assert_allclose(m, m_c, atol=1e-5)
    np.testing.assert_allclose(C, C_c, atol=3e-4, rtol=3e-3)
    np.testing.assert_allclose(n, n_c, atol=3e-4, rtol=3e-3)


@pytest.mark.parametrize("chunk", [64, None])
def test_chunkwise_mlstm_gradients_match_sequential(chunk):
    """Gradients through the chunkwise cell, at 64 and at the chunk that
    ``mlstm_chunk`` picks for the shape (256), against the sequential
    recurrence's, for q, k, v and both gates."""
    B, S, H, hd = 1, 512, 1, 384
    chunk = chunk or L.mlstm_chunk(S, hd)
    args = _mlstm_inputs(B, S, H, hd, seed=3)
    w = jax.random.normal(jax.random.PRNGKey(11), (B, S, H, hd))

    def loss(f):
        def go(*a):
            y, (C, n, m) = f(*a)
            return jnp.sum(y * w) + jnp.sum(C) * 1e-2 + jnp.sum(n)
        return go

    chunked = lambda *a: L._mlstm_chunkwise(*a, _mlstm_state0(B, H, hd),
                                             chunk=chunk)
    g_chk = jax.jit(jax.grad(loss(chunked), argnums=range(5)))(*args)
    g_ref = jax.jit(jax.grad(loss(_mlstm_sequential),
                             argnums=range(5)))(*args)
    for name, gc, gr in zip("q k v it ft".split(), g_chk, g_ref):
        scale = float(jnp.max(jnp.abs(gr)))
        np.testing.assert_allclose(gc, gr, atol=1e-4 * scale, rtol=1e-3,
                                   err_msg=name)


def test_chunkwise_mlstm_keeps_bf16_inputs():
    """bf16 q, k, v give the cell's result on the same values in float32,
    rounded once to bf16 on the way out; the state stays float32."""
    B, S, H, hd = 1, 512, 2, 384
    chunk = L.mlstm_chunk(S, hd)
    q, k, v, it, ft = _mlstm_inputs(B, S, H, hd, dtype=jnp.bfloat16)
    up = [t.astype(jnp.float32) for t in (q, k, v)]
    y16, st16 = L._mlstm_chunkwise(q, k, v, it, ft, _mlstm_state0(B, H, hd),
                                   chunk=chunk)
    y32, st32 = L._mlstm_chunkwise(*up, it, ft, _mlstm_state0(B, H, hd),
                                   chunk=chunk)
    assert y16.dtype == jnp.bfloat16 and y32.dtype == jnp.float32
    assert all(t.dtype == jnp.float32 for t in st16)
    # one rounding to bf16 (8 bits of mantissa) of y
    np.testing.assert_allclose(y16.astype(jnp.float32), y32,
                               atol=1e-6, rtol=2 ** -8)
    for a, b in zip(st16, st32):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("S,hd,chunk", [
    (4096, 384, 256),     # xLSTM-125M: d 768, e 2d, 4 heads
    (4096, 128, 64),      # small heads keep 64
    (192, 16, 64),
    (4096, 192, 128),
    (4096, 1024, 256),    # clamped
    (192, 384, 64),       # halved until it divides S
    (128, 384, 64),       # S > c
    (64, 384, None),      # one chunk: the sequential scan
    (1, 384, None),       # decode
    (100, 128, None),     # no chunk of 64 or more divides S
])
def test_mlstm_chunk_rule(S, hd, chunk):
    c = L.mlstm_chunk(S, hd)
    assert c == chunk
    if c is not None:
        assert S % c == 0 and S > c
