"""The float32 reference against the program, block by block and whole,
at a tiny size on the CPU, with the program in float32 too."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import adamw, mamba, mlstm, shared_attn, slstm
from chipbench.reference.common import exact_mm, fp8_mm, log_sigmoid
from chipbench.reference.model import loss_sum


def tiny(pattern, **kw):
    base = dict(name="tiny", n_layers=len(pattern) * 2, d_model=64,
                n_heads=4, n_kv_heads=4, d_ff=128, vocab=96,
                block_pattern=tuple(pattern), ssm_state=16, ssm_head_dim=16,
                attn_window=48, dtype="float32")
    base.update(kw)
    from repro.models.config import ArchConfig
    arch = ArchConfig(**base)
    d = dataclasses.asdict(arch)
    d["block_pattern"] = list(arch.block_pattern)
    return arch, d


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def params_for(d, seed=3):
    return weights.param_maker(d)(weights.key_data(seed))


def layer(params, j, kind, mod):
    stack = params[f"pos{j}"]
    return jax.tree.map(lambda a: a[0], stack[mod.KEY] if mod.KEY else stack)


@pytest.mark.parametrize("kind", ["mlstm", "slstm", "mamba"])
def test_block_matches_program(kind):
    from repro.models import layers as L
    pattern = {"mlstm": ("mlstm", "slstm"), "slstm": ("mlstm", "slstm"),
               "mamba": ("mamba", "mamba", "shared_attn")}[kind]
    arch, d = tiny(pattern)
    j = pattern.index(kind)
    mod = {"mlstm": mlstm, "slstm": slstm, "mamba": mamba}[kind]
    p = layer(params_for(d), j, kind, mod)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))
    prog = jax.jit({"mlstm": L.mlstm_block, "slstm": L.slstm_block,
                    "mamba": L.mamba_block}[kind], static_argnums=1)(
        p, arch, x)
    ref = jax.jit(lambda p, x: mod.block(p, x, d, exact_mm))(p, x)
    assert rel(ref - x, prog - x) < 1e-4


def test_mlstm_parallel_form_matches_the_recurrence():
    """The reference's parallel mLSTM against the step-by-step recurrence
    of the paper's section 2.3, with the repo's normaliser."""
    B, S, H, hd = 2, 40, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd)) for kk in ks[:3])
    i = jax.random.normal(ks[3], (B, S, H)) * 2
    f = jax.random.normal(ks[4], (B, S, H)) * 2

    C = np.zeros((B, H, hd, hd))
    n = np.zeros((B, H, hd))
    m = np.full((B, H), -np.inf)
    ys = []
    for t in range(S):
        lf = np.asarray(log_sigmoid(f[:, t]))
        m_new = np.maximum(lf + m, np.asarray(i[:, t]))
        fg = np.exp(lf + m - m_new)
        ig = np.exp(np.asarray(i[:, t]) - m_new)
        kt, vt, qt = (np.asarray(a[:, t]) for a in (k, v, q))
        C = fg[..., None, None] * C + ig[..., None, None] * \
            kt[..., :, None] * vt[..., None, :]
        n = fg[..., None] * n + ig[..., None] * kt
        num = np.einsum("bhkv,bhk->bhv", C, qt)
        den = np.abs(np.einsum("bhk,bhk->bh", n, qt))
        ys.append(num / np.maximum(den, 1.0)[..., None])
        m = m_new
    seq = np.stack(ys, axis=1)

    F = jnp.cumsum(log_sigmoid(jnp.moveaxis(f, 1, 2)), axis=-1)
    ii = jnp.moveaxis(i, 1, 2)
    D = jnp.where(jnp.tril(jnp.ones((S, S), bool)),
                  F[..., :, None] - F[..., None, :] + ii[..., None, :],
                  -jnp.inf)
    A = jnp.einsum("bthk,bshk->bhts", q, k) * jnp.exp(
        D - D.max(-1, keepdims=True))
    num = jnp.einsum("bhts,bshk->bthk", A, v)
    den = jnp.moveaxis(jnp.abs(A.sum(-1)), 1, 2)
    par = num / jnp.maximum(den, 1.0)[..., None]
    assert rel(par, seq) < 1e-5


def test_shared_block_matches_program():
    """One (mamba, mamba, shared_attn) cycle through the program's LM and
    through the reference, shared block included."""
    from repro.models.lm import LM
    arch, d = tiny(("mamba", "mamba", "shared_attn"), n_layers=3)
    p = params_for(d)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 129), 0, 96)
    t, lab = toks[:, :-1], toks[:, 1:]
    prog = jax.jit(LM(arch).loss)(p, t, lab)
    ref = jax.jit(lambda p: loss_sum(p, t, lab, cfg=d, mm=exact_mm))(p) \
        / t.size
    assert abs(float(prog) - float(ref)) < 1e-5


@pytest.mark.parametrize("pattern", [("mlstm", "slstm"),
                                     ("mamba", "mamba", "shared_attn")])
def test_loss_and_gradients_match_program(pattern):
    from repro.models.lm import LM
    arch, d = tiny(pattern)
    S = 256 if pattern[0] == "mamba" else 128
    p = params_for(d)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, S + 1), 0, 96)
    t, lab = toks[:, :-1], toks[:, 1:]
    lp, gp = jax.jit(jax.value_and_grad(
        lambda q: LM(arch).loss(q, t, lab)))(p)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda q: loss_sum(q, t, lab, cfg=d, mm=exact_mm) / t.size))(p)
    assert abs(float(lp) - float(lr)) < 1e-5
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert rel(a, b) < 1e-3


def test_adamw_matches_program():
    from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
    opt = dict(peak_lr=3e-4, min_lr_frac=0.1, warmup_steps=2, total_steps=10,
               b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, clip_norm=1.0)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"a": jax.random.normal(ks[0], (8, 4)),
              "b": jax.random.normal(ks[1], (5,))}
    state = init_opt_state(params)
    p_prog, p_ref = params, params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    for t in range(1, 5):
        g = jax.tree.map(lambda x: 3.0 * jnp.sin(x * t), params)
        p_prog, state, _ = adamw_update(p_prog, g, state, AdamWConfig(**opt))
        p_ref, m, v = adamw.update(opt, adamw.coefficients(opt, t), p_ref,
                                   adamw.clipped(opt, g), m, v)
    for a, b in zip(jax.tree.leaves(p_prog), jax.tree.leaves(p_ref)):
        assert rel(a, b) < 1e-6


def test_fp8_matmul_is_coarser():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    exact = exact_mm("ij,jk->ik", a, a)
    err = rel(fp8_mm("ij,jk->ik", a, a), exact)
    assert 1e-3 < err < 0.2
