"""Zamba2-7B through the program against its float32 reference, at a tiny
size on the CPU with seeded weights: each block kind, the whole loss and
gradients over alternating shared sets, the tie between the chip's share
and the uncut layer, the parameter layout the benchmark makes weights
from."""

import dataclasses
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, weights
from chipbench.flops import mamba_ssd
from chipbench.reference import mamba2, zamba2_hybrid
from chipbench.reference.common import exact_mm, rms_norm
from chipbench.reference.model import loss_sum
from chipbench.spec import ROOT, resolve

PATTERN = ("mamba2", "zamba2_hybrid")
ZAMBA = resolve("zamba2-7b-6L.train4k").config


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def tiny(**kw):
    """d 64; attention 4 heads of 32 span 2d (one rank: 2 groups of 4
    Mamba2 heads, all 128 MLP columns); 3 cycles, so 2 shared sets."""
    from repro.models.config import ArchConfig
    base = dict(name="tiny", n_layers=6, d_model=64, n_heads=4, n_kv_heads=4,
                head_dim=32, d_ff=128, vocab=96, block_pattern=PATTERN,
                ssm_state=16, ssm_head_dim=16, ssm_expand=2, norm_eps=1e-5,
                attn_q_chunk=64, dtype="float32")
    base.update(kw)
    arch = ArchConfig(**base)
    d = dataclasses.asdict(arch)
    d["block_pattern"] = list(arch.block_pattern)
    return arch, d


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def params_for(d, seed=2**31 + 11):
    return weights.param_maker(d)(weights.key_data(seed))


def first(tree):
    return jax.tree.map(lambda a: a[0], tree)


def normal(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape)


def test_share_of_the_configuration():
    """The configuration's arch gives the chip 56 of 112 Mamba2 heads, 1 of
    2 groups, 7168 of 14336 MLP columns and one shared set; the published
    32 heads give the uncut layer."""
    from repro.models.config import ArchConfig
    a = dict(ZAMBA["arch"], block_pattern=tuple(ZAMBA["arch"]["block_pattern"]))
    share = ArchConfig(**a).zamba2
    assert (share.ranks, share.ssm_heads, share.ssm_groups, share.mlp,
            share.sets) == (2, 56, 1, 7168, 1)
    whole = ArchConfig(**dict(a, n_heads=32, n_kv_heads=32, n_layers=12))
    assert (whole.zamba2.ssm_heads, whole.zamba2.ssm_groups,
            whole.zamba2.mlp, whole.zamba2.sets) == (112, 2, 14336, 2)
    assert mamba2.share(ZAMBA["arch"]) == {"ranks": 2, "heads": 56,
                                           "e": 3584, "groups": 1}
    with pytest.raises(ValueError):
        ArchConfig(**dict(a, n_heads=8, n_kv_heads=8)).zamba2


@pytest.mark.parametrize("cfg", ["tiny", "zamba2-7b-6L"])
def test_parameter_tree_is_the_benchmarks(cfg):
    """The program lays its parameters out as the benchmark makes them
    (``weights.shape_tree``): the same names and shapes."""
    from repro.models.config import ArchConfig
    from repro.models.lm import LM
    if cfg == "tiny":
        arch, d = tiny()
    else:
        d = ZAMBA["arch"]
        arch = ArchConfig(**dict(d, block_pattern=tuple(d["block_pattern"])))
    prog = jax.tree.map(lambda s: s.shape, LM(arch).abstract_params())
    bench = jax.tree.map(lambda s: s[0], weights.shape_tree(d),
                         is_leaf=weights._is_spec)
    assert prog == bench


def test_parameter_count_of_the_configuration():
    """One Mamba2 layer 39.2M, the shared set 167.0M, the hybrid layer's
    adapter and W_lin 15.1M, the tied embedding 114.7M: 532.2M."""
    tree = weights.shape_tree(ZAMBA["arch"])
    count = lambda t: sum(math.prod(s[0]) for s in jax.tree.leaves(
        t, is_leaf=weights._is_spec))
    d, e, bc, nh = 3584, 3584, 64, 56
    mamba = 3 * d * e + 2 * d * bc + d * nh + 4 * (e + 2 * bc) \
        + (e + 2 * bc) + 3 * nh + d + e
    assert count(tree["pos0"]) == mamba == 39_220_520
    shared = 2 * d + 3 * 2 * d * 16 * 224 + 16 * 224 * d + d \
        + d * 2 * 7168 + 7168 * d
    assert count(tree["shared"]) == shared == 166_996_480
    own = d * 128 + 128 * 2 * 7168 + d * d
    assert count(tree["pos5"]) - mamba == own == 15_138_816
    assert count(tree) == 6 * mamba + own + shared + 32000 * d + d \
        == 532_150_000


def test_every_weight_is_drawn():
    """No weight starts at zero: LoRA's B, the convolution's bias, A_log,
    D and dt_bias are drawn at a nonzero scale, norm gains are ones."""
    _, d = tiny()
    specs = jax.tree.leaves(weights.shape_tree(d), is_leaf=weights._is_spec)
    assert all(init != "zeros" for _, init in specs)
    p = params_for(d)
    for leaf in jax.tree.leaves(p):
        assert float(jnp.min(jnp.abs(leaf))) > 0 or float(
            jnp.std(leaf)) > 0


@pytest.mark.parametrize("n_heads", [4, 2], ids=["2groups", "1group"])
def test_mamba2_block_matches_program(n_heads):
    from repro.models import layers as L
    arch, d = tiny(n_heads=n_heads, n_kv_heads=n_heads)
    p = first(params_for(d)["pos0"]["mamba"])
    x = normal(1, (2, 256, 64))
    prog = jax.jit(L.mamba_block, static_argnums=1)(p, arch, x)
    ref = jax.jit(lambda p, x: mamba2.block(p, x, d, exact_mm))(p, x)
    assert rel(ref - x, prog - x) < 1e-4


@pytest.mark.parametrize("cycle", [0, 1])
def test_hybrid_block_matches_program(cycle, monkeypatch):
    """The hybrid layer with the shared set its cycle picks, given the
    embedding x0 beside x; the reference's attention and MLP in 4 blocks
    of positions."""
    from repro.models import layers as L
    monkeypatch.setattr(zamba2_hybrid, "BLOCK", 64)
    from repro.models.lm import LM
    arch, d = tiny()
    params = params_for(d)
    p = first(params["pos1"])
    x, x0 = normal(1, (2, 256, 64)), normal(2, (2, 256, 64))
    pos = jnp.broadcast_to(jnp.arange(256)[None], (2, 256))
    shared = LM(arch)._shared_set(params["shared"], jnp.int32(cycle))
    prog = jax.jit(L.zamba2_hybrid_block, static_argnums=2)(
        p, shared, arch, x, x0, pos)
    ref = jax.jit(lambda p, s, x, x0: zamba2_hybrid.block(
        p, x, d, exact_mm, s, x0=x0, cycle=jnp.int32(cycle)))(
        p, params["shared"], x, x0)
    assert rel(ref - x, prog - x) < 1e-4
    other = jax.jit(lambda p, s, x, x0: zamba2_hybrid.block(
        p, x, d, exact_mm, s, x0=x0, cycle=jnp.int32(1 - cycle)))(
        p, params["shared"], x, x0)
    assert rel(other - x, prog - x) > 1e-2


def test_loss_and_gradients_match_program():
    """Three cycles of (mamba2, zamba2_hybrid): two groups, two shared sets
    used in turn, x0 into every hybrid layer."""
    from repro.models.lm import LM
    arch, d = tiny()
    assert arch.zamba2.sets == 2 and arch.zamba2.ssm_groups == 2
    p = params_for(d)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 257), 0, 96)
    t, lab = toks[:, :-1], toks[:, 1:]
    lp, gp = jax.jit(jax.value_and_grad(
        lambda q: LM(arch).loss(q, t, lab, remat="full")))(p)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda q: loss_sum(q, t, lab, cfg=d, mm=exact_mm) / t.size))(p)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    names = [jax.tree_util.keystr(k)
             for k, _ in jax.tree_util.tree_leaves_with_path(gp)]
    for name, a, b in zip(names, jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.linalg.norm(b)) > 0, name
        assert rel(a, b) < 1e-3, name


def test_sets_alternate_and_x0_matters():
    """Swapping the two shared sets, or giving the hybrid layers another
    embedding, changes the loss: both paths are live."""
    from repro.models.lm import LM
    arch, d = tiny()
    p = params_for(d)
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, 129), 0, 96)
    t, lab = toks[:, :-1], toks[:, 1:]
    f = jax.jit(LM(arch).loss)
    base = float(f(p, t, lab))
    swapped = dict(p, shared=jax.tree.map(lambda a: a[::-1], p["shared"]))
    assert abs(float(f(swapped, t, lab)) - base) > 1e-4
    assert abs(float(loss_sum(swapped, t, lab, cfg=d, mm=exact_mm)) / t.size
               - float(f(swapped, t, lab))) < 1e-5


# -- the share: the two ranks' partial outputs add up to the uncut layer ----

def halves(a, axis, parts=2):
    return jnp.split(a, parts, axis=axis)


def gate_up_halves(a, axis):
    """[gate | up] columns, each split in two: rank r holds [gate_r | up_r]."""
    gate, up = jnp.split(a, 2, axis=axis)
    return [jnp.concatenate([g, u], axis=axis)
            for g, u in zip(halves(gate, axis), halves(up, axis))]


def test_attention_shares_add_up():
    from repro.models import layers as L
    whole, dw = tiny()
    share, _ = tiny(n_heads=2, n_kv_heads=2)
    at = first(params_for(dw)["shared"]["attn"])
    g = normal(3, (2, 128, 128))
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    parts = [jax.jit(L.zamba2_attention, static_argnums=1)(
        dict(at, wq=q, wk=k, wv=v, wo=o), share, g, pos)
        for q, k, v, o in zip(halves(at["wq"], 1), halves(at["wk"], 1),
                              halves(at["wv"], 1), halves(at["wo"], 0))]
    ref = zamba2_hybrid.attention(at, g, dw, exact_mm)
    assert rel(parts[0] + parts[1], ref) < 1e-5
    assert rel(parts[0], ref) > 0.1


def test_adapted_mlp_shares_add_up():
    from repro.models import layers as L
    _, dw = tiny()
    params = params_for(dw)
    mp, p = first(params["shared"]["mlp"]), first(params["pos1"])
    m = normal(4, (2, 128, 64))
    parts = [jax.jit(L.zamba2_mlp)(dict(mp, w_gu=gu, w_down=dn),
                                   dict(p, lora_B=lb), m)
             for gu, lb, dn in zip(gate_up_halves(mp["w_gu"], 1),
                                   gate_up_halves(p["lora_B"], 1),
                                   halves(mp["w_down"], 0))]
    ref = zamba2_hybrid.mlp(mp, p, m, exact_mm)
    assert rel(parts[0] + parts[1], ref) < 1e-5
    assert rel(parts[0], ref) > 0.1


def test_mamba2_shares_add_up():
    """Each rank holds one group and its heads: its mixer's output, with
    the residual taken off, is its partial sum of the uncut mixer's."""
    from repro.models import layers as L
    _, dw = tiny()
    share, _ = tiny(n_heads=2, n_kv_heads=2)
    p = first(params_for(dw)["pos0"]["mamba"])
    e, N = 128, 16
    conv = lambda a: [jnp.concatenate([xs, bs, cs], axis=-1) for xs, bs, cs
                      in zip(halves(a[..., :e], -1),
                             halves(a[..., e:e + 2 * N], -1),
                             halves(a[..., e + 2 * N:], -1))]
    ranks = [dict(p, w_z=z, w_x=x, w_B=b, w_C=c, w_dt=dt, conv_w=cw,
                  conv_b=cb, A_log=al, D=dd, dt_bias=db, gn=gn, w_out=o)
             for z, x, b, c, dt, cw, cb, al, dd, db, gn, o in zip(
                 *(halves(p[k], -1) for k in ("w_z", "w_x", "w_B", "w_C",
                                              "w_dt")),
                 conv(p["conv_w"]), conv(p["conv_b"]),
                 *(halves(p[k], 0) for k in ("A_log", "D", "dt_bias", "gn",
                                             "w_out")))]
    x = normal(5, (2, 256, 64))
    parts = [jax.jit(L.mamba_block, static_argnums=1)(r, share, x) - x
             for r in ranks]
    ref = mamba2.mixer(p, rms_norm(x, p["ln"], dw["norm_eps"]), dw, exact_mm)
    assert rel(parts[0] + parts[1], ref) < 1e-4
    assert rel(parts[0], ref) > 0.1


# -- the step's other paths --------------------------------------------------

def test_decode_is_refused():
    from repro.models.lm import LM
    arch, _ = tiny()
    m = LM(arch)
    with pytest.raises(NotImplementedError, match="decode"):
        m.init_cache(1, 8)
    with pytest.raises(NotImplementedError, match="decode"):
        m.decode_step(None, None, jnp.zeros((1, 1), jnp.int32),
                      jnp.zeros((1,), jnp.int32))
    with pytest.raises(NotImplementedError, match="decode"):
        m.prefill(m.init(jax.random.PRNGKey(0)),
                  jnp.zeros((1, 8), jnp.int32))


def test_planner_prices_the_kinds_as_mamba():
    arch, _ = tiny()
    assert arch.to_model_desc().block_pattern == ("mamba", "mamba")


def test_scopes_of_a_compiled_zamba2_step():
    """The train step compiled on the CPU names ops under each scope the
    configuration declares, forward and backward."""
    from chipbench import scopes
    from repro.models.lm import LM
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.trainstep import abstract_train_state, make_train_step

    arch, _ = tiny(n_layers=2)
    model = LM(arch)
    step = make_train_step(model, AdamWConfig(), remat="full")
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    text = jax.jit(step).lower(abstract_train_state(model),
                               {"tokens": tokens, "labels": tokens}
                               ).compile().as_text()
    op = re.compile(r'\s*(?:ROOT )?%?([^\s=]+) = .*?op_name="([^"]*)"')
    names = [m.group(2) for m in map(op.match, text.splitlines()) if m]
    declared = scopes.scope_set(ZAMBA)
    found = {scopes.scope_of(n, declared) for n in names}
    wanted = {"mamba2", "mamba_ssd", "zamba2_hybrid", "head", "adamw"}
    assert wanted <= found
    backward = {scopes.scope_of(n, declared) for n in names
                if "transpose(" in n}
    assert wanted - {"adamw"} <= backward


# -- FLOPs and bytes ---------------------------------------------------------

def test_flops_against_the_parameter_count():
    """Forward FLOPs per token are two per weight of every product (the
    shared set counted at its one occurrence, the tied head once), plus the
    SSD's recurrence and the causal attention's scores and sums; norms,
    biases, A_log, D and dt_bias multiply no matrix."""
    cfg = ZAMBA["arch"]
    tree = weights.shape_tree(cfg)
    loose = {"ln", "gn", "conv_b", "A_log", "D", "dt_bias", "final_norm"}
    n = sum(math.prod(s[0]) for path, s in
            jax.tree_util.tree_leaves_with_path(tree,
                                                is_leaf=weights._is_spec)
            if path[-1].key not in loose)
    S = 4096
    ssd = 6 * 2 * 56 * 64 * 64
    attn = 2 * 16 * 224 * (S + 1) / 2
    assert flops.forward_per_token(cfg, S) == pytest.approx(
        2 * (n + ssd + attn))
    assert 3 * flops.forward_per_token(cfg, S) * S == pytest.approx(
        13.505e12, rel=1e-3)


def test_ssd_counts():
    """Six SSDs (five Mamba2 layers and the hybrid's) of 56 heads of 64,
    state 64, over 4096 tokens: FLOPs of the recurrence, three passes;
    bytes of x and y (3584 each), dt (56), B and C (64 each) in, y out,
    then x, dt, B, C, dy in and their gradients out, in bf16."""
    f, b = mamba_ssd.train_step(ZAMBA["arch"], 1, 4096)
    assert f == 6 * 3 * 2 * 56 * (2 * 64 * 64) * 4096
    io = 3584 + 56 + 2 * 64
    assert b == 6 * 4096 * 2 * (io + 3584 + io + 3584 + io)
    assert mamba_ssd.train_step(dict(ZAMBA["arch"], dtype="float32"),
                                1, 4096)[1] == 2 * b
