"""What a configuration can state in new files only: block kinds that read
the embedding's output and their cycle's index, and an untied head; and
that the xLSTM cell's reference and weights are as they were before these
existed."""

import hashlib
import importlib
import sys
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import mlstm
from chipbench.reference.common import F32, exact_mm, rms_norm
from chipbench.reference.embed_xent import embed, xent_sum
from chipbench.reference.model import Reference, loss_sum
from chipbench.spec import resolve

XLSTM = resolve("xlstm-125m.train4k").config
OPT = XLSTM["opt"]


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def tiny_xlstm(**kw):
    return {**dict(name="tiny", n_layers=4, d_model=64, n_heads=4,
                   vocab=96, block_pattern=["mlstm", "slstm"], norm_eps=1e-6,
                   dtype="bfloat16"), **kw}


def parent_loss_sum(params, tokens, labels, *, cfg, mm):
    """``model.loss_sum`` before block kinds could declare inputs, kept
    verbatim as the yardstick of the xLSTM cell's reference."""
    pattern = cfg["block_pattern"]
    shared = params.get("shared")
    mods = [importlib.import_module(f"chipbench.reference.{kind}")
            for kind in pattern]
    stacks = [params[f"pos{j}"][m.KEY] if m.KEY else params[f"pos{j}"]
              for j, m in enumerate(mods)]

    def cycle(x, layer):
        for m, p in zip(mods, layer):
            x = jax.checkpoint(partial(m.block, cfg=cfg, mm=mm))(
                p, x, shared=shared)
        return x, None

    x, _ = jax.lax.scan(cycle, embed(params["embed"], tokens), stacks)
    x = rms_norm(x, params["final_norm"], cfg["norm_eps"])
    return xent_sum(params["embed"], x, labels, mm)


def parent_shape_tree(cfg):
    """``weights.shape_tree`` before the untied head, kept verbatim but
    for the embedding's shapes, written out."""
    d = cfg["d_model"]
    tree = weights._specs({"embed": {"tok": ((cfg["vocab"], d), 0.02)},
                           "final_norm": ((d,), "ones")})
    n = cfg["n_layers"] // len(cfg["block_pattern"])
    for j, kind in enumerate(cfg["block_pattern"]):
        mod = importlib.import_module(f"chipbench.reference.{kind}")
        stacked = weights._specs(mod.param_shapes(cfg), n)
        tree[f"pos{j}"] = {mod.KEY: stacked} if mod.KEY else stacked
    return tree


def value_and_grad(fn, cfg):
    return jax.value_and_grad(
        lambda p, t, lab: fn(p, t, lab, cfg=cfg, mm=exact_mm))


@pytest.mark.parametrize("size", ["tiny", "xlstm-125m"])
def test_xlstm_reference_jaxpr_unchanged(size):
    """Loss and gradients of the xLSTM reference trace to the same
    program as before, at a tiny size and at the cell's (two rows of
    4096, as the check runs it)."""
    cfg = tiny_xlstm() if size == "tiny" else XLSTM["arch"]
    rows, seq = (2, 128) if size == "tiny" else (XLSTM["reference_rows"],
                                                 4096)
    params = jax.eval_shape(weights.param_maker(cfg), weights.key_data(1))
    p32 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, F32), params)
    toks = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
    new = jax.make_jaxpr(value_and_grad(loss_sum, cfg))(p32, toks, toks)
    old = jax.make_jaxpr(value_and_grad(parent_loss_sum, cfg))(p32, toks,
                                                                toks)
    assert str(new) == str(old)


def test_xlstm_reference_readings_unchanged():
    cfg = tiny_xlstm()
    p = jax.tree.map(lambda a: a.astype(F32), weights.param_maker(cfg)(
        weights.key_data(2**32 + 5)))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 129), 0, 96)
    t, lab = toks[:, :-1], toks[:, 1:]
    l_new, g_new = jax.jit(value_and_grad(loss_sum, cfg))(p, t, lab)
    l_old, g_old = jax.jit(value_and_grad(parent_loss_sum, cfg))(p, t, lab)
    assert float(l_new) == float(l_old)
    for a, b in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_xlstm_weights_unchanged():
    """The cell's weight layout is the parent's, and a tiny xLSTM's
    weights from a large seed hash as the parent made them."""
    assert weights.shape_tree(XLSTM["arch"]) == parent_shape_tree(
        XLSTM["arch"])
    p = weights.param_maker(tiny_xlstm())(weights.key_data(2**33 + 7))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(p):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == ("6421795e4f1773b73e5bd9082df244d3"
                             "2e4e804f8de8b921f82901e15c8da1af")


# -- a block kind that declares inputs, as a new file would -----------------

def probe_module(seen):
    """``reference/probe.py``: adds x0 scaled by its weight and by the
    cycle's index plus one."""
    mod = types.ModuleType("chipbench.reference.probe")
    mod.KEY = "probe"
    mod.INPUTS = ("x0", "cycle")
    mod.param_shapes = lambda cfg: {"w": ((cfg["d_model"],), 0.5)}

    def block(p, x, cfg, mm, shared=None, *, x0, cycle):
        seen.append((x0.dtype, x0.shape, cycle.dtype, cycle.shape))
        return x + p["w"].astype(F32) * x0 * (cycle + 1).astype(F32)

    mod.block = block
    return mod


@pytest.fixture
def probe(monkeypatch):
    seen = []
    monkeypatch.setitem(sys.modules, "chipbench.reference.probe",
                        probe_module(seen))
    return seen


def probe_cfg():
    return dict(name="probe", n_layers=6, d_model=32, n_heads=2, vocab=64,
                block_pattern=["probe", "mlstm"], norm_eps=1e-6,
                dtype="float32")


def by_hand(params, tokens, labels, cfg):
    """The probe's pattern unrolled: every layer sees the embedding and
    its cycle's index as plain values."""
    x0 = embed(params["embed"], tokens)
    x = x0
    for c in range(cfg["n_layers"] // 2):
        x = x + params["pos0"]["probe"]["w"][c] * x0 * (c + 1.0)
        x = mlstm.block(jax.tree.map(lambda a: a[c], params["pos1"]["mlstm"]),
                        x, cfg, exact_mm)
    x = rms_norm(x, params["final_norm"], cfg["norm_eps"])
    return xent_sum(params["embed"], x, labels, exact_mm)


def test_declared_inputs_reach_the_block(probe):
    cfg = probe_cfg()
    tree = weights.shape_tree(cfg)
    assert tree["pos0"] == {"probe": {"w": ((3, 32), 0.5)}}
    p = weights.param_maker(cfg)(weights.key_data(2**31 + 3))
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 65), 0, 64)
    t, lab = toks[:, :-1], toks[:, 1:]
    got = jax.jit(partial(loss_sum, cfg=cfg, mm=exact_mm))(p, t, lab)
    want = by_hand(p, t, lab, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert probe[0] == (jnp.float32, (2, 64, 32), jnp.int32, ())

    ref = Reference(cfg, OPT, rows=1)
    losses, first, after = ref.train(p, [(t, lab), (t, lab)])
    assert losses[0] == pytest.approx(float(want) / t.size, rel=1e-6)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(p)]
    assert float(first[names.index("['pos0']['probe']['w']")]) > 0
    moved = np.asarray(after["pos0"]["probe"]["w"] - p["pos0"]["probe"]["w"])
    assert np.all(np.abs(moved).sum(-1) > 0)


def test_cycle_index_matters(probe):
    """Cycles swapped in the stack read differently: the block's scale by
    the cycle's index follows the layer, not its weights."""
    cfg = probe_cfg()
    p = weights.param_maker(cfg)(weights.key_data(9))
    w = p["pos0"]["probe"]["w"]
    swapped = dict(p, pos0={"probe": {"w": w[::-1]}})
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, 33), 0, 64)
    t, lab = toks[:, :-1], toks[:, 1:]
    f = jax.jit(partial(loss_sum, cfg=cfg, mm=exact_mm))
    assert float(f(swapped, t, lab)) == pytest.approx(
        float(by_hand(swapped, t, lab, cfg)), rel=1e-6)
    assert float(f(swapped, t, lab)) != pytest.approx(float(f(p, t, lab)),
                                                      rel=1e-6)


# -- the untied head ---------------------------------------------------------

def test_untied_head_layout():
    cfg = tiny_xlstm(tie_embeddings=False)
    assert weights.shape_tree(cfg)["embed"] == {
        "tok": ((96, 64), 0.02), "head": ((96, 64), 0.02)}
    assert "head" not in weights.shape_tree(tiny_xlstm(tie_embeddings=True))[
        "embed"]
    p = weights.param_maker(cfg)(weights.key_data(3))
    assert p["embed"]["head"].shape == (96, 64)
    assert not np.array_equal(np.asarray(p["embed"]["head"]),
                              np.asarray(p["embed"]["tok"]))


def test_untied_head_equal_to_tok_reads_as_tied():
    """With head := tok the loss is the tied one; the head gets the output
    projection's gradient, and tok the embedding's alone: none on a token
    that no row holds."""
    cfg = tiny_xlstm(tie_embeddings=False, dtype="float32")
    p = weights.param_maker(cfg)(weights.key_data(2**31 + 1))
    p["embed"]["head"] = p["embed"]["tok"]
    tied = dict(p, embed={"tok": p["embed"]["tok"]})
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 65), 0, 48)
    t, lab = toks[:, :-1], toks[:, 1:]
    f = jax.jit(value_and_grad(loss_sum, cfg))
    l_un, g_un = f(p, t, lab)
    l_ti, g_ti = f(tied, t, lab)
    assert float(l_un) == float(l_ti)
    head, tok = (np.asarray(g_un["embed"][k]) for k in ("head", "tok"))
    assert np.abs(head).sum() > 0
    unseen = np.setdiff1d(np.arange(96), np.asarray(t))
    assert len(unseen) and not np.any(tok[unseen])
    assert np.any(np.asarray(g_ti["embed"]["tok"])[unseen])
    np.testing.assert_allclose(head + tok, np.asarray(g_ti["embed"]["tok"]),
                               rtol=1e-5, atol=1e-5)
