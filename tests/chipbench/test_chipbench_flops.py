"""Model FLOPs per token against hand counts."""

import json

import pytest

from chipbench import flops
from chipbench.spec import ROOT


def config(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())["arch"]


def n_params(cfg):
    from chipbench.weights import shape_tree, _is_spec
    import jax
    import math
    return sum(math.prod(s[0]) for s in jax.tree.leaves(
        shape_tree(cfg), is_leaf=_is_spec))


def test_xlstm_against_six_n():
    """6 N counts every parameter as one multiply-add forward and two
    backward.  The count here differs by exactly two terms: the 28 norm
    weight vectors (one in each of 21 mLSTM blocks, two in each of 3 sLSTM
    blocks, the final one), which multiply no matrix, and the mLSTM's
    matrix memory, which has no parameters (per head and token the update
    and the read-out, 2 hd^2 + 2 hd multiply-adds, hd = 384)."""
    cfg = config("xlstm-125m")
    N = n_params(cfg)
    assert N == 302_564_352
    norms = 28 * 768
    memory = 21 * 4 * (2 * 384 * 384 + 2 * 384)
    assert flops.train_per_token(cfg, 4096) == 6 * (N - norms) + 6 * memory
    assert flops.train_per_token(cfg, 4096) == 1_964_279_808


def test_shared_block_counted_per_occurrence():
    cfg = config("zamba2-2.7b-9L")
    d, f, S = 2560, 10240, 4096
    keys = (S + 1) / 2                    # window 4096 covers every key
    shared = 2 * (d * d + 4 * d * d + 2 * 32 * 80 * keys + 3 * d * f)
    from chipbench.flops import shared_attn
    assert shared_attn.forward(cfg, S) == pytest.approx(shared)
    one = dict(cfg, n_layers=3)
    six = dict(cfg, n_layers=18)
    head = 2 * d * 32000
    assert flops.forward_per_token(six, S) - head == pytest.approx(
        6 * (flops.forward_per_token(one, S) - head))


def test_window_caps_the_keys():
    cfg = dict(config("zamba2-2.7b-9L"), attn_window=1024)
    from chipbench.flops import shared_attn
    full = dict(cfg, attn_window=0)
    assert shared_attn.forward(cfg, 4096) < shared_attn.forward(full, 4096)


def test_no_recomputation_counted():
    """Training is three forward passes whatever the remat setting: the
    functions see the architecture only."""
    cfg = config("xlstm-125m")
    assert flops.train_per_token(cfg, 4096) == 3 * flops.forward_per_token(
        cfg, 4096)


def test_peaks_by_device_kind():
    """The v5e's published bf16 peak; a kind not in the table is an
    error, never a default."""
    from chipbench.cell import peaks
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
