"""A kernel's share of its roofline: the helper on hand-made numbers, the
mLSTM cell's counts and reader, and a new kernel's scope and reader added
as files of their own."""

import sys
import types

import pytest

from chipbench import cell, metrics, roofline, scopes
from chipbench.flops import mlstm_cell
from chipbench.spec import resolve

V5E = cell.peaks("TPU v5 lite")
XLSTM = resolve("xlstm-125m.train4k").config


def record(config, chips=1):
    """A finished traced run on v5e with 2 traced steps."""
    run = cell.RunRecord(chips=chips, flops_per_token=1.0,
                         peak_flops=V5E["bf16_flops_per_s"],
                         hbm_bytes_per_s=V5E["hbm_bytes_per_s"],
                         config=config, seq_len=4096)
    run.chunks = [{"kind": "steady", "steps": 2, "traced": True},
                  {"kind": "steady", "steps": 10, "traced": False}]
    run.trace = {"busy_s": 1.0, "window_s": 1.0}
    return run


@pytest.fixture
def trace_seconds(monkeypatch, tmp_path):
    """Self seconds per scope that the run's trace holds, as the test sets
    them; the trace reads a scope only where the scope set names it."""
    monkeypatch.setattr(cell, "RUN_DIR", tmp_path)
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace" / "host.xplane.pb").write_bytes(b"")
    scopes._read_once.cache_clear()
    held = {}

    def read(path, *, window=scopes.WINDOW, scopes=scopes.SCOPES,
             unscoped=scopes.UNSCOPED):
        return {k: v for k, v in held.items()
                if k in scopes or k == unscoped}

    monkeypatch.setattr(scopes, "read", read)
    yield held
    scopes._read_once.cache_clear()


@pytest.mark.parametrize("flops, nbytes, ms, chips, want", [
    (197e9, 81.9e6, 4.0, 1, 25.0),      # compute-bound: 1 ms of FLOPs
    (19.7e9, 819e6, 2.0, 1, 50.0),      # memory-bound: 1 ms of bytes
    (394e9, 0.0, 4.0, 2, 25.0),         # two chips share the step's count
    (0.0, 1638e6, 8.0, 2, 12.5),
])
def test_share_on_hand_numbers(monkeypatch, flops, nbytes, ms, chips, want):
    monkeypatch.setattr(roofline, "per_step_ms", lambda run, scope: ms)
    assert roofline.share(record({}, chips), "k", flops, nbytes) == \
        pytest.approx(want)


def test_share_reads_nothing_without_a_time_or_a_count(trace_seconds):
    trace_seconds["mlstm_cell"] = 0.2
    run = record({})
    assert roofline.share(run, "mlstm_cell", 0.0, 0.0) is None
    assert roofline.share(run, "slstm_scan", 1e9, 1e9) is None
    run.trace = None
    assert roofline.share(run, "mlstm_cell", 1e9, 1e9) is None


def test_mlstm_cell_counts():
    """21 mLSTM layers of 4 heads of 384 over 4 x 4096 tokens: 3 x 2 x
    (2 hd^2 + 2 hd) FLOPs per head and token; bytes of q, k, v, y, dy, dq,
    dk, dv (4 x 4096 x 1536 each) and of the two gates in, again in the
    backward pass and their gradients out (4 x 4096 x 4 each), in bf16."""
    f, b = mlstm_cell.train_step(XLSTM["arch"], 4, 4096)
    assert f == 21 * 3 * 2 * 4 * (2 * 384 ** 2 + 2 * 384) * 16384
    assert f == 2_441_588_244_480
    assert b == 21 * 2 * (11 * 16384 * 1536 + 6 * 16384 * 4)
    assert b == 11_643_125_760
    assert mlstm_cell.train_step(dict(XLSTM["arch"], dtype="float32"),
                                 4, 4096)[1] == 2 * b
    slstm_only = dict(XLSTM["arch"], block_pattern=["slstm"])
    assert mlstm_cell.train_step(slstm_only, 4, 4096) == (0, 0)


def test_mlstm_cell_roofline_reader(trace_seconds):
    """The cell's self time per step is PR 15's 181.62 ms: memory bounds
    it, at 14.22 ms of HBM traffic against 12.39 ms of FLOPs."""
    trace_seconds["mlstm_cell"] = 2 * 0.18162
    got = metrics.read("mlstm_cell_roofline", record(XLSTM))
    assert got == pytest.approx(100 * 11_643_125_760 / 819e9 / 0.18162)
    assert 5 < got < 12


def test_new_kernel_scope_and_reader_as_files(monkeypatch, trace_seconds):
    """A configuration that declares its kernel's scope, and the kernel's
    roofline reader, each a file of its own: the reader reads the
    kernel's own self time, and nothing where the scope is not declared
    (its ops then count under the enclosing block)."""
    reader = types.ModuleType("chipbench.metrics.probe_ssd_roofline")
    reader.read = lambda run: roofline.share(
        run, "probe_ssd", run.seq_len * 1e6 * 197, 0.0)
    monkeypatch.setitem(sys.modules, reader.__name__, reader)
    trace_seconds.update({"mamba": 0.004, "probe_ssd": 0.016})
    declared = record({"scopes": ["probe_ssd"], "arch": {}})
    assert metrics.read("probe_ssd_roofline", declared) == pytest.approx(
        100 * 4.096 / 8.0)
    assert metrics.read("probe_ssd_roofline", record({"arch": {}})) is None
