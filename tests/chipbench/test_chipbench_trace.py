"""The reduction from a profiler trace to busy time, idle gaps and top
device operations."""

from pathlib import Path

import pytest

from chipbench import trace

HERE = Path(__file__).resolve().parent
RECORDED = HERE / "data" / "small_trace"

MS = 1_000_000


def test_reduce_hand_made():
    """Window 0-100 ms; ops at 10-30, 30-40, 70-80 and 95-120 ms; the gap
    40-70 ms lies inside an event span, 0-10 ms in a steady one, 80-95 ms
    in none of the harness's."""
    spans = [("chipbench.traced", 0, 100 * MS),
             ("chipbench.event", 35 * MS, 75 * MS),
             ("chipbench.steady", 0, 35 * MS),
             ("unrelated", 0, 100 * MS)]
    ops = {"/device:TPU:0": [("matmul", 10 * MS, 30 * MS),
                             ("fusion", 30 * MS, 40 * MS),
                             ("matmul", 70 * MS, 80 * MS),
                             ("late", 95 * MS, 120 * MS)]}
    r = trace.reduce(spans, ops, window="chipbench.traced")
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["device_ops"][0] == ["matmul", pytest.approx(0.03)]
    gaps = dict((round(s, 6), n) for n, s in r["idle_gaps"])
    assert gaps[0.03] == "chipbench.event"
    assert gaps[0.01] == "chipbench.steady"
    assert gaps[0.015] == "outside the harness's spans"


def test_nested_ops_get_their_self_time():
    """A loop's interval holds its body's operations: each is credited
    once, the loop with what its body leaves."""
    ops = {"/device:TPU:0": [("while.5", 0, 10 * MS), ("fusion.1", 1 * MS,
                                                       4 * MS),
                             ("fusion.2", 5 * MS, 9 * MS)]}
    r = trace.reduce([("chipbench.traced", 0, 10 * MS)], ops,
                     window="chipbench.traced")
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.2": 0.004, "fusion.1": 0.003, "while.5": 0.003})
    assert r["busy_s"] == pytest.approx(0.01)
    assert trace.op_name("%fusion.3 = bf16[8]{0} fusion(%p)") == "fusion.3"


def test_reduce_needs_the_window():
    with pytest.raises(ValueError):
        trace.reduce([], {}, window="chipbench.traced")


def test_recorded_trace():
    """A trace recorded on one TPU v5e by ``chipbench/record_test_trace.py``:
    three matmul programs, each followed by 20 ms of host sleep inside a
    ``chipbench.event`` span."""
    spans, ops = trace.read(RECORDED)
    assert list(ops) == ["/device:TPU:0"]
    r = trace.reduce(spans, ops, window="chipbench.traced")
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    longest = r["idle_gaps"][0]
    assert longest[0] == "chipbench.event" and longest[1] >= 0.015
