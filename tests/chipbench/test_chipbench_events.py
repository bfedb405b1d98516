"""Event schedules and the chunks a window is cut into."""

import itertools
import json

from chipbench import data, events
from chipbench.cell import plan_chunks
from chipbench.spec import ROOT

SPEC = json.loads((ROOT / "chipbench" / "traffic" / "train4k.events.json")
                  .read_text())["events"]


def test_same_arrivals_for_every_seed():
    a = events.schedule(SPEC, 1, 10)
    b = events.schedule(SPEC, 2**40 + 9, 10)
    assert [s for s, _, _ in a] == [s for s, _, _ in b] == \
        [2, 3, 7, 11, 15, 19, 23, 27, 31, 35]
    assert [f for *_, f in a] != [f for *_, f in b]
    assert events.schedule(SPEC, 1, 10) == a


def test_flaps_degrade_then_repair():
    """Every factor is a degradation in the stated range or the repair of
    one (its reciprocal)."""
    lo, hi = SPEC["severity_range"]
    open_flaps = []
    for _, sel, f in events.schedule(SPEC, 5, 40):
        assert sel == "dci"
        if f < 1:
            assert lo <= f <= hi
            open_flaps.append(f)
        else:
            assert any(abs(f * g - 1) < 1e-12 for g in open_flaps)


def test_chunks_put_each_event_alone():
    chunks = list(itertools.islice(plan_chunks(3, [2, 3, 7, 11], 4), 6))
    assert chunks == [("event", 3, 4), ("steady", 4, 7), ("event", 7, 8),
                      ("steady", 8, 11), ("event", 11, 12),
                      ("steady", 12, 16)]
    assert list(itertools.islice(plan_chunks(3, [], 10), 3)) == [
        ("steady", 3, 10), ("steady", 10, 20), ("steady", 20, 30)]


def test_topology_is_two_pods_of_sixteen():
    topo = events.topology(SPEC["topology"])
    assert len(topo.devices) == 32


def test_batches_repeat_from_the_seed_and_rows_differ():
    t1, l1 = data.batch(2**33 + 1, 0, rows=8, seq=64, vocab=50304)
    t2, l2 = data.batch(2**33 + 1, 0, rows=8, seq=64, vocab=50304)
    assert (t1 == t2).all() and (l1 == l2).all()
    assert (t1[:, 1:] == l1[:, :-1]).all()
    assert len({r.tobytes() for r in t1}) == 8


def test_batches_are_the_programs_feed():
    """The copy in chipbench/data.py makes what the trainer's feed makes,
    so the reference reads the batches the program was given."""
    from repro.data.pipeline import DataConfig, SyntheticLM
    feed = SyntheticLM(DataConfig(vocab=50304, seq_len=64, global_batch=8,
                                  seed=2**35 + 3))
    for step in (0, 2):
        got = feed.batch(step)
        t, lab = data.batch(2**35 + 3, step, rows=8, seq=64, vocab=50304)
        assert (got["tokens"] == t).all() and (got["labels"] == lab).all()
