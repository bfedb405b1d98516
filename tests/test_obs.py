"""Unified tracing + metrics (ISSUE 7): tracer/metrics units, worker span
shipping, exporter round-trips, the counter/stat drift invariant, and the
benchmark provenance header."""

import json
import pickle
import statistics

import pytest

from benchmarks.common import bench_meta, write_json
from benchmarks.compare import compare_rows
from repro.core import (ModelDesc, NetworkEvent, ReplanEngine, SearchExecutor,
                        StrategyCache, hetero_cluster, plan_hybrid)
from repro.obs import (METRICS_KEY, NULL_OBS, Histogram, Obs, Tracer,
                       chrome_trace, resolve_obs, write_trace)
from repro.obs.tracer import NULL_HANDLE
from tools.trace_report import phase_table, render

DESC = ModelDesc(name="m", n_layers=12, d_model=1024, n_heads=16,
                 n_kv_heads=16, d_ff=4096, vocab=32000)


def small_topo():
    return hetero_cluster({"RTX4090D": 4, "V100": 4}, gpus_per_node=4)


# ---------------------------------------------------------------------------
# Metrics: histogram percentiles, counters
# ---------------------------------------------------------------------------


def test_histogram_percentiles_match_statistics_quantiles():
    samples = [0.001, 0.004, 0.0041, 0.02, 0.05, 0.3, 0.31, 0.9, 2.0, 7.5,
               0.011, 0.012, 0.6, 1.4, 0.0007]
    h = Histogram("replan.latency_s")
    for v in samples:
        h.observe(v)
    cuts = statistics.quantiles(samples, n=100, method="inclusive")
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(cuts[q - 1])
    assert h.count == len(samples)
    assert h.mean == pytest.approx(statistics.mean(samples))
    assert sum(h.bucket_counts) == len(samples)


def test_histogram_snapshot_merge_preserves_percentiles():
    a, b = Histogram("h"), Histogram("h")
    for i in range(10):
        (a if i % 2 else b).observe(i * 0.01)
    merged = Histogram("h")
    merged.merge_dict(a.to_dict())
    merged.merge_dict(b.to_dict())
    all_samples = [i * 0.01 for i in range(10)]
    cuts = statistics.quantiles(all_samples, n=100, method="inclusive")
    assert merged.count == 10
    assert merged.percentile(50) == pytest.approx(cuts[49])


# ---------------------------------------------------------------------------
# Disabled path: shared no-ops, nothing allocated or recorded
# ---------------------------------------------------------------------------


def test_disabled_obs_is_shared_noop(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert NULL_OBS.enabled is False
    assert NULL_OBS.tracer is None and NULL_OBS.metrics is None
    # every span() call returns the one shared handle — no allocation
    h1 = NULL_OBS.span("search.cascade", n_points=10)
    h2 = NULL_OBS.span("plan.hybrid")
    assert h1 is NULL_HANDLE and h2 is NULL_HANDLE
    with h1 as h:
        h.set(simulated=5)           # all no-ops
    NULL_OBS.inc("cache.hit")
    NULL_OBS.observe("replan.latency_s", 0.1)
    assert NULL_OBS.current_span_id() is None
    assert NULL_OBS.export_delta() is None
    # an explicit bundle always wins over the env-driven default
    mine = Obs()
    assert resolve_obs(mine) is mine
    assert resolve_obs(None).enabled is False


# ---------------------------------------------------------------------------
# Tracer: nesting, adoption/re-parenting, pickling
# ---------------------------------------------------------------------------


def test_span_nesting_and_attrs():
    obs = Obs()
    with obs.span("outer", kind="test") as outer:
        with obs.span("inner") as inner:
            inner.set(n=3)
        outer.set(done=True)
    spans = {s.name: s for s in obs.tracer.spans}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["inner"].attrs == {"n": 3}
    assert spans["outer"].attrs == {"kind": "test", "done": True}
    assert spans["inner"].duration >= 0.0
    assert spans["outer"].duration >= spans["inner"].duration


def test_adopt_remaps_ids_and_preserves_worker_pid():
    worker = [  # two spans shipped from a fictitious worker, pid 99999
        {"name": "search.worker.chunk", "t0": 1.0, "t1": 2.0, "span_id": 1,
         "parent_id": None, "pid": 99999, "tid": 7, "attrs": {"chunk": 0}},
        {"name": "sim.batch", "t0": 1.2, "t1": 1.8, "span_id": 2,
         "parent_id": 1, "pid": 99999, "tid": 7, "attrs": {}},
    ]
    parent = Tracer()
    with parent.span("search.tier3") as tier3:
        parent.adopt(worker, tier3.span_id)
    by_name = {s.name: s for s in parent.spans}
    root = by_name["search.worker.chunk"]
    child = by_name["sim.batch"]
    assert root.parent_id == by_name["search.tier3"].span_id
    assert child.parent_id == root.span_id
    # ids were remapped out of the worker's private space
    assert root.span_id != 1 and child.span_id != 2
    assert root.pid == 99999 and child.pid == 99999
    assert root.attrs == {"chunk": 0}


def test_obs_pickle_round_trip_keeps_spans_and_metrics():
    obs = Obs()
    with obs.span("a"):
        obs.inc("cache.hit", 3)
        obs.observe("replan.latency_s", 0.25)
    clone = pickle.loads(pickle.dumps(obs))
    assert [s.name for s in clone.tracer.spans] == ["a"]
    assert clone.metrics.counter_value("cache.hit") == 3
    with clone.span("b"):                       # still records after thaw
        clone.inc("cache.hit")
    assert clone.metrics.counter_value("cache.hit") == 4
    assert {s.name for s in clone.tracer.spans} == {"a", "b"}


# ---------------------------------------------------------------------------
# Exporters: Perfetto JSON round-trip, trace_report rendering
# ---------------------------------------------------------------------------


def _traced_plan(executor=None, **kw):
    obs = Obs()
    res = plan_hybrid(small_topo(), DESC, global_batch=32, seq=1024,
                      with_baseline=False, executor=executor, obs=obs, **kw)
    return obs, res


def test_chrome_trace_round_trips_json(tmp_path):
    obs, _ = _traced_plan()
    path = write_trace(obs, tmp_path / "trace.json")
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events and all(ev["ph"] == "X" for ev in events)
    names = {ev["name"] for ev in events}
    assert {"plan.hybrid", "plan.enumerate", "search.cascade",
            "search.tiers012", "search.tier_lp", "search.tier3",
            "sim.batch"} <= names
    ids = {ev["args"]["span_id"] for ev in events}
    assert len(ids) == len(events)              # unique span ids
    for ev in events:
        pid = ev["args"]["parent_id"]
        assert pid is None or pid in ids        # every parent link resolves
        assert ev["dur"] >= 0.0
    snap = doc[METRICS_KEY]
    assert snap["search.simulated"] > 0
    assert snap["sim.plans"] > 0


def test_trace_report_renders_phases_and_counters():
    obs, _ = _traced_plan()
    obs.inc("cache.hit", 3)
    obs.inc("cache.miss", 1)
    obs.observe("replan.latency_s", 0.02)
    doc = chrome_trace(obs)
    out = render(doc)
    assert "self time per phase" in out
    assert "plan.hybrid" in out and "search.tier3" in out
    assert "replan.latency_s" in out and "p95=" in out
    assert "cache hit rate" in out and "75.0%" in out
    # self-time accounting: a parent's self excludes its children
    rows = {r["phase"]: r for r in phase_table(doc["traceEvents"])}
    hybrid = rows["plan.hybrid"]
    assert hybrid["total_s"] >= hybrid["self_s"] >= 0.0


def test_trace_report_renders_fabric_fidelity_line():
    obs, _ = _traced_plan()
    obs.inc("fabric.relays", 2)
    obs.inc("fabric.relay_hops", 5)
    obs.inc("fabric.chunks", 12)
    obs.inc("sim.reroute.events", 3)
    obs.inc("sim.reroute.steps", 2)
    out = render(chrome_trace(obs))
    assert "fabric fidelity" in out
    assert "2 relayed transfer(s), 2.5 hops avg, 12 chunk(s)" in out
    assert "3 mid-flight reroute event(s) across 2 split step(s)" in out


# ---------------------------------------------------------------------------
# Instrumentation: counters agree with SearchStats (the drift invariant)
# ---------------------------------------------------------------------------


def test_search_counters_match_search_stats():
    obs, res = _traced_plan(prune="cascade")
    snap = obs.metrics.snapshot()
    stats = res.search_stats
    pruned = sum(v for k, v in snap.items()
                 if isinstance(v, int) and k.startswith("search.pruned."))
    assert pruned == stats.pruned
    assert snap.get("search.pruned.coarse", 0) == stats.pruned_coarse
    assert snap.get("search.pruned.bound", 0) == stats.pruned_bound
    assert snap.get("search.pruned.feasibility", 0) == stats.pruned_feasibility
    assert snap.get("search.pruned.lp", 0) == stats.pruned_lp
    assert stats.pruned_lp > 0           # hetero cluster: the LP tier bites
    assert snap["search.simulated"] == stats.simulated


def test_replan_paths_and_latency_flow_through_registry():
    obs = Obs()
    engine = ReplanEngine(DESC, global_batch=32, seq=512,
                          cache=StrategyCache(obs=obs), obs=obs)
    topo = hetero_cluster({"V100": 8}, intra_bw_map={"V100": 25e9},
                          inter_bw=12.5e9, gpus_per_node=4)
    engine.plan(topo)
    low = hetero_cluster({"V100": 8}, intra_bw_map={"V100": 25e9 * 0.2},
                         inter_bw=12.5e9 * 0.2, gpus_per_node=4)
    res = engine.replan(low, NetworkEvent(1.0, "bandwidth", factor=0.2))
    snap = obs.metrics.snapshot()
    assert snap["replan.path.cold-plan"] == 1
    assert snap[f"replan.path.{res.path}"] == 1
    hist = snap["replan.latency_s"]
    assert hist["type"] == "histogram" and hist["count"] == 2
    # the backdated replan.<path> spans cover the whole call
    by_name = {s.name: s for s in obs.tracer.spans}
    assert by_name["replan.cold-plan"].duration == pytest.approx(
        engine.history[0].wall_time, rel=0.5)
    assert f"replan.{res.path}" in by_name
    # cache hit/miss counters are the same funnel as CacheStats
    assert snap.get("cache.hit", 0) == engine.cache.stats.hits
    assert snap.get("cache.miss", 0) == engine.cache.stats.misses


# ---------------------------------------------------------------------------
# Executor workers: spans ship back, tree shape is deterministic
# ---------------------------------------------------------------------------


def _span_shape(obs):
    """(name, parent-name, n_tasks-attr) multiset — the run's tree shape,
    independent of timings, span ids, and which worker ran which chunk."""
    by_id = {s.span_id: s.name for s in obs.tracer.spans}
    return sorted((s.name, by_id.get(s.parent_id),
                   s.attrs.get("n_tasks")) for s in obs.tracer.spans)


def test_worker_spans_ship_back_and_tree_is_deterministic():
    shapes, counters = [], []
    for _ in range(2):
        obs = Obs()
        with SearchExecutor(n_procs=2) as ex:
            plan_hybrid(small_topo(), DESC, global_batch=32, seq=1024,
                        with_baseline=False, executor=ex, obs=obs)
        spans = obs.tracer.spans
        chunks = [s for s in spans if s.name == "search.worker.chunk"]
        assert chunks, "no worker spans were shipped back"
        tier3 = next(s for s in spans if s.name == "search.tier3")
        assert all(c.parent_id == tier3.span_id for c in chunks)
        assert {c.pid for c in chunks} - {tier3.pid}, \
            "worker spans should carry worker pids"
        ids = {s.span_id for s in spans}
        assert all(s.parent_id in ids for s in spans
                   if s.parent_id is not None)
        shapes.append(_span_shape(obs))
        counters.append(obs.metrics.counter_value("search.worker.chunks"))
    assert shapes[0] == shapes[1]
    assert counters[0] == counters[1] == len(
        [s for s in shapes[0] if s[0] == "search.worker.chunk"])


# ---------------------------------------------------------------------------
# Benchmark provenance header (satellite: meta rows)
# ---------------------------------------------------------------------------


def test_bench_meta_header_written_and_ignored_by_compare(tmp_path):
    meta = bench_meta(quick=True)
    assert meta["kind"] == "meta"
    for key in ("git_sha", "timestamp_utc", "python", "jax", "quick"):
        assert key in meta
    path = tmp_path / "bench.json"
    write_json([{"topology": "hetero", "gpus": 16, "prune_rate": 0.5}],
               path, quick=True)
    rows = json.loads(path.read_text())
    assert rows[0]["kind"] == "meta" and rows[0]["quick"] is True
    assert rows[1]["gpus"] == 16
    # compare treats meta rows as absent on either side
    ps = {"topology": "hetero", "gpus": 16,
          "argmin_matches_exhaustive": True,
          "parallel_matches_serial": True, "prune_rate": 0.5,
          "pruned_coarse": 40}
    assert compare_rows("planner_search", [ps], [bench_meta(quick=True), ps]) \
        == []
    assert compare_rows("planner_search", [bench_meta(), ps], [ps]) == []


def test_chrome_trace_lane_attr_groups_onto_named_rows():
    """Spans with a `lane` attr (the planner service's per-job spans) get
    one synthetic named row per distinct lane value, labeled by a
    thread_name metadata event; laneless spans keep their OS tid."""
    obs = Obs()
    with obs.span("service.admit", lane="job-0"):
        pass
    with obs.span("service.replan", lane="job-1"):
        pass
    with obs.span("service.replan", lane="job-0"):
        pass
    with obs.span("plain"):
        pass
    doc = chrome_trace(obs)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert [m["args"]["name"] for m in meta] == ["job-0", "job-1"]
    by_lane = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X" and "lane" in e["args"]:
            by_lane.setdefault(e["args"]["lane"], set()).add(e["tid"])
    assert len(by_lane["job-0"]) == 1 and len(by_lane["job-1"]) == 1
    assert by_lane["job-0"] != by_lane["job-1"]
    lane_tids = by_lane["job-0"] | by_lane["job-1"]
    assert {m["tid"] for m in meta} == lane_tids   # rows are labeled
    plain = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"] == "plain"]
    assert plain[0]["tid"] not in lane_tids


# ---------------------------------------------------------------------------
# The profiler's clock, and regions timed once
# ---------------------------------------------------------------------------


def test_enabled_span_shows_on_the_profilers_host_plane(tmp_path):
    """With JAX imported, an enabled span is also a TraceAnnotation: a
    ``jax.profiler`` trace holds it on a host plane, around the work."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    obs = Obs()
    x = jnp.ones((64, 64))
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("trainer.batch"):
        (x @ x).block_until_ready()
    jax.profiler.stop_trace()
    NULL_OBS.span("never.recorded").__exit__(None, None, None)
    found = sorted(tmp_path.rglob("*.xplane.pb"))
    assert found
    host = [(e.name, e.duration_ns) for plane in
            ProfileData.from_file(str(found[-1])).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    mine = [d for n, d in host if n == "trainer.batch"]
    assert len(mine) == 1 and mine[0] > 0
    assert not any(n == "never.recorded" for n, _ in host)
    assert [s.name for s in obs.tracer.spans] == ["trainer.batch"]


def test_timed_region_is_read_once():
    """Enabled, a timed region's seconds are its span's own clock reads;
    disabled, it keeps its own reads beside the shared no-op handle."""
    obs = Obs()
    with obs.timed("trainer.compile", step=3) as t:
        pass
    (span,) = obs.tracer.spans
    assert (t.t0, t.t1) == (span.t0, span.t1)
    assert t.seconds == span.duration and span.attrs == {"step": 3}
    with NULL_OBS.timed("trainer.compile", step=3) as off:
        pass
    assert off._handle is NULL_HANDLE and off.seconds >= 0.0
