"""Fault-tolerant runtime: S1/S2/S3 events -> re-plan -> elastic resume."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (NetworkEvent, ParallelPlan, hetero_cluster,
                        plan_hybrid)
from repro.core.dynamic import DynamicOrchestrator, PlanTemplates
from repro.optim.adamw import AdamWConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def _tiny_cfg():
    return get_config("qwen2_7b").reduced(n_layers=2, d_model=64, vocab=128,
                                          d_ff=128)


def _tcfg(tmp_path, steps=12):
    return TrainerConfig(arch=_tiny_cfg(), steps=steps, global_batch=4,
                         seq_len=32, ckpt_dir=str(tmp_path), ckpt_every=5,
                         log_every=100,
                         opt=AdamWConfig(peak_lr=1e-3, warmup_steps=2,
                                         total_steps=20))


def test_trainer_runs_and_checkpoints(tmp_path):
    tr = Trainer(_tcfg(tmp_path))
    state, hist = tr.run()
    assert hist and np.isfinite(hist[-1]["loss"])
    from repro.checkpoint.store import latest_step
    assert latest_step(tmp_path) is not None


def test_failure_event_triggers_template_failover_and_resume(tmp_path):
    """S3: node failure -> Oobleck-style template plan -> elastic resume.

    Loss continuity: the post-failover loss stays close to pre-failure (it
    restored the checkpointed state rather than reinitializing)."""
    topo = hetero_cluster({"RTX4090D": 4, "V100": 4}, gpus_per_node=4)
    ev = NetworkEvent(0.0, "fail", device_id=7)
    cfg = _tcfg(tmp_path, steps=14)
    cfg.log_every = 1
    tr = Trainer(cfg, topo=topo, events=[(7, ev)],
                 plan=ParallelPlan(dp=2, tp=2, pp=2, microbatches=2))
    state, hist = tr.run()
    assert tr.replans == 1
    rec = tr._orch.history[-1]
    # engine-driven trainer: device-set change takes a neighborhood / full /
    # cold path; engine-less orchestrators keep the template lookup
    assert rec.action in ("template-failover", "full-replan",
                          "neighborhood", "cold-plan")
    losses = {h["step"]: h["loss"] for h in hist}
    # resumed loss (step 7, restored from the step-7 snapshot) close to the
    # trajectory before the event
    assert abs(losses[7] - losses[6]) < 1.0
    assert np.isfinite(hist[-1]["loss"])


def test_slowdown_event_reassigns_without_topology_change(tmp_path):
    topo = hetero_cluster({"RTX4090D": 4, "V100": 4}, gpus_per_node=4)
    desc = _tiny_cfg().to_model_desc()
    plan = plan_hybrid(topo, desc, global_batch=8, seq=32,
                       with_baseline=False).plan
    orch = DynamicOrchestrator(model=desc, global_batch=8, seq=32)
    ev = NetworkEvent(1.0, "slowdown", device_id=0, factor=0.25)
    topo.apply_event(ev)
    new = orch.adapt(plan, topo, ev)
    assert orch.history[-1].action == "straggler-reassign"
    assert (new.dp, new.tp, new.pp) == (plan.dp, plan.tp, plan.pp)
    # the slowed device's stage lost layers or its rank lost batch share
    assert new.stages != plan.stages or new.batch_shares != plan.batch_shares


def test_bandwidth_event_replans_only_when_worth_it():
    topo = hetero_cluster({"V100": 8}, gpus_per_node=8)
    desc = _tiny_cfg().to_model_desc()
    plan = plan_hybrid(topo, desc, global_batch=8, seq=32,
                       with_baseline=False).plan
    orch = DynamicOrchestrator(model=desc, global_batch=8, seq=32,
                               replan_threshold=1.10)
    ev = NetworkEvent(1.0, "bandwidth", factor=1.0, selector="ib")
    new = orch.adapt(plan, topo, ev)   # nothing changed -> keep
    assert orch.history[-1].action == "keep"
    assert new == plan


def test_trainer_accepts_scenario_trace(tmp_path):
    """A Trace drives the trainer: event times map onto steps, adaptation
    records surface through the public ``adaptations`` property."""
    from repro.scenarios import Trace

    topo = hetero_cluster({"RTX4090D": 4, "V100": 4}, gpus_per_node=4)
    trace = Trace.from_events(
        "unit", [NetworkEvent(5.0, "slowdown", device_id=2, factor=0.4)],
        horizon=10.0)
    cfg = _tcfg(tmp_path, steps=10)
    tr = Trainer(cfg, topo=topo, scenario=trace,
                 plan=ParallelPlan(dp=2, tp=2, pp=2, microbatches=2))
    assert tr.trace is trace
    assert [s for s, _ in tr.events] == [5]        # t=5 of 10 -> step 5
    state, hist = tr.run()
    assert tr.replans == 1 and len(tr.adaptations) == 1
    assert tr.adaptations[0].event.kind == "slowdown"
    assert tr.engine is not None and tr.engine.history
    assert np.isfinite(hist[-1]["loss"])


def test_event_restore_through_abstract_template_is_bitwise(tmp_path):
    """An event saves, replans, rebuilds and restores into the structure of
    ``abstract_train_state`` (no second materialized state): the restored
    bf16 params and fp32 moments equal the saved state bit for bit."""
    cfg = get_config("qwen2_7b").reduced(n_layers=2, d_model=64, vocab=128,
                                         d_ff=128, dtype="bfloat16")
    tcfg = TrainerConfig(arch=cfg, steps=2, global_batch=4, seq_len=32,
                         ckpt_dir=str(tmp_path), ckpt_every=0)
    topo = hetero_cluster({"V100": 2}, gpus_per_node=2)
    tr = Trainer(tcfg, topo=topo)
    state, _ = tr.run()
    saved = jax.device_get(state)
    ev = NetworkEvent(0.0, "bandwidth", factor=0.5, selector="nvlink",
                      mode="scale")
    restored = tr._handle_event(2, ev, state)
    flat_saved = jax.tree_util.tree_leaves_with_path(saved)
    flat_restored = jax.tree_util.tree_leaves(restored)
    assert len(flat_saved) == len(flat_restored)
    for (path, a), b in zip(flat_saved, flat_restored):
        b = np.asarray(jax.device_get(b))
        assert b.dtype == a.dtype and b.shape == a.shape, path
        assert b.tobytes() == np.asarray(a).tobytes(), path
    assert jax.tree.leaves(restored)[0].sharding == \
        jax.tree.leaves(tr.state_sh)[0]
    assert [e["kind"] for e in tr.event_log] == ["bandwidth"]
    assert tr.event_log[0]["stall_s"] >= tr.event_log[0]["restore_s"] > 0


def test_trainer_reports_compile_apart_from_steps(tmp_path):
    """Each (re)build compiles once, before its first step; logged steps
    carry their own wall time."""
    topo = hetero_cluster({"V100": 2}, gpus_per_node=2)
    ev = NetworkEvent(0.0, "bandwidth", factor=0.5, selector="nvlink",
                      mode="scale")
    cfg = _tcfg(tmp_path, steps=4)
    cfg.log_every = 1
    tr = Trainer(cfg, topo=topo, events=[(2, ev)])
    _, hist = tr.run()
    assert len(tr.compile_s) == 2 and all(s > 0 for s in tr.compile_s)
    assert [h["step"] for h in hist] == [0, 1, 2, 3]
    assert all(h["step_s"] > 0 for h in hist)


def test_plan_templates_failover_lookup():
    topo = hetero_cluster({"V100": 8}, gpus_per_node=8)
    desc = _tiny_cfg().to_model_desc()
    tpl = PlanTemplates.precompute(topo, desc, global_batch=8, seq=32,
                                   failure_budget=2)
    assert 8 in tpl.templates and 7 in tpl.templates
    assert tpl.plan_for(7).world <= 7
    with pytest.raises(KeyError):
        tpl.plan_for(0)


def test_trainer_with_null_obs_records_no_span(tmp_path, monkeypatch):
    """Tracing off, every span of the loop is the shared no-op handle: no
    span is opened and nothing reaches the profiler."""
    from repro.obs import NULL_OBS
    from repro.obs import tracer

    opened = []
    monkeypatch.setattr(tracer.Tracer, "span",
                        lambda self, name, **kw: opened.append(name))
    monkeypatch.setattr(tracer, "_profiler_annotation",
                        lambda name: opened.append(name))
    cfg = _tcfg(tmp_path, steps=6)
    cfg.log_every = 1
    tr = Trainer(cfg, obs=NULL_OBS)
    _, hist = tr.run()
    assert tr.obs is NULL_OBS and opened == []
    assert len(tr.compile_s) == 1 and all(h["step_s"] > 0 for h in hist)


def test_trainer_spans_are_its_timings(tmp_path):
    """Tracing on, the loop and the event handler record their spans, and
    compile_s, step_s, restore_s and stall_s are those spans' own reads."""
    from repro.obs import Obs

    topo = hetero_cluster({"V100": 2}, gpus_per_node=2)
    ev = NetworkEvent(0.0, "bandwidth", factor=0.5, selector="nvlink",
                      mode="scale")
    cfg = _tcfg(tmp_path, steps=6)
    cfg.log_every = 2
    obs = Obs()
    tr = Trainer(cfg, topo=topo, events=[(3, ev)], obs=obs)
    _, hist = tr.run()
    spans = obs.tracer.spans
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert len(by["trainer.batch"]) == len(by["trainer.dispatch"]) == 6
    assert [s.attrs["step"] for s in by["trainer.compile"]] == [0, 3]
    assert tr.compile_s == [s.duration for s in by["trainer.compile"]]
    assert len(by["trainer.ckpt"]) == 1             # step 5, ckpt_every 5
    syncs = by["trainer.sync"]
    assert len(syncs) == len(hist) == 4             # steps 0, 2, 4, 5
    dispatches = by["trainer.dispatch"]             # one per step, in order
    for h, sync in zip(hist, syncs):
        assert h["step_s"] == sync.t1 - dispatches[h["step"]].t0
    # a log step before the last is synced once the next step is
    # dispatched, but before an event that is due next (step 2: the event
    # at 3 reads the history)
    assert [max(i for i, d in enumerate(dispatches) if d.t1 <= sync.t0)
            for sync in syncs] == [1, 2, 5, 5]
    (event,) = by["trainer.event"]
    assert event.attrs == {"kind": "bandwidth"}
    for part in ("save", "replan", "rebuild", "restore"):
        (child,) = by[f"trainer.event.{part}"]
        assert child.parent_id == event.span_id
    assert tr.event_log[0]["stall_s"] == event.duration
    assert tr.event_log[0]["restore_s"] == \
        by["trainer.event.restore"][0].duration
