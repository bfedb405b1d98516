"""Fault-tolerant training runtime.

Drives the jitted train step over the synthetic pipeline with:

  * periodic async checkpoints (repro.checkpoint),
  * an *event loop* mirroring the paper's dynamic scenarios: injected
    :class:`NetworkEvent`s (S1 bandwidth / S2 slowdown / S3 failure) are
    applied to the analytic :class:`ClusterTopology`, the
    :class:`DynamicOrchestrator` re-plans (template failover for failures,
    local reassignment for stragglers, threshold re-plan for bandwidth), and
    the trainer rebuilds its mesh/shardings and elastically reshards the
    restored checkpoint onto the new layout.

The plan is carried into checkpoints but not yet executed: the mesh is the
one passed in, or a data-parallel ``(n, 1)`` mesh over ``jax.devices()`` of
this one process, and every device gets an equal batch share.

Each (re)build compiles the step ahead of time at its first use, so
``compile_s`` holds compile time apart from step time; every logged history
entry carries ``step_s``, the step's wall time up to ``block_until_ready``;
``event_log`` holds each handled event's stall (save through restore).

With ``repro.obs`` enabled (``obs=`` or ``REPRO_TRACE``) the loop records
spans, mirrored onto the profiler's clock: ``trainer.batch`` (make and
place the batch), ``trainer.compile`` (attribute ``step``),
``trainer.dispatch`` (the compiled call), ``trainer.sync`` (a log step's
``block_until_ready``), ``trainer.ckpt`` (a periodic save's submit), and
per event ``trainer.event`` (attribute ``kind``) around
``trainer.event.save``, ``.replan``, ``.rebuild`` and ``.restore``.  The
timings above are those spans' own clock reads.  A log step other than
the run's last is synced once the next step is dispatched, so the device
is not left idle while the host reads and prints it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import (ClusterTopology, DynamicOrchestrator, ModelDesc,
                        NetworkEvent, ParallelPlan, ReplanEngine,
                        StrategyCache)
from repro.checkpoint.store import AsyncSaver, latest_step, restore
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.config import ArchConfig
from repro.models.lm import LM
from repro.obs import Obs, resolve_obs
from repro.optim.adamw import AdamWConfig
from repro.parallel import sharding as shd
from repro.parallel.axes import use_rules
from repro.parallel.trainstep import (abstract_train_state, init_train_state,
                                      make_train_step)

Pytree = Any


@dataclass
class TrainerConfig:
    arch: ArchConfig
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 20
    log_every: int = 10
    remat: str = "none"
    microbatches: int = 1
    zero3: bool = False
    opt: AdamWConfig = field(default_factory=AdamWConfig)
    seed: int = 0


class Trainer:
    def __init__(self, cfg: TrainerConfig, *,
                 mesh: Mesh | None = None,
                 plan: ParallelPlan | None = None,
                 topo: ClusterTopology | None = None,
                 events: Sequence[tuple[int, NetworkEvent]] = (),
                 scenario: "str | object | None" = None,
                 obs: Obs | None = None):
        self.cfg = cfg
        self.obs = resolve_obs(obs)
        self.model = LM(cfg.arch)
        self.plan = plan
        self.topo = topo
        self.trace = None
        events = list(events)
        if scenario is not None:
            # a catalog name or a repro.scenarios.Trace: event times map
            # onto training steps via Trace.to_step_events, and a catalog
            # name also supplies the topology when none was given
            from repro.scenarios import Trace, build_trace, get_scenario
            if isinstance(scenario, str):
                self.trace = build_trace(scenario, seed=cfg.seed)
                if topo is None:
                    topo = self.topo = get_scenario(scenario).make_topology()
            elif isinstance(scenario, Trace):
                if topo is None:
                    raise ValueError(
                        "an explicit Trace needs an explicit topo=")
                self.trace = scenario
            else:
                raise TypeError(f"scenario must be a catalog name or Trace, "
                                f"got {type(scenario).__name__}")
            events += self.trace.to_step_events(cfg.steps)
        if topo is not None:
            # fail fast on a trace/topology mismatch instead of KeyError-ing
            # mid-run (e.g. a 16-device catalog trace on an 8-device topo)
            missing = sorted({ev.device_id for _, ev in events
                              if ev.device_id is not None}
                             - set(topo.devices))
            if missing:
                raise ValueError(
                    f"events reference device ids {missing} not present "
                    f"in the topology ({sorted(topo.devices)})")
        self.events = sorted(events, key=lambda e: e[0])
        self.saver = AsyncSaver()
        self.history: list[dict] = []
        self.replans = 0
        self.compile_s: list[float] = []
        self.event_log: list[dict] = []
        self._start_step = 0
        self._hist_mark = 0
        self._orch = None
        self._engine = None
        if topo is not None:
            desc = cfg.arch.to_model_desc()
            # the incremental re-planning engine handles every event kind
            # (device-set changes included), so the Oobleck-style
            # PlanTemplates precompute is no longer paid here — it remains
            # available for engine-less DynamicOrchestrator users
            self._engine = ReplanEngine(
                desc, global_batch=cfg.global_batch, seq=cfg.seq_len,
                cache=StrategyCache())
            # cold plan up front: warms the strategy cache + candidate
            # portfolio so every later event takes a warm path
            self._engine.plan(topo)
            self._orch = DynamicOrchestrator(
                model=desc, global_batch=cfg.global_batch, seq=cfg.seq_len,
                engine=self._engine)
        self._build(mesh)

    # -- public adaptation telemetry ------------------------------------------

    @property
    def adaptations(self) -> list:
        """Adaptation records (one per handled event) — the public view of
        the orchestrator history; empty when no topology was attached."""
        return list(self._orch.history) if self._orch is not None else []

    @property
    def engine(self):
        """The incremental ReplanEngine (None when no topology attached)."""
        return self._engine

    # -- (re)build against the current mesh/plan -----------------------------

    def _build(self, mesh: Mesh | None) -> None:
        if mesh is None:
            n = len(jax.devices())
            mesh = Mesh(np.array(jax.devices()).reshape(n, 1),
                        ("data", "model"))
        self.mesh = mesh
        self.prof = shd.profile_for(self.cfg.arch, mesh,
                                    zero3=self.cfg.zero3)
        self.state_sh = {
            "params": shd.param_shardings(self.model, mesh, self.prof.rules),
            "opt": shd.opt_state_shardings(self.model, mesh,
                                           self.prof.opt_rules),
        }
        step_fn = make_train_step(self.model, self.cfg.opt,
                                  microbatches=self.cfg.microbatches,
                                  remat=self.cfg.remat)

        def wrapped(state, batch):
            with use_rules(mesh, self.prof.rules):
                return step_fn(state, batch)

        self._jit = jax.jit(wrapped, in_shardings=(self.state_sh, None),
                            out_shardings=(self.state_sh, None),
                            donate_argnums=(0,))
        self._compiled = None
        a = self.cfg.arch
        self.data = SyntheticLM(DataConfig(
            vocab=a.vocab, seq_len=self.cfg.seq_len,
            global_batch=self.cfg.global_batch, seed=self.cfg.seed,
            audio_seq=a.audio_seq if a.encoder_layers else 0,
            vision_seq=a.vision_seq if a.cross_attn_every else 0,
            d_model=a.d_model))

    def init_state(self) -> Pytree:
        state = init_train_state(self.model, jax.random.PRNGKey(self.cfg.seed))
        return jax.device_put(state, self.state_sh)

    def _place(self, batch: dict) -> dict:
        out = {}
        for k, v in batch.items():
            axes = ["batch"] + [None] * (v.ndim - 1)
            sh = self.prof.rules.sharding(axes, v.shape, self.mesh)
            out[k] = jax.device_put(v, sh)
        return out

    # -- event handling (paper §2.2: S1/S2/S3) --------------------------------

    def _handle_event(self, step: int, ev: NetworkEvent,
                      state: Pytree) -> Pytree:
        assert self.topo is not None and self._orch is not None
        obs = self.obs
        with obs.timed("trainer.event", kind=ev.kind) as stall:
            with obs.span("trainer.event.save"):
                self.saver.wait()
                ck = Path(self.cfg.ckpt_dir) / f"step_{step}"
                self.saver.submit(ck, state, step=step,
                                  plan_json=self.plan.to_json()
                                  if self.plan else "")
                self.saver.wait()
            with obs.span("trainer.event.replan"):
                self.topo.apply_event(ev)
                if (self._engine is not None
                        and len(self.history) > self._hist_mark):
                    # remaining-horizon budget for the engine's switch-cost
                    # hysteresis: steps left x the measured mean step wall
                    # time.  Only entries logged by *this* run() invocation
                    # qualify: their wall is measured from this run's t0
                    # and covers the steps since start_step (a previous
                    # run's entries would mix timebases)
                    m = self.history[-1]
                    done = max(m["step"] - self._start_step + 1, 1)
                    self._engine.switch_horizon_s = \
                        (self.cfg.steps - step) * m["wall"] / done
                old_plan = self.plan or ParallelPlan()
                self.plan = self._orch.adapt(old_plan, self.topo, ev)
                self.replans += 1
            # rebuild (the mesh shape may change on a real cluster; on the
            # host mesh we rebuild shardings/jit against the new plan) and
            # reshard the checkpoint elastically onto the new layout.
            with obs.span("trainer.event.rebuild"):
                self._build(self.mesh)
            with obs.timed("trainer.event.restore") as rs:
                restored, _ = restore(ck, abstract_train_state(self.model),
                                      shardings=self.state_sh)
                jax.block_until_ready(restored)
        self.event_log.append({"step": step, "kind": ev.kind,
                               "restore_s": rs.seconds,
                               "stall_s": stall.seconds})
        if self._engine is not None:
            # calibration hook: fold the measured checkpoint-restore path
            # into the reconfiguration cost model, so simulated switch
            # charges track what elastic restore costs on this deployment
            nbytes = sum(
                getattr(leaf, "nbytes", 0)
                for leaf in jax.tree_util.tree_leaves(restored))
            self._engine.reconfig.calibrate_io(rs.seconds, float(nbytes))
        return restored

    # -- main loop -------------------------------------------------------------

    def run(self, state: Pytree | None = None,
            start_step: int = 0) -> tuple[Pytree, list[dict]]:
        cfg = self.cfg
        obs = self.obs
        state = state if state is not None else self.init_state()
        self._start_step = start_step
        self._hist_mark = len(self.history)
        ev_i = 0
        t0 = time.perf_counter()
        # a log step other than the last is read once the next step is
        # dispatched, so that the device has work queued while the host
        # waits for the logged step and prints it
        pending = None
        for step in range(start_step, cfg.steps):
            if pending and ev_i < len(self.events) \
                    and self.events[ev_i][0] == step:
                self._log(t0, *pending)
                pending = None
            while ev_i < len(self.events) and self.events[ev_i][0] == step:
                _, ev = self.events[ev_i]
                state = self._handle_event(step, ev, state)
                ev_i += 1
            with obs.span("trainer.batch"):
                batch = self._place(self.data.batch(step))
            if self._compiled is None:
                with obs.timed("trainer.compile", step=step) as compiling:
                    self._compiled = self._jit.lower(state, batch).compile()
                self.compile_s.append(compiling.seconds)
            with obs.timed("trainer.dispatch") as dispatch:
                state, metrics = self._compiled(state, batch)
            if pending:
                self._log(t0, *pending)
                pending = None
            if step == cfg.steps - 1:
                self._log(t0, step, metrics, dispatch.t0, state)
            elif step % cfg.log_every == 0:
                pending = (step, metrics, dispatch.t0)
            if cfg.ckpt_every and step and step % cfg.ckpt_every == 0:
                with obs.span("trainer.ckpt"):
                    self.saver.submit(Path(cfg.ckpt_dir) / f"step_{step}",
                                      state, step=step,
                                      plan_json=self.plan.to_json()
                                      if self.plan else "")
        self.saver.wait()
        return state, self.history

    def _log(self, t0: float, step: int, metrics: dict, dispatched: float,
             state: Pytree = None) -> None:
        """Wait for the step's metrics (and ``state``, the last step's),
        then record and print them; ``step_s`` runs from its dispatch."""
        with self.obs.timed("trainer.sync") as sync:
            jax.block_until_ready((state, metrics))
        m = {k: float(v) for k, v in jax.device_get(metrics).items()}
        m.update(step=step, wall=sync.t1 - t0,
                 step_s=sync.t1 - dispatched)
        self.history.append(m)
        tok_s = m["tokens"] * (step - self._start_step + 1) / m["wall"]
        print(f"  step {step:4d} loss {m['loss']:.4f} "
              f"gnorm {m['grad_norm']:.2f} lr {m['lr']:.2e} "
              f"tok/s {tok_s:,.0f}", flush=True)
