"""One run of one training cell.

Set-up: the program's ``Trainer`` is built as a user builds it, with the
cell's settings; the weights and optimizer state are made on the device
from the seed in one jitted call; the first three steps run through
``Trainer.run`` one step at a time (compiling the step, and in an event
mix handling one event), and what the comparison needs is read from them.

Window: ``Trainer.run`` is called on chunks of whole steps until
``seconds`` have passed and the last chunk was steady; each chunk ends
blocked on its last step.  In an event mix, each event's step is a chunk
of its own, so its handling and the first step after it are timed from
outside the program, and the window holds whole cycles.  With
``trace`` the profiler records one part before the window (a few steady
steps, or one event and the steps up to the next) in a span named
``chipbench.traced``.

Afterwards the program's state is freed and the reference follows the
first three steps (``chipbench/check.py``).
"""

from __future__ import annotations

import gc
import os
import shutil
import statistics
import time
from dataclasses import dataclass, field
from pathlib import Path

from chipbench import check, data, events, flops, metrics, trace as tracing
from chipbench.spec import ROOT, Cell

RUN_DIR = ROOT / ".chipbench_run"
CHECK_STEPS = 3
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass
class RunRecord:
    """What the window left for the readers in ``chipbench/metrics``, and
    what they count from: the configuration's file as run, the traffic's
    sequence length and the chip's peaks (``peaks.json``)."""
    chips: int
    flops_per_token: float
    peak_flops: float
    hbm_bytes_per_s: float
    config: dict
    seq_len: int
    chunks: list = field(default_factory=list)
    trace: dict | None = None
    window_events: list = field(default_factory=list)
    window_recompile_s: list = field(default_factory=list)
    window_replan_s: list = field(default_factory=list)


def peaks(kind: str) -> dict:
    import json
    table = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def build_trainer(cell: Cell, seed: int, ckpt_dir: Path):
    """The program's Trainer with the cell's settings, and the events."""
    from repro.core import NetworkEvent
    from repro.models.config import ArchConfig
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    arch = cell.config["arch"]
    a = dict(arch, block_pattern=tuple(arch["block_pattern"]))
    tcfg = TrainerConfig(
        arch=ArchConfig(**a), steps=1,
        global_batch=cell.config["global_batch"],
        seq_len=cell.traffic["seq_len"], ckpt_dir=str(ckpt_dir),
        ckpt_every=cell.traffic["ckpt_every"], remat=cell.config["remat"],
        opt=AdamWConfig(**cell.config["opt"]), seed=seed)
    ev_spec = cell.traffic.get("events")
    if not ev_spec:
        return Trainer(tcfg), []
    sched = events.schedule(ev_spec, seed, ev_spec["count"])
    evs = [(s, NetworkEvent(0.0, "bandwidth", factor=f, selector=sel,
                            mode="scale")) for s, sel, f in sched]
    return Trainer(tcfg, topo=events.topology(ev_spec["topology"]),
                   events=evs), [s for s, _, _ in sched]


def state_maker(cell: Cell, shardings):
    """jitted key data -> the program's train state, from the seed."""
    import jax
    import jax.numpy as jnp
    from repro.optim.adamw import OptState

    from chipbench.weights import make_params

    def make(kd):
        p = make_params(cell.config["arch"], jax.random.wrap_key_data(kd))
        z = lambda x: jnp.zeros(x.shape, jnp.float32)
        return {"params": p, "opt": OptState(
            m=jax.tree.map(z, p), v=jax.tree.map(z, p),
            step=jnp.zeros((), jnp.int32))}

    return jax.jit(make, out_shardings=shardings)


def plan_chunks(start: int, event_steps: list, chunk_steps: int):
    """Endless (kind, first step, end) chunks from ``start``.  A steady chunk
    ends at the next multiple of ``chunk_steps`` or at the next event, so
    that with ``chunk_steps`` a multiple of the trainer's ``log_every`` the
    step on which the trainer blocks to log is the first of a chunk, and
    the steps after it are all dispatched before it waits again."""
    later = sorted(s for s in event_steps if s >= start)
    s = start
    while True:
        if later and later[0] == s:
            later.pop(0)
            yield "event", s, s + 1
            s += 1
        else:
            end = min([(s // chunk_steps + 1) * chunk_steps] + later[:1])
            yield "steady", s, end
            s = end


def enable_compile_cache() -> None:
    """JAX's persistent cache on, for every program however small."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Programs compiled from now on: requests to the persistent cache
    that it could not serve (a program found there is loaded, not
    compiled), and the seconds spent in the backend."""

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        self.seconds = 0.0

        def event(name, **kw):
            if name == CACHE_REQUEST:
                self.requests += 1
            elif name == CACHE_HIT:
                self.hits += 1

        def duration(name, secs, **kw):
            if name == BACKEND_COMPILE:
                self.seconds += secs

        jax.monitoring.register_event_listener(event)
        jax.monitoring.register_event_duration_secs_listener(duration)

    @property
    def compiled(self) -> int:
        return self.requests - self.hits


def advance(trainer, held: list, start: int) -> None:
    """``Trainer.run`` from ``start`` on the state that ``held`` holds, which
    lets go of it first: a state that an event replaces is then freed
    before the steps after the event run."""
    state, _ = trainer.run(held.pop(), start)
    held.append(state)


def setup(cell: Cell, seed: int, ckpt_dir: Path):
    """Build the trainer and state, drive the first steps.  Returns
    (trainer, event steps, [state], the program's readings: losses,
    per-leaf norms of the first step's gradient, parameters after the
    last)."""
    import jax
    import numpy as np

    from chipbench.weights import key_data

    trainer, event_steps = build_trainer(cell, seed, ckpt_dir)
    held = [state_maker(cell, trainer.state_sh)(key_data(seed))]
    for k in range(CHECK_STEPS):
        trainer.cfg.steps = k + 1
        advance(trainer, held, k)
        if k == 0:
            grad = np.asarray(check.leaf_norms(held[0]["opt"].m)) \
                / (1.0 - cell.config["opt"]["b1"])
    # kept on the host, so that the window's memory is the program's alone
    p_after = jax.device_get(held[0]["params"])
    losses = [h["loss"] for h in trainer.history[:CHECK_STEPS]]
    return trainer, event_steps, held, {"losses": losses, "grad": grad,
                                        "params": p_after}


def batches(cell: Cell, seed: int):
    cfg = cell.config["arch"]
    return [data.batch(seed, k, rows=cell.config["global_batch"],
                       seq=cell.traffic["seq_len"], vocab=cfg["vocab"])
            for k in range(CHECK_STEPS)]


def make_reference(cell: Cell, precision: str = "float32"):
    from chipbench.reference.model import Reference
    return Reference(cell.config["arch"], cell.config["opt"],
                     rows=cell.config["reference_rows"], precision=precision)


def reference(cell: Cell, seed: int, ref=None, feed=None) -> dict:
    """The readings of ``ref`` (by default the float32 reference) over the
    first steps, from the seed's weights and batches; ``feed`` may alter
    the batches (a planted fault)."""
    import numpy as np

    from chipbench.weights import key_data, param_maker

    p0 = param_maker(cell.config["arch"])(key_data(seed))
    b = batches(cell, seed)
    ref = ref or make_reference(cell)
    losses, grad, params = ref.train(p0, feed(b) if feed else b)
    return {"losses": losses, "grad": np.asarray(grad),
            "change": np.asarray(check.change_norms(params, p0))}


def compare(cell: Cell, seed: int, prog: dict, ref: dict):
    """(readings, correct, {name: value and limit}) of the program's
    readings against the reference's."""
    import numpy as np

    from chipbench.weights import key_data, param_maker

    p0 = param_maker(cell.config["arch"])(key_data(seed))
    if "change" not in prog:
        prog = dict(prog, change=np.asarray(
            check.change_norms(prog["params"], p0)))
    values = check.readings(prog, ref, check.leaf_names(p0))
    correct, compared = check.judge(values, cell.limits)
    return values, correct, compared


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, log=print) -> dict:
    import jax
    import numpy as np

    enable_compile_cache()
    compiles = CompileCounter()
    devs = jax.devices()
    peak = peaks(devs[0].device_kind)
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    RUN_DIR.mkdir(parents=True)
    tokens_per_step = cell.config["global_batch"] * cell.traffic["seq_len"]

    # -- set-up --------------------------------------------------------------
    trainer, event_steps, held, prog = setup(cell, seed, RUN_DIR / "ckpt")
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {setup_s:.3f} s; compiled {compiles.compiled} programs, "
        f"{compiles.hits} from the cache ({compiles.seconds:.2f} s in the "
        f"backend); losses {prog['losses']}")

    # -- traced part and window ---------------------------------------------
    rec = RunRecord(chips=cell.chips, peak_flops=peak["bf16_flops_per_s"],
                    hbm_bytes_per_s=peak["hbm_bytes_per_s"],
                    flops_per_token=flops.train_per_token(
                        cell.config["arch"], cell.traffic["seq_len"]),
                    config=cell.config, seq_len=cell.traffic["seq_len"])
    n_compiled = compiles.compiled
    n_events_before = len(trainer.event_log)
    chunks = plan_chunks(CHECK_STEPS, event_steps,
                         cell.traffic["chunk_steps"])

    def do_chunk(kind, s, e, traced):
        trainer.cfg.steps = e
        # Trainer.run scans its event list from the first entry and stops
        # at the first one that is not due at ``start_step``: events already
        # handled are dropped so that a run resumed at ``s`` sees its own
        trainer.events = [ev for ev in trainer.events if ev[0] >= s]
        with jax.profiler.TraceAnnotation(f"chipbench.{kind}"):
            t0 = time.perf_counter()
            advance(trainer, held, s)
            t1 = time.perf_counter()
        rec.chunks.append({"kind": kind, "steps": e - s, "traced": traced,
                           "tokens": (e - s) * tokens_per_step,
                           "t0": t0, "t1": t1})

    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(RUN_DIR / "trace"),
                                 profiler_options=opts)
        with jax.profiler.TraceAnnotation("chipbench.traced"):
            if event_steps:
                do_chunk(*next(chunks), True)
                do_chunk(*next(chunks), True)
            else:
                end = CHECK_STEPS + cell.traffic["trace_steps"]
                do_chunk("steady", CHECK_STEPS, end, True)
                chunks = plan_chunks(end, [], cell.traffic["chunk_steps"])
        jax.profiler.stop_trace()
    t_window = time.perf_counter()
    first_window_chunk = len(rec.chunks)
    # the window closes on a steady chunk, so that in an event mix it holds
    # whole cycles (an event, then the steps up to the next one)
    while (time.perf_counter() - t_window < seconds
           or rec.chunks[-1]["kind"] != "steady"):
        do_chunk(*next(chunks), False)
    t_end = time.perf_counter()
    window_compiles = compiles.compiled - n_compiled
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    window = rec.chunks[first_window_chunk:]
    steps_run = sum(c["steps"] for c in rec.chunks)
    failed = sum(not np.isfinite(h["loss"])
                 for h in trainer.history[CHECK_STEPS:])
    rec.window_events = trainer.event_log[n_events_before:]
    rec.window_recompile_s = trainer.compile_s[1 + n_events_before:]
    if os.environ.get("REPRO_TRACE"):
        from repro.obs import default_obs
        t_first = rec.chunks[0]["t0"] if rec.chunks else t_window
        rec.window_replan_s = [
            sp.duration for sp in default_obs().tracer.spans
            if sp.name.startswith("replan.") and sp.t0 >= t_first]
    log(f"[window] {len(window)} chunks, {steps_run} steps after set-up, "
        f"{t_end - t_window:.3f} s; programs compiled in it: "
        f"{window_compiles}; peak bytes {memory_peak}")
    log("[window] chunk seconds: " + " ".join(
        f"{c['kind'][0]}{c['steps']}:{c['t1'] - c['t0']:.3f}" for c in window))
    if window_compiles:
        raise RuntimeError(f"{window_compiles} programs compiled inside the "
                           f"measured part")

    # -- free the program's state, reduce the trace -------------------------
    del held, trainer
    gc.collect()
    shutil.rmtree(RUN_DIR / "ckpt", ignore_errors=True)
    if trace:
        spans, ops = tracing.read(RUN_DIR / "trace")
        rec.trace = tracing.reduce(spans, ops, window="chipbench.traced")
        del spans, ops
        log(f"[trace] busy {rec.trace['busy_s']:.4f} s of "
            f"{rec.trace['window_s']:.4f} s")

    # -- the reference follows the first steps -------------------------------
    t_ref = time.perf_counter()
    ref = reference(cell, seed)
    values, correct, compared = compare(cell, seed, prog, ref)
    log(f"[check] reference {time.perf_counter() - t_ref:.3f} s; losses "
        f"{prog['losses']} vs {ref['losses']}; worst: "
        + ", ".join(f"{k} at {v[1]}" for k, v in values.items()))

    # -- metrics ------------------------------------------------------------
    out = {}
    if trace:
        for m in cell.per_layer:
            v = metrics.read(m["name"], rec)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        wall = t_end - t_window
        ev = [c["t1"] - c["t0"] for c in window if c["kind"] == "event"]
        e2e = {"tokens_per_s": sum(c["tokens"] for c in window) / wall,
               "resume_s": statistics.fmean(ev) if ev else None,
               "setup_s": setup_s}
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                out[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": steps_run, "failed": failed,
              "metrics": out, "device": device}
    if trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    result["check"] = compared
    return result
