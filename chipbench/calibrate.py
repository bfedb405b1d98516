"""Readings that the limits of ``chipbench/limits/<cell>.json`` are set
from, at the cell's own size, on the chip.  Not part of a benchmark run.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--faults 1,2,3] [--out readings.jsonl] [--raw sides.jsonl]

For each of ``--seeds``: the program's first steps through the trainer
(as a run's set-up drives them) against the float32 reference.  For each
of ``--faults``, in a mix without events: the control (the reference
computed with float8 operands, one precision below the configuration's
bfloat16) and the planted faults (the reference fed half of each batch,
and fed labels shifted by one position) against the float32 reference.
These depend on the configuration, the batch and the seed alone, so an
event mix of the same configuration and batch has the same readings; for
each of its ``--faults`` it reads instead the program's set-up with its
checkpoint restore broken (the Adam moments zeroed, the step counter put
back to 0).  ``--raw`` keeps each side's losses and per-leaf norms, from
which another compared number can be read again without a chip.  A step that returns its state unchanged reads 1 on
``grad_gap`` and ``step_gap_med`` by their definition and needs no run.  One
JSON line per reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def half_batch(batches):
    return [(t[:len(t) // 2], l[:len(l) // 2]) for t, l in batches]


def shifted_labels(batches):
    import numpy as np
    return [(t, np.roll(l, 1, axis=1)) for t, l in batches]


def zeroed_moments(state):
    import jax
    import jax.numpy as jnp
    z = lambda t: jax.tree.map(jnp.zeros_like, t)
    opt = state["opt"]
    return dict(state, opt=opt._replace(m=z(opt.m), v=z(opt.v)))


def step_reset(state):
    import jax.numpy as jnp
    opt = state["opt"]
    return dict(state, opt=opt._replace(step=jnp.zeros_like(opt.step)))


RESTORE_FAULTS = {"restore_zeroed_moments": zeroed_moments,
                  "restore_step_reset": step_reset}


def broken_restore(fault):
    """Plant ``fault`` in the program's checkpoint restore, as the trainer
    calls it after an event; returns the function that takes it out."""
    import repro.runtime.trainer as T
    real = T.restore

    def restore(*a, **kw):
        state, manifest = real(*a, **kw)
        return fault(state), manifest

    T.restore = restore
    return lambda: setattr(T, "restore", real)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--raw", default="",
                    help="also write each side's losses and per-leaf norms")
    args = ap.parse_args(argv)
    here = str(ROOT / "chipbench")
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != here]
    import jax

    from chipbench import cell as C, check
    from chipbench.spec import resolve
    from chipbench.weights import key_data, param_maker

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    cell = resolve(args.workload)
    C.enable_compile_cache()
    out = open(args.out, "a") if args.out else None
    raw = open(args.raw, "a") if args.raw else None

    t0 = time.perf_counter()

    def emit(kind, seed, values, **extra):
        line = json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                           **{k: v[0] for k, v in values.items()},
                           "worst": {k: v[1] for k, v in values.items()},
                           "t": round(time.perf_counter() - t0, 1), **extra})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def dump(kind, seed, side: dict):
        if raw:
            raw.write(json.dumps({"workload": cell.name, "kind": kind,
                                  "seed": seed, **{
                k: [float(x) for x in side[k]]
                for k in ("losses", "grad", "change")}}) + "\n")
            raw.flush()

    seeds = [int(s) for s in args.seeds.split(",") if s]
    faults = [int(s) for s in args.faults.split(",") if s]
    exact = C.make_reference(cell)

    def program(seed):
        trainer, _, held, prog = C.setup(cell, seed, C.RUN_DIR / "ckpt")
        del trainer, held
        gc.collect()
        return prog

    for seed in seeds:
        prog = program(seed)
        ref = C.reference(cell, seed, exact)
        emit("program", seed, C.compare(cell, seed, prog, ref)[0],
             losses=[float(x) for x in prog["losses"]],
             ref_losses=[float(x) for x in ref["losses"]])
        p0 = param_maker(cell.config["arch"])(key_data(seed))
        prog["change"] = check.change_norms(prog["params"], p0)
        dump("program", seed, prog)
        dump("reference", seed, ref)
        del prog, p0
    if cell.traffic.get("events"):
        for seed in faults:
            ref = C.reference(cell, seed, exact)
            dump("reference", seed, ref)
            for kind, fault in RESTORE_FAULTS.items():
                take_out = broken_restore(fault)
                try:
                    prog = program(seed)
                finally:
                    take_out()
                emit(kind, seed, C.compare(cell, seed, prog, ref)[0])
                p0 = param_maker(cell.config["arch"])(key_data(seed))
                prog["change"] = check.change_norms(prog["params"], p0)
                dump(kind, seed, prog)
                del prog, p0
        return 0
    plants = {"control_fp8": dict(ref=C.make_reference(cell, "fp8")),
              "half_batch": dict(ref=exact, feed=half_batch),
              "shifted_labels": dict(ref=exact, feed=shifted_labels)}
    if cell.config["global_batch"] < 2:
        del plants["half_batch"]
    names = check.leaf_names(jax.eval_shape(
        param_maker(cell.config["arch"]), key_data(0)))
    for seed in faults:
        ref = C.reference(cell, seed, exact)
        dump("reference", seed, ref)
        for kind, kw in plants.items():
            side = C.reference(cell, seed, **kw)
            emit(kind, seed, check.readings(side, ref, names))
            dump(kind, seed, side)
    return 0


if __name__ == "__main__":
    sys.exit(main())
