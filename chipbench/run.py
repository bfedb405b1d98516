"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, metrics and bounds are in ``BENCHMARK.json`` at the root of the
checkout.  ``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced), and last ``check``, each compared number beside
its limit; the same numbers are the last lines of standard error.

The run needs a TPU with at least the cell's chips; without one it exits
with code 2 and prints no result.  JAX's compile cache is kept in
``$JAX_COMPILATION_CACHE_DIR`` if set, else in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    # the package is imported as ``chipbench``; its own directory, which
    # Python puts first for a script, would shadow modules such as ``trace``
    here = str(ROOT / "chipbench")
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != here]
    from chipbench.spec import resolve
    cell = resolve(args.workload)
    if args.trace and cell.traffic.get("events"):
        os.environ["REPRO_TRACE"] = str(ROOT / ".chipbench_run" /
                                        "repro_trace.json")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    from chipbench.cell import run
    result = run(cell, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), t_start=T_START,
                 log=lambda s: print(s, file=sys.stderr, flush=True))
    for k, v in result["check"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
