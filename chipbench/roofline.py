"""A kernel's share of its roofline, in %, from the profiler trace.

    share = 100 x max(flops / bf16 FLOP/s, bytes / HBM bytes/s) / time

``time`` is the device self time per traced step of the kernel's named
scope (``chipbench/scopes.py``).  ``flops`` and ``bytes`` are the whole
step's, counted from shapes by a file kept with the benchmark
(``chipbench/flops``): model FLOPs with no recomputation, and each input
read once and each output written once, forward and backward, at the
configuration's dtypes.  Both are least counts, whatever implements the
kernel, so no correct program reads above 100%.  The peaks are the chip's
(``chipbench/peaks.json``); a step on several chips shares its counts
evenly among them.
"""

from __future__ import annotations

from chipbench.scopes import per_step_ms


def share(run, scope: str, flops: float, nbytes: float):
    """The roofline share of ``scope`` in a finished run
    (:class:`chipbench.cell.RunRecord`); None in an untraced run, where no
    operation of ``scope`` ran, or where there is nothing to count."""
    ms = per_step_ms(run, scope)
    if ms is None or not (flops or nbytes):
        return None
    least_s = max(flops / run.peak_flops,
                  nbytes / run.hbm_bytes_per_s) / run.chips
    return 100.0 * least_s / (ms / 1000.0)
