"""The chip benchmark: cells, metrics and bounds are in ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Layout (everything of one configuration, traffic mix or metric is a file
of its own, found by its name):

    configs/<config>.json       sizes, optimizer, batch, as run, and the
                                named scopes of its own kernels
    traffic/<traffic>.json      the mix: sequence length, events, chunks
    limits/<cell>.json          the limits of the comparison for a cell
    metrics/<metric>.py         one reader per per-layer metric
    flops/<block kind>.py       model FLOPs per token of one layer
    flops/<kernel>.py           a kernel's FLOPs and bytes in one step
    reference/<block kind>.py   plain float32 reference of one block kind
    peaks.json                  the chip's peaks, by ``device_kind``
    run.py, cell.py, spec.py    the harness; check.py the comparison;
    trace.py, scopes.py         the profiler trace's reduction, and per
                                named scope; roofline.py a kernel's share;
    calibrate.py                the readings the limits are set from.
"""
