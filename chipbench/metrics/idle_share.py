"""Share of the traced window, in %, in which no operation ran on the
device: 1 - busy / window, from the profiler trace (``chipbench/trace.py``)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
