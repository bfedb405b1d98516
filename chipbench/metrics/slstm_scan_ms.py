"""Device self time per traced step, in ms, of the sLSTM's sequential time
scan (scope ``slstm_scan``), the loops' own overhead included, forward,
recomputed and backward. From the profiler trace (``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "slstm_scan")
