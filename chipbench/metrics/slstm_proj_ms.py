"""Device self time per traced step, in ms, of the sLSTM blocks outside
their time scan (scope ``slstm`` less ``slstm_scan``): norm, gate
projection, group norm and the gated MLP, forward, recomputed and backward.
From the profiler trace (``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "slstm")
