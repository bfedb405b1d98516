"""Device self time per traced step, in ms, of the mLSTM blocks outside
their chunkwise cell (scope ``mlstm`` less ``mlstm_cell``): norm,
up-projection, q, k, v, gates, output gate and down-projection, forward,
recomputed and backward. From the profiler trace (``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "mlstm")
