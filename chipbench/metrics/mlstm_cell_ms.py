"""Device self time per traced step, in ms, of the mLSTM chunkwise cell
(scope ``mlstm_cell``): the stabilised intra-chunk products and the scan
over chunks that carries the matrix memory, forward, recomputed and
backward. From the profiler trace (``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "mlstm_cell")
