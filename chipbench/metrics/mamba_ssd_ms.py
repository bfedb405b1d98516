"""Device self time per traced step, in ms, of the Mamba2 SSD (scope
``mamba_ssd``): the chunkwise products and the scan over chunks that
carries the state, forward, recomputed and backward. From the profiler
trace (``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "mamba_ssd")
