"""Device self time per traced step, in ms, of Zamba2's Mamba2 layers
outside their SSD (scope ``mamba2`` less ``mamba_ssd``), the hybrid
layers' included: norm, in-projections, the convolution over x, B and C,
the gated group norm and the out-projection, forward, recomputed and
backward. From the profiler trace (``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "mamba2")
