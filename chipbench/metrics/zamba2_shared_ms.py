"""Device self time per traced step, in ms, of Zamba2's hybrid layers
outside their Mamba2 layer (scope ``zamba2_hybrid`` less ``mamba2``): the
concat with the embedding, the shared block's attention and adapted MLP,
and W_lin, forward, recomputed and backward. From the profiler trace
(``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "zamba2_hybrid")
