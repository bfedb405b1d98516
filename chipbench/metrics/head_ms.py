"""Device self time per traced step, in ms, of the head (scope ``head``):
the embedding, the final norm and the tied output projection with the
chunked cross-entropy, forward and backward. From the profiler trace
(``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "head")
