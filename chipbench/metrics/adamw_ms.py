"""Device self time per traced step, in ms, of the optimizer (scope
``adamw``): the gradient's global norm, the clip and the AdamW update of
every leaf. From the profiler trace (``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "adamw")
