"""Device self time per traced step, in ms, of the operations under none of
the program's named scopes: the layer scan's loop, its copies and slices of
the stacked weights and carries, and whatever the HLO does not name. From
the profiler trace (``chipbench/scopes.py``)."""

from chipbench.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "unscoped")
