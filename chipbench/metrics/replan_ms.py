"""Mean milliseconds of the planner's re-plan per event in the window: the
``replan.<path>`` spans that ``repro.obs`` records from ``ReplanEngine``
when ``REPRO_TRACE`` is set (it is, in traced runs)."""


def read(run):
    vals = run.window_replan_s
    return 1000.0 * sum(vals) / len(vals) if vals else None
