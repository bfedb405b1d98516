"""Mean seconds the trainer spends building the step program again after
an event in the window: its ``compile_s`` entry for the first step after
each event (lowering, and the compile or the persistent cache's load)."""


def read(run):
    vals = run.window_recompile_s
    return sum(vals) / len(vals) if vals else None
