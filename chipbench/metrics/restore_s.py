"""Mean seconds to restore the checkpoint after an event in the window:
the trainer's ``event_log[*]["restore_s"]`` (read, re-place on the device,
block until ready)."""


def read(run):
    vals = [e["restore_s"] for e in run.window_events]
    return sum(vals) / len(vals) if vals else None
