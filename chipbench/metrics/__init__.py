"""Per-layer metrics, one reader per file: ``metrics/<name>.py`` has
``read(run) -> float | None``, where ``run`` is the finished run
(:class:`chipbench.cell.RunRecord`).  A reader that finds nothing to read
returns None and the metric is left out of the result."""

from __future__ import annotations

import importlib


def read(name: str, run):
    return importlib.import_module(f"{__name__}.{name}").read(run)
