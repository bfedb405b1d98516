"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` names a configuration (its ``file``), a
traffic mix (``chipbench/traffic/<traffic>.json``), its limits
(``chipbench/limits/<cell>.json``), and the per-layer metrics that list it
(``chipbench/metrics/<metric>.py``).  Adding any of these is adding a
file and an entry; no code changes.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list       # the BENCHMARK.json entries this cell reports
    per_layer: list


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    here = root / "chipbench"
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (here / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((here / "limits" / f"{name}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, name) and m["moves"] in reported]
    for m in layer:
        if not (here / "metrics" / f"{m['name']}.py").exists():
            raise FileNotFoundError(f"no reader chipbench/metrics/"
                                    f"{m['name']}.py")
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                limits=limits["limits"], end_to_end=e2e, per_layer=layer)
