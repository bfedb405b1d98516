"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files it stands for."""

import json
import re
import shutil
import subprocess
import sys

import pytest

from chipbench.spec import ROOT, benchmark, resolve

BENCH = benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_keys():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(CELLS)) == len(CELLS)
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files(name):
    cell = resolve(name)
    assert cell.chips == 1
    assert {"loss_gap", "grad_gap", "step_gap_med"} <= set(cell.limits)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    conf = next(c for c in BENCH["configs"] if c["name"] == cell.config[
        "name"])
    assert conf["reduced"] == cell.config["reduced"]


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_states_every_field(name):
    """Each configuration file names every field of the program's
    ArchConfig, so a later default cannot move the yardstick."""
    import dataclasses

    from repro.models.config import ArchConfig
    conf = next(c for c in BENCH["configs"] if c["name"] == name)
    arch = json.loads((ROOT / conf["file"]).read_text())["arch"]
    assert set(arch) == {f.name for f in dataclasses.fields(ArchConfig)}


NEW_FILES = {
    "chipbench/configs/tiny-new.json": None,          # filled in the test
    "chipbench/traffic/train1k.json": {
        "why": "short", "seq_len": 1024, "ckpt_every": 0, "chunk_steps": 2,
        "trace_steps": 1, "events": None},
    "chipbench/limits/tiny-new.train1k.json": {
        "why": "test", "limits": {"loss_gap": 0.1, "grad_gap": 0.1,
                                  "step_gap_med": 0.1}},
    "chipbench/metrics/steps_run.py":
        "def read(run):\n    return float(sum(c['steps'] for c in "
        "run.chunks))\n",
    "chipbench/flops/tinymix.py":
        "def forward(cfg, seq):\n    return 7.0\n",
}


def test_new_files_are_found_without_edits(tmp_path):
    """A configuration, a traffic mix, limits, a per-layer metric and a
    block kind's FLOPs, each added as a file with an entry in
    BENCHMARK.json, are found by name; no existing file changes."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    conf = json.loads((ROOT / "chipbench/configs/xlstm-125m.json")
                      .read_text())
    conf["name"] = "tiny-new"
    NEW_FILES["chipbench/configs/tiny-new.json"] = conf
    for rel, body in NEW_FILES.items():
        (root / rel).write_text(body if isinstance(body, str)
                                else json.dumps(body))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-new", "source": "test",
                             "file": "chipbench/configs/tiny-new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-new.train1k",
                               "config": "tiny-new", "traffic": "train1k",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("tiny-new.train1k")
    bench["per_layer"].append({"name": "steps_run", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "model step",
                               "moves": "tokens_per_s",
                               "workloads": ["tiny-new.train1k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import sys, json; sys.path[:0] = [sys.argv[1], sys.argv[2]]\n"
        "from pathlib import Path\n"
        "from chipbench.spec import resolve\n"
        "from chipbench import metrics, flops\n"
        "c = resolve('tiny-new.train1k', Path(sys.argv[1]))\n"
        "class R: chunks = [{'steps': 3}, {'steps': 4}]\n"
        "print(json.dumps([c.traffic['seq_len'], c.limits['loss_gap'],\n"
        "  sorted(m['name'] for m in c.per_layer),\n"
        "  metrics.read('steps_run', R),\n"
        "  flops.forward_per_token(dict(block_pattern=['tinymix'],\n"
        "    n_layers=2, d_model=4, vocab=3), 8)]))\n")
    out = subprocess.run([sys.executable, "-c", code, str(root),
                          str(ROOT / "src")], capture_output=True, text=True,
                         check=True)
    seq, lim, layer, steps, fl = json.loads(out.stdout.strip().splitlines()[-1])
    assert (seq, lim, steps) == (1024, 0.1, 7.0)
    assert "steps_run" in layer and "mfu" in layer
    assert fl == 2 * 7.0 + 2 * 4 * 3
    after = {p: p.read_bytes() for p in before}
    assert after == before
