"""run.py refuses to run without a TPU, and without the program."""

import os
import shutil
import subprocess
import sys

from chipbench.spec import ROOT, benchmark

CELL = benchmark()["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1",
        "--trace", "0"]


def run(cwd, **env):
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, capture_output=True, text=True,
                          env={**os.environ, **env}, timeout=300)


def test_exits_nonzero_on_cpu():
    out = run(ROOT, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "needs 1 TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files has no program to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in benchmark()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = run(tmp_path, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
