"""Sharded checkpointing with elastic resharding + async save.

Arrays are gathered to host and written as one npz per *shard group* plus a
JSON manifest holding the step, the serialized ParallelPlan and the pytree
structure.  Restore is mesh-agnostic: arrays are re-placed under whatever
NamedSharding tree the *new* plan/mesh dictates — that is the elastic
resharding used after S3 failover (topology changed → planner re-plans →
restore reshards), cf. Oobleck's template switch.
"""

from __future__ import annotations

import json
import threading
import time
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

Pytree = Any
_SEP = "|"


def _flatten(tree: Pytree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        arr = np.asarray(jax.device_get(leaf))
        if arr.dtype.name == "bfloat16":     # npz has no native bf16
            arr = arr.astype(np.float32)
        flat[key] = arr
    return flat


def save(path: str | Path, state: Pytree, *, step: int,
         plan_json: str = "", extra: dict | None = None) -> None:
    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    flat = _flatten(state)
    tmp = path / ".tmp.arrays.npz"
    np.savez(tmp, **flat)
    tmp.rename(path / "arrays.npz")      # atomic-ish publish
    treedef = jax.tree_util.tree_structure(state)
    manifest = {"step": step, "plan": plan_json,
                "treedef": str(treedef), "keys": sorted(flat),
                "time": time.time(), **(extra or {})}
    (path / "manifest.json").write_text(json.dumps(manifest, indent=1))


class AsyncSaver:
    """Fire-and-forget background checkpoint writes (one in flight)."""

    def __init__(self):
        self._thread: threading.Thread | None = None

    def submit(self, path, state, *, step: int, plan_json: str = "") -> None:
        self.wait()
        host_state = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                  state)
        self._thread = threading.Thread(
            target=save, args=(path, host_state),
            kwargs={"step": step, "plan_json": plan_json}, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None


def restore(path: str | Path, like: Pytree, *,
            shardings: Pytree | None = None) -> tuple[Pytree, dict]:
    """Restore into the structure and dtypes of ``like`` (arrays or
    ``jax.ShapeDtypeStruct``s, e.g. ``abstract_train_state``); place under
    ``shardings`` (the *new* mesh's sharding tree — elastic resharding)."""
    path = Path(path)
    manifest = json.loads((path / "manifest.json").read_text())
    data = np.load(path / "arrays.npz")
    leaves_like, treedef = jax.tree_util.tree_flatten_with_path(like)
    out = []
    for p, leaf in leaves_like:
        key = _SEP.join(str(getattr(q, "key", getattr(q, "idx", q)))
                        for q in p)
        arr = data[key]
        if hasattr(leaf, "dtype") and arr.dtype != leaf.dtype:
            arr = arr.astype(leaf.dtype)
        out.append(arr)
    tree = jax.tree_util.tree_unflatten(treedef, out)
    if shardings is not None:
        tree = jax.tree.map(
            lambda x, s: jax.device_put(x, s), tree, shardings)
    return tree, manifest


def latest_step(root: str | Path) -> int | None:
    root = Path(root)
    steps = [int(p.name.split("_")[-1]) for p in root.glob("step_*")
             if (p / "manifest.json").exists()]
    return max(steps) if steps else None
