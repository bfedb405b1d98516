"""The comparison that decides ``correct`` for a training cell.

Set-up drives the program's compiled step through its first three steps,
through the same ``Trainer.run`` and feed as the window.  The reference
(``chipbench/reference``, float32) follows the same three steps from the
same weights on the same batches.  Three numbers are compared, each with
its limit from ``chipbench/limits/<cell>.json``:

  loss_gap  the largest |loss - reference loss| / reference loss over the
            three steps;
  grad_gap  the first step's clipped gradient, as the optimizer got it
            (its first moment after one step over 1 - b1), by the worst
            leaf: |norm - reference norm| / max(reference norm of the leaf,
            of the median leaf);
  step_gap_med  the parameters' change over the three steps, by the
            median leaf: each leaf's |norm - reference norm| / max(reference
            norm of the leaf, of the median leaf), and the median of these,
            over the leaves whose reference gradient is at least a
            thousandth of the median leaf's (the others move by round-off
            alone).  The worst leaf's change is noise of single small leaves
            under Adam and does not tell the program from the control; the
            median leaf's does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("loss_gap", "grad_gap", "step_gap_med")


@jax.jit
def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def change_norms(after, before) -> jax.Array:
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        after, before))


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(k)
            for k, _ in jax.tree_util.tree_leaves_with_path(tree)]


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, counted) -> np.ndarray:
    """Each counted leaf's gap, measured against the larger of its
    reference norm and the median counted leaf's."""
    floor = float(np.median(ref[counted]))
    return (np.abs(prog - ref) / np.maximum(ref, floor))[counted]


def worst_leaf(prog: np.ndarray, ref: np.ndarray, names):
    """(gap, leaf) of the worst leaf."""
    gaps = leaf_gaps(prog, ref, np.ones(len(ref), bool))
    if not np.all(np.isfinite(gaps)):
        return math.inf, "non-finite"
    i = int(np.argmax(gaps))
    return float(gaps[i]), names[i]


def median_leaf(prog: np.ndarray, ref: np.ndarray, counted):
    """(gap, how many leaves counted) of the median counted leaf."""
    gaps = leaf_gaps(prog, ref, counted)
    if not np.all(np.isfinite(gaps)):
        return math.inf, "non-finite"
    return float(np.median(gaps)), f"median of {len(gaps)} leaves"


def readings(prog: dict, ref: dict, names) -> dict:
    """prog, ref: {"losses": [3], "grad": per-leaf norms, "change":
    per-leaf norms}.  Returns {name: (value, the worst step or leaf, or
    the leaves counted)}."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss = np.abs(lp - lr) / np.abs(lr)
    loss_gap = float(np.max(loss)) if np.all(np.isfinite(loss)) else math.inf
    median = float(np.median(ref["grad"]))
    counted = ref["grad"] >= 1e-3 * median
    return {"loss_gap": (loss_gap, f"step {int(np.argmax(loss))}"
                         if math.isfinite(loss_gap) else "non-finite"),
            "grad_gap": worst_leaf(prog["grad"], ref["grad"], names),
            "step_gap_med": median_leaf(prog["change"], ref["change"],
                                        counted)}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """correct, and {name: {"value", "limit"}} in the order of NAMES."""
    out = {k: {"value": values[k][0], "limit": limits[k]} for k in NAMES}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values())
    return ok, out
