"""Weights and optimizer state of a configuration, made from the seed.

The shapes come from the reference's file of each block kind
(``reference/<kind>.py``: ``param_shapes``), laid out as the program
stacks them: ``pos<j>`` holds the ``n_cycles`` layers of pattern slot
``j``.  A weight whose initial scale is not stated is drawn with standard
deviation 1/sqrt(fan-in), its fan-in being the product of all its axes but
the last.  Everything is made on the device in one jitted call, in the
configuration's dtype, from a key that is an argument (so one compiled
program serves every seed).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.model import kind_module
from chipbench.reference import embed_xent


def key_data(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size."""
    return np.random.SeedSequence(seed).generate_state(2).astype(np.uint32)


def _init(shape, init):
    """A stated init ("ones", "zeros", a standard deviation), or None for
    1/sqrt(fan-in)."""
    return init if init is not None else 1.0 / math.sqrt(math.prod(shape[:-1]))


def _specs(shapes: dict, n: int | None = None) -> dict:
    """name -> (shape, init), each shape stacked ``n`` deep if given."""
    return {k: _specs(v, n) if isinstance(v, dict) else
            ((*(() if n is None else (n,)), *v[0]), _init(*v))
            for k, v in shapes.items()}


def shape_tree(cfg: dict) -> dict:
    """The parameter tree as the program lays it out: name -> (shape,
    init)."""
    tree = _specs(embed_xent.param_shapes(cfg))
    pattern = cfg["block_pattern"]
    n = cfg["n_layers"] // len(pattern)
    for j, kind in enumerate(pattern):
        mod = kind_module(kind)
        stacked = _specs(mod.param_shapes(cfg), n)
        tree[f"pos{j}"] = {mod.KEY: stacked} if mod.KEY else stacked
        if hasattr(mod, "shared_shapes"):
            tree["shared"] = _specs(mod.shared_shapes(cfg))
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_params(cfg: dict, key: jax.Array):
    dtype = jnp.dtype(cfg["dtype"])
    specs, treedef = jax.tree.flatten(shape_tree(cfg), is_leaf=_is_spec)
    out = []
    for i, (shape, init) in enumerate(specs):
        if init == "ones":
            out.append(jnp.ones(shape, dtype))
        elif init == "zeros":
            out.append(jnp.zeros(shape, dtype))
        else:
            out.append((jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * init).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def param_maker(cfg: dict, out_shardings=None):
    """jitted ``key_data -> params``."""
    def make(kd):
        return make_params(cfg, jax.random.wrap_key_data(kd))
    return jax.jit(make, out_shardings=out_shardings)
