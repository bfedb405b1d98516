"""The whole reference: the configuration's layer pattern built from one
file per block kind, its mean cross-entropy, its gradients and the
optimizer's first steps, in float32 (or in the control's precision).

A block kind ``k`` is the module ``reference/k.py``.  It gives:

    KEY                 the name the layer's weights sit under in its
                        ``pos<j>`` slot, or None for the slot itself;
    param_shapes(cfg)   {name: (shape, init)} of one layer (``weights.py``
                        stacks them over the pattern's cycles);
    shared_shapes(cfg)  optional: weights shared by all its layers, passed
                        to every block as ``shared``;
    INPUTS              optional: more inputs the block takes, each passed
                        as a keyword: ``"x0"``, the embedding's output as
                        ``embed`` gives it (float32, (rows, positions,
                        d_model)); ``"cycle"``, the index of the pattern's
                        cycle the layer is in (a traced int32 scalar);
    block(p, x, cfg, mm, shared, **inputs)  the layer's output.

A block kind that declares no ``INPUTS`` is called as
``block(p, x, cfg=, mm=, shared=)`` alone, and the scan carries the cycle
index only where some kind of the pattern declares it.  The loss and
gradients of a batch are taken a block of rows at a time and summed, which
is exact, so that the reference fits in the chip's memory at the timed
sizes.
"""

from __future__ import annotations

import importlib
from functools import partial

import jax
import jax.numpy as jnp

from . import adamw
from .common import F32, MATMULS, rms_norm
from .embed_xent import embed, xent_sum


def kind_module(kind: str):
    """``reference/<kind>.py``, as the module docstring describes it."""
    return importlib.import_module(f"{__package__}.{kind}")


def loss_sum(params, tokens, labels, *, cfg, mm):
    """Summed token cross-entropy of the rows given.  The layers run as a
    scan over the pattern's cycles, each block recomputed in the backward
    pass (``jax.checkpoint``)."""
    pattern = cfg["block_pattern"]
    shared = params.get("shared")
    mods = [kind_module(kind) for kind in pattern]
    stacks = [params[f"pos{j}"][m.KEY] if m.KEY else params[f"pos{j}"]
              for j, m in enumerate(mods)]
    wants = [getattr(m, "INPUTS", ()) for m in mods]
    x0 = embed(params["embed"], tokens)
    counted = any("cycle" in w for w in wants)

    def cycle(carry, layer):
        x, c = carry if counted else (carry, None)
        given = {"x0": x0, "cycle": c}
        for m, p, w in zip(mods, layer, wants):
            x = jax.checkpoint(partial(m.block, cfg=cfg, mm=mm))(
                p, x, shared=shared, **{k: given[k] for k in w})
        return ((x, c + 1) if counted else x), None

    init = (x0, jnp.zeros((), jnp.int32)) if counted else x0
    out, _ = jax.lax.scan(cycle, init, stacks)
    x = out[0] if counted else out
    x = rms_norm(x, params["final_norm"], cfg["norm_eps"])
    return xent_sum(params["embed"], x, labels, mm)


class Reference:
    """Trains a copy of the parameters on given batches and reports what
    the comparison reads: each step's loss, the clipped gradient of the
    first step and the parameters after the last."""

    def __init__(self, cfg: dict, opt: dict, *, rows: int,
                 precision: str = "float32"):
        self.cfg, self.opt, self.rows = cfg, opt, rows
        mm = MATMULS[precision]

        def grad_rows(p32, tokens, labels, n_tokens):
            loss, g = jax.value_and_grad(
                lambda p: loss_sum(p, tokens, labels, cfg=cfg, mm=mm))(p32)
            return loss / n_tokens, jax.tree.map(lambda a: a / n_tokens, g)

        self._grad_rows = jax.jit(grad_rows)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._to32 = jax.jit(lambda p: jax.tree.map(
            lambda a: a.astype(F32), p))
        self._clip = jax.jit(partial(adamw.clipped, opt))
        self._update = jax.jit(partial(adamw.update, opt),
                               donate_argnums=(3, 4))
        self._norms = jax.jit(lambda t: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(t)]))

    def loss_and_grads(self, params, tokens, labels):
        B, S = tokens.shape
        p32 = self._to32(params)
        loss, grads = 0.0, None
        for lo in range(0, B, self.rows):
            l, g = self._grad_rows(p32, tokens[lo:lo + self.rows],
                                   labels[lo:lo + self.rows], float(B * S))
            loss += float(l)
            grads = g if grads is None else self._add(grads, g)
        return loss, grads

    def train(self, params, batches):
        """Runs one optimizer step per batch.  Returns (losses, per-leaf
        norms of the clipped gradient of step 1, final parameters)."""
        m = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        v = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        losses, first = [], None
        for t, (tokens, labels) in enumerate(batches, start=1):
            loss, grads = self.loss_and_grads(params, tokens, labels)
            grads = self._clip(grads)
            if first is None:
                first = self._norms(grads)
            params, m, v = self._update(adamw.coefficients(self.opt, t),
                                        params, grads, m, v)
            losses.append(loss)
        return losses, first, params
