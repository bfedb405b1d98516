"""The mLSTM's chunkwise cell (the program's scope ``mlstm_cell``) in one
training step, counted from shapes: the least work and traffic that any
implementation of it has.

FLOPs: the matrix memory in its recurrent form, as ``mlstm.py`` counts it
(per head and token the update C += k v^T and the read-out C q, hd x hd
each, and the normaliser's n += k and n . q), times three for the forward
pass and the backward pass's two products, with no recomputation.

Bytes, at the configuration's dtype: the forward pass reads q, k, v and
the input and forget gates and writes y; the backward pass reads q, k, v,
the gates and dy and writes dq, dk, dv and the gates' gradients.  The
state starts at zero and its end is not kept in training, so neither is
counted.
"""

from __future__ import annotations

import jax.numpy as jnp


def train_step(cfg: dict, rows: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the cells of every mLSTM layer in a step of
    ``rows`` sequences of ``seq`` tokens."""
    pattern = cfg["block_pattern"]
    layers = cfg["n_layers"] // len(pattern) * list(pattern).count("mlstm")
    H = cfg["n_heads"]
    e = 2 * cfg["d_model"]
    hd = e // H
    tokens = rows * seq
    flops = 3 * 2.0 * H * (2 * hd * hd + 2 * hd) * tokens
    size = jnp.dtype(cfg["dtype"]).itemsize
    vec, gate = tokens * e * size, tokens * H * size
    forward = 4 * vec + 2 * gate
    backward = 7 * vec + 4 * gate
    return layers * flops, layers * (forward + backward)
