"""The Mamba2 SSD (the program's scope ``mamba_ssd``) of every Mamba2 layer
in one training step, the hybrid layers' included, counted from shapes at
the chip's share (``reference/mamba2.py``): the least work and traffic that
any implementation of it has.

FLOPs: the recurrence in its recurrent form, as ``mamba2.py`` counts it
(per head and token the update s += dt x B^T and the read-out s C, hd x N
each), times three for the forward pass and the backward pass's two
products, with no recomputation.

Bytes, at the configuration's dtype: the forward pass reads x, dt, B and C
and writes y; the backward pass reads x, dt, B, C and dy and writes dx,
ddt, dB and dC.  The state starts at zero and its end is not kept in
training, so neither is counted; A_log and D are a few numbers a layer.
"""

from __future__ import annotations

import jax.numpy as jnp

from chipbench.reference.mamba2 import share

LAYERS = ("mamba2", "zamba2_hybrid")     # each holds one Mamba2 layer


def train_step(cfg: dict, rows: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the SSDs of a step of ``rows`` sequences of
    ``seq`` tokens."""
    pattern = list(cfg["block_pattern"])
    layers = cfg["n_layers"] // len(pattern) * sum(
        pattern.count(k) for k in LAYERS)
    s = share(cfg)
    hd, N = cfg["ssm_head_dim"], cfg["ssm_state"]
    tokens = rows * seq
    flops = 3 * 2.0 * s["heads"] * (2 * hd * N) * tokens
    size = jnp.dtype(cfg["dtype"]).itemsize
    inputs = s["e"] + s["heads"] + 2 * s["groups"] * N     # x, dt, B, C
    forward = inputs + s["e"]
    backward = inputs + s["e"] + inputs
    return layers * flops, layers * tokens * size * (forward + backward)
