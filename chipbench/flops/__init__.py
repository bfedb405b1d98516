"""Model FLOPs of a training step, counted from shapes.

One file per block kind, ``flops/<kind>.py``, gives ``forward(cfg, seq)``:
the multiply-adds (as 2 FLOPs each) of one layer's forward pass per token.
``embed_xent.py`` gives the tied output projection.  Training counts each
forward FLOP three times (forward, and the two products of the backward
pass) and no recomputation.
"""

from __future__ import annotations

import importlib


def forward_per_token(cfg: dict, seq: int) -> float:
    pattern = cfg["block_pattern"]
    n_cycles = cfg["n_layers"] // len(pattern)
    total = importlib.import_module(f"{__name__}.embed_xent").forward(cfg, seq)
    for kind in pattern:
        total += n_cycles * importlib.import_module(
            f"{__name__}.{kind}").forward(cfg, seq)
    return total


def train_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * forward_per_token(cfg, seq)
