"""The token batches a training cell feeds, made again from the seed.

A copy of the program's synthetic stream (``repro.data.pipeline.
SyntheticLM.batch``, tokens and labels only), so that the reference reads
the batches the program was fed without taking them from the program:
row b of step s is ``(start_b + a_b * t) mod vocab`` with 2% of positions
replaced by uniform noise, all drawn from ``(seed, step)``.
"""

from __future__ import annotations

import numpy as np


def batch(seed: int, step: int, *, rows: int, seq: int,
          vocab: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng((seed, step))
    a = rng.integers(1, 5, size=(rows, 1))
    start = rng.integers(0, vocab, size=(rows, 1))
    toks = (start + a * np.arange(seq + 1)[None, :]) % vocab
    noise = rng.integers(0, vocab, size=(rows, seq + 1))
    keep = rng.random((rows, seq + 1)) < 0.98
    toks = np.where(keep, toks, noise).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]
