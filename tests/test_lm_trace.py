"""The xLSTM cell's loss traces to the same program as before the Zamba2
block kinds came into ``repro.models``: the hybrid kinds' cycle index and
embedding input are carried only for a pattern that has them."""

import dataclasses
import hashlib
import json
import re
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.models.config import ArchConfig
from repro.models.lm import LM

ROOT = Path(__file__).resolve().parents[1]

# sha256 of the jaxpr of LM.loss's value and gradient (remat "full"), with
# object addresses taken out, as the program before the Zamba2 kinds traced
# it: xLSTM-125M at d 64 and vocab 96 (two periods, 2 x 128 tokens), and at
# the cell's size (4 x 4096)
XLSTM_JAXPR = {
    "small": "f12c50c82b98bff0eb8ef8d1f9b4a81b66c47120a8a359f780db895622461ed3",
    "cell": "182043bc53b21f124ba86a24055e68348300e9938af4128a360f3b971d44afd1",
}


@pytest.mark.parametrize("size", ["small", "cell"])
def test_xlstm_loss_jaxpr_unchanged(size):
    a = json.loads((ROOT / "chipbench/configs/xlstm-125m.json")
                   .read_text())["arch"]
    arch = ArchConfig(**dict(a, block_pattern=tuple(a["block_pattern"])))
    rows, seq = 4, 4096
    if size == "small":
        arch = dataclasses.replace(arch, n_layers=16, d_model=64, vocab=96)
        rows, seq = 2, 128
    m = LM(arch)
    toks = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(partial(m.loss, remat="full")))(
        m.abstract_params(), toks, toks)
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest() == XLSTM_JAXPR[size]
