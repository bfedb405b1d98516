"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler compiles for a described ``v5e:2x2`` topology here, so
these tests catch what interpret mode cannot: block shapes the TPU lowering
refuses, and a train step that does not fit the chip's HBM.  Nothing runs;
they say nothing about results or times.

The topology is described inside module-scoped fixtures, never at import:
only one process may load the TPU library at a time, so describing it while
the module is collected would break parallel test workers.  Keep every such
compile in this one file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.kernels import ops
from repro.optim.adamw import AdamWConfig
from repro.parallel.trainstep import abstract_train_state
from repro.runtime.trainer import Trainer, TrainerConfig

V5E_HBM_BYTES = 15.75e9     # what one v5e chip lets a program use


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("heads", [(28, 4, 128), (4, 4, 192)],
                         ids=["qwen2_7b", "xlstm_hd192"])
def test_flash_attention_compiles(one_chip, heads):
    """Forward at seq 4096, bf16: Qwen2-7B GQA heads, and xLSTM's hd 192."""
    H, KV, hd = heads
    q = _spec((1, 4096, H, hd), one_chip)
    kv = _spec((1, 4096, KV, hd), one_chip)
    compiled = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v)).lower(
        q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,d", [(4096, 3584), (3000, 768)])
def test_rmsnorm_compiles(one_chip, rows, d):
    """Including a row count that the 256-row block does not divide."""
    compiled = jax.jit(lambda x, w: ops.rmsnorm(x, w)).lower(
        _spec((rows, d), one_chip), _spec((d,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xlstm_train_step_fits_one_chip(topo):
    """The Trainer's own step for xLSTM-125M at published widths, cut to one
    layer period (2 layers), batch 8 x seq 4096, full recomputation."""
    arch = dataclasses.replace(get_config("xlstm_125m"), n_layers=2)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    tcfg = TrainerConfig(arch=arch, steps=1, global_batch=8, seq_len=4096,
                         remat="full", opt=AdamWConfig())
    tr = Trainer(tcfg, mesh=mesh)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract_train_state(tr.model), tr.state_sh)
    batch_sh = NamedSharding(mesh, P())
    batch = {k: _spec((8, 4096), batch_sh, jnp.int32)
             for k in ("tokens", "labels")}
    mem = tr._jit.lower(state, batch).compile().memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, used
