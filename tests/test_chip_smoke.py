"""chip_smoke.py on the CPU: it refuses to run, and its phases rehearse.

The script's phases run here at a tiny size (kernels in interpret mode, a
reduced xLSTM) so a change that breaks them shows up before a chip run.
The four-device mesh phase rehearses in tests/test_parallel_multidev.py.
"""

import importlib.util
import math
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_exits_nonzero_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err


def test_smoke_kernel_phase_rehearses_in_interpret_mode(smoke, capsys):
    smoke.kernel_phase(seq=128, heads=(4, 2, 128),
                       norm_shapes=((300, 128),), interpret=True)
    out = capsys.readouterr().out
    assert "flash_attention" in out and "rmsnorm rows=300" in out


def test_smoke_train_phase_rehearses_reduced(smoke, capsys):
    smoke.train_phase("TPUv5e", reduced=True, seq=32, batch=4, steps=4,
                      event_step=2)
    out = capsys.readouterr().out
    assert "restored_bitwise=True" in out and "kind=bandwidth" in out
    assert not math.isnan(float(out.split("median_step_s=")[1].split()[0]))
