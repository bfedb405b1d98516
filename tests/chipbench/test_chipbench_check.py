"""The comparison that decides ``correct``, driven through a whole run on
the CPU at a tiny size: a sound program passes, and each fault that a
one-chip training cell can have, planted in the program's step, fails.
The control (the reference computed with float8 operands) fails too."""

import json

import jax
import numpy as np
import pytest

from chipbench import calibrate, cell as C, check
from chipbench.spec import ROOT, resolve
from chipbench.weights import key_data, param_maker

SEED = 2**33 + 17
CELL = "xlstm-125m.train4k"

# Limits for this size, set the way the cells' are: on the CPU, seeds SEED
# and 5 to 9, the program (bfloat16) read loss_gap <= 6.1e-5, grad_gap <=
# 4.9e-3 and step_gap_med <= 4.0e-3, its event cell the same; the float8
# control (seeds SEED, 5, 6) read loss_gap >= 3.7e-4 and step_gap_med >=
# 8.9e-3; half of each batch step_gap_med >= 7.2e-2; shifted labels
# loss_gap >= 7.5e-3; a restore with the moments zeroed or the step
# counter reset step_gap_med >= 0.27.
TINY_LIMITS = {"loss_gap": 1.5e-4, "grad_gap": 0.05, "step_gap_med": 0.006}


def tiny(name):
    """The cell ``<config>.<traffic>`` at a tiny size.  The configuration and
    harness come from the listed cell; the traffic mix from its file, so
    that a mix no cell lists yet (the event mix) is driven too."""
    c = resolve(CELL)
    c.traffic = json.loads((ROOT / "chipbench" / "traffic" /
                            f"{name.split('.', 1)[1]}.json").read_text())
    c.config["arch"].update(n_layers=2, block_pattern=["mlstm", "slstm"],
                            d_model=64, n_heads=4, n_kv_heads=4, vocab=256)
    c.config.update(global_batch=4, reference_rows=2)
    c.traffic["seq_len"] = 128
    c.limits = dict(TINY_LIMITS)
    return c


@pytest.fixture(autouse=True)
def cpu_run(tmp_path, monkeypatch):
    """A persistent compile cache of the test's own, as a run has one (the
    step after an event is then loaded, not compiled), put back as it was
    afterwards since other tests share the process; a run directory of the
    test's own; a peak for the CPU so that the run's arithmetic goes
    through."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    monkeypatch.setattr(C, "RUN_DIR", tmp_path / "run")
    monkeypatch.setattr(C, "peaks", lambda kind: {"bf16_flops_per_s": 1e12,
                                                  "hbm_bytes_per_s": 1e11})
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])
    cc.reset_cache()


def run(cell):
    return C.run(cell, seed=SEED, seconds=0.2, trace=False, t_start=0.0,
                 log=lambda s: None)


def broken(monkeypatch, wrap):
    import repro.runtime.trainer as T
    real = T.make_train_step

    def make(*a, **kw):
        return wrap(real(*a, **kw))

    monkeypatch.setattr(T, "make_train_step", make)


def unchanged(step):
    def f(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return f


def half_batch(step):
    def f(state, batch):
        return step(state, {k: v[: v.shape[0] // 2] for k, v in
                            batch.items()})
    return f


def shifted_labels(step):
    def f(state, batch):
        return step(state, dict(batch, labels=jax.numpy.roll(
            batch["labels"], 1, axis=1)))
    return f


@pytest.mark.parametrize("name", ["xlstm-125m.train4k",
                                  "xlstm-125m.train4k.events"])
def test_sound_run_is_correct(name):
    result = run(tiny(name))
    assert result["correct"], result["check"]
    assert list(result)[-1] == "check"


@pytest.mark.parametrize("fault", [unchanged, half_batch, shifted_labels])
def test_fault_in_the_step_is_not_correct(monkeypatch, fault):
    broken(monkeypatch, fault)
    result = run(tiny("xlstm-125m.train4k"))
    assert not result["correct"], result["check"]


@pytest.mark.parametrize("fault", sorted(calibrate.RESTORE_FAULTS))
def test_fault_in_the_restore_is_not_correct(fault):
    """The event cell's third compared step runs on the state restored
    after its set-up event: a restore that loses the Adam moments, or
    puts the step counter back, is caught."""
    take_out = calibrate.broken_restore(calibrate.RESTORE_FAULTS[fault])
    try:
        result = run(tiny("xlstm-125m.train4k.events"))
    finally:
        take_out()
    assert not result["correct"], result["check"]


def test_control_is_not_correct():
    cell = tiny("xlstm-125m.train4k")
    names = check.leaf_names(jax.eval_shape(param_maker(cell.config["arch"]),
                                            key_data(0)))
    ref = C.reference(cell, SEED)
    control = C.reference(cell, SEED, C.make_reference(cell, "fp8"))
    correct, compared = check.judge(check.readings(control, ref, names),
                                    cell.limits)
    assert not correct, compared


def test_planted_reference_faults_read_above_the_program():
    cell = tiny("xlstm-125m.train4k")
    names = check.leaf_names(jax.eval_shape(param_maker(cell.config["arch"]),
                                            key_data(0)))
    exact = C.make_reference(cell)
    ref = C.reference(cell, SEED, exact)
    for feed in (calibrate.half_batch, calibrate.shifted_labels):
        values = check.readings(C.reference(cell, SEED, exact, feed), ref,
                                names)
        assert not check.judge(values, cell.limits)[0]
