"""End-to-end system behaviour: plan -> train -> checkpoint -> resume."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.core import hetero_cluster, plan_hybrid
from repro.optim.adamw import AdamWConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def _cfg():
    return get_config("qwen2_7b").reduced(n_layers=2, d_model=64, vocab=128,
                                          d_ff=128)


def test_public_api_imports():
    import repro.core as core
    import repro.kernels.ops as ops
    import repro.models as models
    import repro.parallel.sharding as sharding
    from repro.launch.mesh import make_production_mesh
    assert len(ARCH_IDS) == 10
    for a in ARCH_IDS:
        cfg = get_config(a)
        assert cfg.n_layers > 0 and cfg.vocab > 0
        assert cfg.shapes(), a


def test_compile_cache_dir_is_env_or_fixed_repo_path(tmp_path, monkeypatch):
    """Entry points keep the persistent compile cache where
    JAX_COMPILATION_CACHE_DIR says, else at the fixed <root>/.jax_cache."""
    from repro.launch.train import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert enable_compile_cache(tmp_path) == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(tmp_path.resolve() / ".jax_cache")
        assert enable_compile_cache(tmp_path) == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_plan_train_checkpoint_resume(tmp_path):
    """The full loop: auto-plan on an analytic cluster, train, checkpoint,
    build a NEW trainer, restore, and continue with matching loss."""
    topo = hetero_cluster({"RTX4090D": 2, "V100": 2}, gpus_per_node=2)
    plan = plan_hybrid(topo, _cfg().to_model_desc(), global_batch=4,
                       seq=32, with_baseline=False).plan
    tcfg = TrainerConfig(arch=_cfg(), steps=9, global_batch=4, seq_len=32,
                         ckpt_dir=str(tmp_path), ckpt_every=4, log_every=1,
                         opt=AdamWConfig(peak_lr=1e-3, warmup_steps=2,
                                         total_steps=20))
    tr = Trainer(tcfg, plan=plan)
    state, hist = tr.run()
    losses = {h["step"]: h["loss"] for h in hist}

    from repro.checkpoint.store import latest_step, restore
    from repro.parallel.trainstep import init_train_state
    step = latest_step(tmp_path)
    assert step == 8
    import dataclasses
    tcfg2 = dataclasses.replace(tcfg, steps=12)
    tr2 = Trainer(tcfg2, plan=plan)
    like = init_train_state(tr2.model, jax.random.PRNGKey(tcfg.seed))
    restored, manifest = restore(tmp_path / f"step_{step}", like,
                                 shardings=tr2.state_sh)
    state2, hist2 = tr2.run(state=restored, start_step=step + 1)
    # resumed losses continue the trajectory (same data stream)
    assert abs(hist2[0]["loss"] - losses[8]) < 0.6


def test_planner_to_trainer_knobs_flow():
    topo = hetero_cluster({"V100": 4}, gpus_per_node=4)
    res = plan_hybrid(topo, _cfg().to_model_desc(), global_batch=8, seq=32,
                      with_baseline=False)
    assert res.plan.world <= 4
    assert res.plan.microbatches >= 1
    tcfg = TrainerConfig(arch=_cfg(), steps=3, global_batch=8, seq_len=32,
                         ckpt_every=0, microbatches=2)
    tr = Trainer(tcfg, plan=res.plan)
    _, hist = tr.run()
    assert np.isfinite(hist[-1]["loss"])
