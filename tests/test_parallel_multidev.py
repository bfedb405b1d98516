"""Multi-device distribution tests (8 emulated host devices, subprocess).

The main pytest process must keep seeing ONE device (smoke tests), so every
case here launches a fresh interpreter with
XLA_FLAGS=--xla_force_host_platform_device_count=8 and asserts inside it.
Covers: sharded-vs-single-device train-step equivalence, the shard_map
pipeline, explicit collective schedules, and a small-mesh dry-run lowering.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

# every case spawns a fresh interpreter and recompiles under an 8-device
# host mesh — minutes of wall time, excluded from the tier-1 CI gate
pytestmark = pytest.mark.slow

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_devices(body: str, n: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        assert len(jax.devices()) == {n}
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    run_devices("""
        from repro.configs import get_config
        from repro.models.lm import LM
        from repro.optim.adamw import AdamWConfig
        from repro.parallel import sharding as shd
        from repro.parallel.axes import use_rules
        from repro.parallel.trainstep import init_train_state, make_train_step
        cfg = get_config("qwen2_7b").reduced(n_layers=2, d_model=64,
                                             vocab=128, d_ff=128,
                                             n_heads=4, n_kv_heads=2,
                                             head_dim=16)
        model = LM(cfg)
        key = jax.random.PRNGKey(0)
        step = make_train_step(model, AdamWConfig(peak_lr=1e-3,
                                                  warmup_steps=1,
                                                  total_steps=10))
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}
        # single device
        s0 = init_train_state(model, key)
        s0, m0 = jax.jit(step)(s0, batch)
        # sharded 4x2 (data x model)
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2),
                    ("data", "model"))
        prof = shd.profile_for(cfg, mesh, zero3=True)
        st_sh = {"params": shd.param_shardings(model, mesh, prof.rules),
                 "opt": shd.opt_state_shardings(model, mesh,
                                                prof.opt_rules)}
        def wrapped(state, b):
            with use_rules(mesh, prof.rules):
                return step(state, b)
        s1 = jax.device_put(init_train_state(model, key), st_sh)
        with mesh:
            s1, m1 = jax.jit(wrapped, in_shardings=(st_sh, None),
                             out_shardings=(st_sh, None))(s1, batch)
        assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-4, \\
            (float(m0["loss"]), float(m1["loss"]))
        for a, b in zip(jax.tree.leaves(s0["params"]),
                        jax.tree.leaves(s1["params"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=2e-3)
        print("sharded == single OK")
    """)


def test_pipeline_uneven_stages_fwd_bwd():
    run_devices("""
        from repro.parallel.pipeline import pad_stages, pipeline_forward
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("pipe",))
        L, d, M, mb = 7, 16, 6, 3
        key = jax.random.PRNGKey(0)
        Ws = jax.random.normal(key, (L, d, d)) * 0.3
        sizes = [2, 2, 2, 1]                      # planner's uneven split
        sp, mask = pad_stages({"w": Ws}, sizes)
        x = jax.random.normal(key, (M, mb, d))
        fn = lambda p, h: jnp.tanh(h @ p["w"])
        out = pipeline_forward(fn, sp, mask, x, mesh=mesh)
        ref = x
        for i in range(L): ref = jnp.tanh(ref @ Ws[i])
        np.testing.assert_allclose(out, ref, atol=1e-5)
        def loss(W):
            s, m = pad_stages({"w": W}, sizes)
            return jnp.sum(pipeline_forward(fn, s, m, x, mesh=mesh) ** 2)
        g = jax.grad(loss)(Ws)
        def loss_ref(W):
            r = x
            for i in range(L): r = jnp.tanh(r @ W[i])
            return jnp.sum(r ** 2)
        gr = jax.grad(loss_ref)(Ws)
        np.testing.assert_allclose(g, gr, atol=1e-4)
        print("pipeline OK")
    """, n=4)


def test_collective_schedules_equivalent():
    run_devices("""
        from repro.parallel.collectives import sync_grads
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        g = {"a": jnp.arange(24.0).reshape(4, 6), "b": jnp.ones((7,))}
        ar, _ = sync_grads(g, mesh, "data", schedule="allreduce")
        rs, _ = sync_grads(g, mesh, "data", schedule="rs_ag")
        for x, y in zip(jax.tree.leaves(ar), jax.tree.leaves(rs)):
            np.testing.assert_allclose(x, y, atol=1e-6)
        # int8: bounded per-step error, error-feedback residual carried
        q, err = sync_grads(g, mesh, "data", schedule="int8")
        scale = float(jnp.max(jnp.abs(g["a"]))) / 127
        assert float(jnp.max(jnp.abs(q["a"] - g["a"]))) <= scale + 1e-6
        assert err is not None
        print("collectives OK")
    """)


def test_small_mesh_dryrun_lowers_and_compiles():
    """End-to-end dry-run machinery on a 2x4 mesh (fast miniature of the
    production 16x16 path, exercising identical code)."""
    run_devices("""
        from repro.configs import get_config
        from repro.launch.dryrun import build_lowered
        from repro.launch.mesh import make_mesh
        from repro.models.config import SHAPES_BY_NAME, ShapeSpec
        from repro.parallel import sharding as shd
        import dataclasses
        cfg = get_config("gemma_7b").reduced()
        shape = ShapeSpec("mini_train", 64, 8, "train")
        mesh = make_mesh((2, 4), ("data", "model"))
        prof = shd.profile_for(cfg, mesh, zero3=True)
        lowered = build_lowered(cfg, shape, mesh, prof, microbatches=2,
                                donate=True)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        assert ca["flops"] > 0
        txt = compiled.as_text()
        assert any(k in txt for k in ("all-reduce", "all-gather",
                                      "reduce-scatter"))
        print("mini dryrun OK")
    """)


def test_chip_smoke_mesh_phase_rehearses_on_host_mesh():
    """chip_smoke.py --chips 4's path at a tiny size on 4 host devices:
    2x2 vs 1x1 losses agree and the state spans all four devices."""
    out = run_devices(f"""
        import sys
        sys.path.insert(0, {str(Path(SRC).parent)!r})
        import chip_smoke
        chip_smoke.mesh_phase(reduced=True, seq=32, batch=8, steps=2)
    """, n=4)
    assert "max relative loss difference" in out
