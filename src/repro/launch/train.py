"""Training launcher CLI.

Plans with the paper's search (over an analytic cluster of the devices this
process sees), then trains the selected architecture on those devices:

  PYTHONPATH=src python -m repro.launch.train --arch xlstm_125m --steps 50 \\
      --global-batch 8 --seq 4096 --remat full [--reduced] [--plan auto|none]

The defaults are the full published xLSTM-125M at batch 8 × seq 4096 with
full recomputation, which fits one TPU v5e chip.  ``--reduced`` uses the
smoke-scale config (CPU-friendly; pair it with ``--plan none`` on the CPU,
which has no device profile to plan for).  The run is one process over its
local devices.
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, get_config
from repro.core import hetero_cluster, plan_hybrid, profile_for_device_kind
from repro.optim.adamw import AdamWConfig
from repro.runtime.trainer import Trainer, TrainerConfig

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache(root: str | Path = REPO_ROOT) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself).  Otherwise the cache lives at the fixed ``<root>/.jax_cache``:
    the path is part of the cache key, so it must not move between runs.
    Entry points call this; library modules never do."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm_125m", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--plan", default="auto", choices=["auto", "none"],
                    help="auto: plan for the local devices' profile; none: "
                         "skip planning")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="full",
                    choices=["none", "selective", "full"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    # Plan against an analytic cluster of the local devices (the paper's
    # planning step).  The trainer records the plan in its checkpoints but
    # does not execute it yet: it runs data-parallel over these devices.
    plan = None
    if args.plan == "auto":
        devs = jax.devices()
        topo = hetero_cluster(
            {profile_for_device_kind(devs[0].device_kind): len(devs)},
            gpus_per_node=len(devs))
        res = plan_hybrid(topo, cfg.to_model_desc(),
                          global_batch=args.global_batch, seq=args.seq,
                          with_baseline=False)
        plan = res.plan
        print(f"[plan] {plan.describe()} "
              f"(predicted step {res.predicted.step_time*1e3:.1f} ms)")

    tcfg = TrainerConfig(
        arch=cfg, steps=args.steps, global_batch=args.global_batch,
        seq_len=args.seq, ckpt_dir=args.ckpt_dir,
        microbatches=args.microbatches, remat=args.remat,
        opt=AdamWConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 5),
                        total_steps=args.steps))
    trainer = Trainer(tcfg, plan=plan)
    _, hist = trainer.run()
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"[train] loss {first:.4f} -> {last:.4f} over {args.steps} steps")


if __name__ == "__main__":
    main()
