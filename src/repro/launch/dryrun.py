import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each of the 40 assigned cells on the single-pod 16×16 mesh AND the
2×16×16 multi-pod mesh:

  * build the model + sharding profile,
  * ``jax.jit(step, in_shardings=…, out_shardings=…).lower(**specs)``
    with ShapeDtypeStruct stand-ins (no allocation),
  * ``.compile()`` — GSPMD must partition every collective,
  * print ``memory_analysis()`` (proves the 16 GB/v5e-chip fit) and
    ``cost_analysis()`` (FLOPs/bytes for §Roofline),
  * probe-lower the same step at 1 and 2 layer-cycles to recover true
    per-step FLOPs/bytes (XLA cost_analysis counts scan bodies once — see
    repro.launch.roofline), and write a JSON report to reports/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma_7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi     # pod axis
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import build_report, combine_probe_costs
from repro.models.config import SHAPES_BY_NAME, ArchConfig, ShapeSpec
from repro.models.lm import LM
from repro.optim.adamw import AdamWConfig
from repro.parallel import sharding as shd
from repro.parallel.axes import use_rules
from repro.parallel.trainstep import (abstract_train_state, make_prefill_step,
                                      make_serve_step, make_train_step)

REPORTS = Path(__file__).resolve().parents[3] / "reports" / "dryrun"


# ---------------------------------------------------------------------------
# Per-cell policy (the planner's memory model, Eq. 6, applied to the mesh)
# ---------------------------------------------------------------------------


def needs_zero3(cfg: ArchConfig, shape: ShapeSpec, model_extent: int) -> bool:
    n = LM(cfg).n_params()
    if shape.kind == "train":
        resident = 4.0 * n / model_extent          # bf16 p+g, moments zero1'd
    else:
        resident = 2.0 * n / model_extent
    return resident > 6e9


def choose_microbatches(cfg: ArchConfig, shape: ShapeSpec,
                        dp_extent: int) -> int:
    """Smallest grad-accumulation factor whose activation estimate fits."""
    if shape.kind != "train":
        return 1
    per_dev_batch = max(shape.global_batch // dp_extent, 1)
    # MoE working set: top_k routed copies + dispatch/combine buffers
    # (~K·(1+cf)·d_ff per token), much larger than the expert d_ff alone.
    d_ff_eff = cfg.top_k * cfg.d_ff * (1 + cfg.moe_capacity_factor) \
        if cfg.n_experts else cfg.d_ff
    for M in (1, 2, 4, 8, 16, 32):
        if M > per_dev_batch:
            return per_dev_batch
        mb_tokens = per_dev_batch // M * shape.seq_len
        stored = cfg.n_layers * mb_tokens * cfg.d_model * 2      # remat=full
        work = mb_tokens * max(d_ff_eff, 4 * cfg.d_model) * 2 * 4
        if stored + work < 6e9:
            return M
    return per_dev_batch


# ---------------------------------------------------------------------------
# Lowering builder (shared by the full cell and the cost probes)
# ---------------------------------------------------------------------------


def build_lowered(cfg: ArchConfig, shape: ShapeSpec, mesh, prof, *,
                  microbatches: int, donate: bool, remat: str = "full",
                  unroll: bool = False):
    """Lower one step for ``cfg`` on ``mesh``; returns the jax Lowered."""
    if cfg.n_experts:
        # group-local MoE dispatch aligned with the data shards (the global
        # argsort would all-gather every token — see layers.moe_block)
        mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_ext = mesh_shape.get("data", 1) * mesh_shape.get("pod", 1)
        t_mb = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                     else 1) // microbatches
        if t_mb % dp_ext == 0:
            cfg = dataclasses.replace(cfg, moe_groups=dp_ext)
    model = LM(cfg, unroll=unroll)
    specs = cfg.input_specs(shape)
    batch_sh = shd.batch_shardings(mesh, specs, prof.rules)
    with mesh:
        if shape.kind == "train":
            step = make_train_step(model, AdamWConfig(),
                                   microbatches=microbatches, remat=remat)
            state_sh = {
                "params": shd.param_shardings(model, mesh, prof.rules),
                "opt": shd.opt_state_shardings(model, mesh, prof.opt_rules),
            }
            state_abs = abstract_train_state(model)
            metrics_sh = jax.tree.map(
                lambda _: NamedSharding(mesh, P()),
                {"loss": 0, "grad_norm": 0, "lr": 0, "tokens": 0})

            def wrapped(state, batch):
                with use_rules(mesh, prof.rules):
                    return step(state, batch)

            return jax.jit(wrapped,
                           in_shardings=(state_sh, batch_sh),
                           out_shardings=(state_sh, metrics_sh),
                           donate_argnums=(0,) if donate else ()
                           ).lower(state_abs, specs)
        if shape.kind == "prefill":
            step = make_prefill_step(model)
            p_sh = shd.param_shardings(model, mesh, prof.rules)

            def wrapped(params, batch):
                with use_rules(mesh, prof.rules):
                    return step(params, batch)

            # pin output shardings: last-token logits + the stacked prefill
            # cache (otherwise GSPMD under-shards the 32k cache output)
            out_abs = jax.eval_shape(wrapped, model.abstract_params(), specs)
            logits_sh = prof.rules.sharding(
                ("batch", "vocab"), out_abs[0].shape, mesh)
            cache_sh = shd._tree_shardings(model.stacked_cache_axes(),
                                           out_abs[1], mesh, prof.rules)
            return jax.jit(wrapped, in_shardings=(p_sh, batch_sh),
                           out_shardings=(logits_sh, cache_sh)
                           ).lower(model.abstract_params(), specs)
        # decode
        step = make_serve_step(model)
        p_sh = shd.param_shardings(model, mesh, prof.rules)
        cache_abs = model.init_cache(shape.global_batch, shape.seq_len,
                                     abstract=True)
        cache_sh = shd.cache_shardings(model, mesh, prof.rules,
                                       shape.global_batch, shape.seq_len)
        logits_sh = prof.rules.sharding(
            ("batch", "vocab"), (shape.global_batch, cfg.vocab), mesh)

        def wrapped(params, cache, batch):
            with use_rules(mesh, prof.rules):
                return step(params, cache, batch)

        return jax.jit(wrapped,
                       in_shardings=(p_sh, cache_sh, batch_sh),
                       out_shardings=(logits_sh, cache_sh),
                       donate_argnums=(1,) if donate else ()
                       ).lower(model.abstract_params(), cache_abs, specs)


def probe_costs(cfg: ArchConfig, shape: ShapeSpec, mesh, prof, *,
                remat: str = "full") -> dict:
    """1-/2-cycle probe lowerings -> true per-device per-step flops/bytes."""
    cyc = cfg.cycle_len

    def cost_of(n_layers: int, enc: int) -> dict[str, float]:
        sub = dataclasses.replace(cfg, n_layers=n_layers,
                                  encoder_layers=enc)
        lowered = build_lowered(sub, shape, mesh, prof, microbatches=1,
                                donate=False, unroll=True, remat=remat)
        ca = lowered.compile().cost_analysis()
        return {"flops": float(ca.get("flops", 0.0)),
                "bytes": float(ca.get("bytes accessed", 0.0))}

    # Probes run at microbatches=1 with the FULL global batch, so their
    # flops/bytes already cover every token of the step — no M scaling.
    enc1 = min(cfg.encoder_layers, 1)
    f1 = cost_of(cyc, enc1)
    f2 = cost_of(2 * cyc, enc1)
    f_enc = cost_of(cyc, 2) if cfg.encoder_layers else None
    return combine_probe_costs(
        f1=f1, f2=f2, n_cycles=cfg.n_cycles, microbatches=1,
        f_enc1=f_enc, n_enc=cfg.encoder_layers)


# ---------------------------------------------------------------------------
# Cell driver
# ---------------------------------------------------------------------------


def lower_cell(arch: str, shape_name: str, mesh_kind: str, *,
               verbose: bool = True, zero3: bool | None = None,
               donate: bool = True, with_probe: bool = True,
               microbatches: int | None = None, remat: str = "full",
               attn_fused: bool = False, pad_q_heads: bool = False):
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    if shape not in cfg.shapes():
        reason = dict(cfg.skipped_shapes()).get(shape, "not applicable")
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skip", "reason": str(reason)}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    if pad_q_heads:
        cfg = shd.pad_heads(cfg, mesh)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_extent = mesh_shape.get("model", 1)
    dp_extent = mesh_shape.get("data", 1) * mesh_shape.get("pod", 1)
    if zero3 is None:
        zero3 = needs_zero3(cfg, shape, model_extent)
    M = microbatches or choose_microbatches(cfg, shape, dp_extent)
    prof = shd.profile_for(cfg, mesh, zero3=zero3)

    t0 = time.perf_counter()
    per_dev_batch = max(shape.global_batch // dp_extent, 1)
    while True:
        lowered = build_lowered(cfg, shape, mesh, prof, microbatches=M,
                                donate=donate, remat=remat)
        compiled = lowered.compile()
        ma0 = compiled.memory_analysis()
        used = (ma0.argument_size_in_bytes + ma0.temp_size_in_bytes
                + ma0.output_size_in_bytes - ma0.alias_size_in_bytes)
        # memory-driven microbatch escalation (Eq. 6 applied post-compile)
        if used <= 16e9 or shape.kind != "train" or M >= per_dev_batch:
            break
        M = min(M * 2, per_dev_batch)
    t_lower = 0.0
    t_compile = time.perf_counter() - t0

    probe = None
    if with_probe:
        probe = probe_costs(cfg, shape, mesh, prof, remat=remat)

    hlo_text = compiled.as_text()
    rep = build_report(arch=arch, shape=shape, mesh_name=mesh_kind,
                       mesh_shape=mesh_shape, cfg=cfg, compiled=compiled,
                       hlo_text=hlo_text, zero3=zero3, zero1=True,
                       microbatches=M, probe=probe, remat_policy=remat,
                       attn_fused=attn_fused)
    out = {"status": "ok", "t_lower_s": round(t_lower, 1),
           "t_compile_s": round(t_compile, 1), "zero3": zero3,
           "microbatches": M, "remat": remat,
           "profile_notes": list(prof.notes),
           **rep.to_dict()}
    if verbose:
        ma = compiled.memory_analysis()
        print(f"  memory_analysis: args={ma.argument_size_in_bytes/1e9:.2f}GB "
              f"temp={ma.temp_size_in_bytes/1e9:.2f}GB "
              f"out={ma.output_size_in_bytes/1e9:.2f}GB "
              f"alias={ma.alias_size_in_bytes/1e9:.2f}GB "
              f"-> fits16GB={out['fits']}")
        print(f"  cost_analysis(static): flops/dev={rep.hlo_flops_static:.3e}"
              f" bytes/dev={rep.hlo_bytes_static:.3e}")
        print(f"  probe-scaled: flops/dev={rep.flops:.3e} "
              f"bytes/dev={rep.bytes:.3e}")
        print(f"  roofline: compute={rep.t_compute*1e3:.1f}ms "
              f"memory={rep.t_memory*1e3:.1f}ms "
              f"collective={rep.t_collective*1e3:.1f}ms "
              f"-> {rep.bottleneck}-bound  useful={rep.useful_ratio:.2f}")
        print(f"  hlo collectives (static): {rep.hlo_coll_counts}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES_BY_NAME)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    REPORTS.mkdir(parents=True, exist_ok=True)

    results = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                tag = f"{arch} × {shape} × {mk}"
                print(f"[dryrun] {tag}", flush=True)
                t0 = time.perf_counter()
                try:
                    r = lower_cell(arch, shape, mk,
                                   donate=not args.no_donate,
                                   with_probe=not args.no_probe)
                except Exception as e:  # noqa: BLE001 — report, keep going
                    r = {"arch": arch, "shape": shape, "mesh": mk,
                         "status": "fail", "error": repr(e),
                         "trace": traceback.format_exc()[-2000:]}
                    print(f"  FAIL: {e!r}")
                r["wall_s"] = round(time.perf_counter() - t0, 1)
                results.append(r)
                path = REPORTS / f"{arch}.{shape}.{mk}.json"
                path.write_text(json.dumps(r, indent=1, default=str))
                print(f"  -> {r['status']} ({r['wall_s']}s)", flush=True)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_fail = sum(r["status"] == "fail" for r in results)
    print(f"\n[dryrun] done: {n_ok} ok, {n_skip} skip, {n_fail} FAIL "
          f"of {len(results)} cells")
    if n_fail:
        for r in results:
            if r["status"] == "fail":
                print(f"  FAILED {r['arch']} {r['shape']} {r['mesh']}: "
                      f"{r['error']}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
