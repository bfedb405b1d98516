"""Chip smoke test: the trainer's main path, once, on a TPU.

    python chip_smoke.py             # one chip: kernels + xLSTM-125M training
    python chip_smoke.py --chips 4   # four chips: 2x2 mesh vs one-device mesh

One chip runs two phases:

  * kernels: the compiled Pallas ``flash_attention`` at Qwen2-7B attention
    shapes and ``rmsnorm`` at d 768 / 3584, each against ``kernels/ref.py``
    in float32 at highest matmul precision, within a stated error bound;
  * train: the full published xLSTM-125M (12 layers, d 768, vocab 50304,
    bf16) at global batch 8 × seq 4096 with full recomputation, through the
    normal ``Trainer``, with a topology built from the chip's device profile
    and one bandwidth event mid-run (save → replan → rebuild → restore).
    Every loss must be finite and the restored state bitwise equal to the
    saved one.  Compile time is reported apart from step time.

``--chips 4`` runs only the same training steps on a (2, 2) data × model
mesh and on a (1, 1) mesh of the first device, in this one process, and
compares their losses; it also checks that the state really spans all four
devices.

Without a TPU the script exits non-zero before doing any work.  The last
line of standard output is ``{"ok": true, "device": {...}}``; it is printed
only when every check passed.  All work sits under ``__main__``, so
importing this file touches no JAX.
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEQ, BATCH, STEPS, EVENT_STEP = 4096, 8, 6, 3
MESH_STEPS = 3
# --chips 4: two meshes run the same steps on the same data; their losses
# differ only by the order of bf16 reductions (sharded matmul partial sums,
# the data-parallel gradient reduction).  Each loss is a mean over
# batch x seq tokens, so it must agree within one bf16 rounding: the unit
# roundoff, 2^-8 relative.
MESH_LOSS_RTOL = 2.0 ** -8


def _check(ok: bool, what: str) -> None:
    """A failed check ends the run (not ``assert``, which ``-O`` strips)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _arch(reduced: bool):
    from repro.configs import get_config
    cfg = get_config("xlstm_125m")
    return cfg.reduced(dtype="bfloat16") if reduced else cfg


def _trainer_config(arch, *, seq: int, batch: int, steps: int, ckpt_dir: str):
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.trainer import TrainerConfig
    return TrainerConfig(
        arch=arch, steps=steps, global_batch=batch, seq_len=seq,
        ckpt_dir=ckpt_dir, ckpt_every=0, log_every=1, remat="full",
        opt=AdamWConfig(peak_lr=3e-4, warmup_steps=2, total_steps=steps))


def kernel_phase(*, seq: int = SEQ, heads: tuple[int, int, int] = (28, 4, 128),
                 norm_shapes=((4096, 768), (3000, 768), (4096, 3584),
                              (3000, 3584)),
                 interpret: bool = False) -> None:
    """Compiled kernels vs the float32 references.

    Both kernels take bf16 inputs, which the float32 reference reads
    exactly, and write bf16 output: rounding to bf16 moves a value by at
    most its unit roundoff, 2^-8 relative.  rmsnorm computes in float32
    otherwise.  flash_attention may also feed the softmax weights to the MXU
    as bf16 (2^-8 relative each), which moves the output by at most
    2^-8 max|v|.  Each bound doubles those terms, leaving the other half for
    float32 differences in summation order."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    f32 = jnp.float32
    H, KV, hd = heads
    kq, kk, kv, kx = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (1, seq, H, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, seq, KV, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (1, seq, KV, hd), jnp.bfloat16)
    o = ops.flash_attention(q, k, v, causal=True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        r = jax.jit(functools.partial(ops.mha_reference, causal=True))(
            q.astype(f32), k.astype(f32), v.astype(f32))
    o, r, vmax = (np.asarray(o, np.float32), np.asarray(r),
                  float(jnp.max(jnp.abs(v.astype(f32)))))
    err = np.abs(o - r)
    bound = 2.0 ** -7 * (np.abs(r) + vmax)
    print(f"[kernel] flash_attention B=1 S={seq} H={H} KV={KV} hd={hd} bf16 "
          f"causal: max_abs_err={err.max()} "
          f"max_err_over_bound={(err / bound).max()}", flush=True)
    _check(o.shape == r.shape and np.all(err <= bound), "flash_attention")
    del q, k, v, o, r

    for rows, d in norm_shapes:
        x = jax.random.normal(kx, (rows, d), jnp.bfloat16)
        w = (1 + 0.1 * jax.random.normal(kx, (d,))).astype(jnp.bfloat16)
        y = np.asarray(ops.rmsnorm(x, w, interpret=interpret), np.float32)
        with jax.default_matmul_precision("highest"):
            r = np.asarray(ops.rmsnorm_reference(x.astype(f32),
                                                 w.astype(f32)))
        err = np.abs(y - r)
        bound = 2.0 ** -7 * np.abs(r) + 1e-6
        print(f"[kernel] rmsnorm rows={rows} d={d} bf16: "
              f"max_abs_err={err.max()} "
              f"max_err_over_bound={(err / bound).max()}", flush=True)
        _check(y.shape == r.shape and np.all(err <= bound), "rmsnorm")


def train_phase(profile: str, *, reduced: bool = False, seq: int = SEQ,
                batch: int = BATCH, steps: int = STEPS,
                event_step: int = EVENT_STEP) -> None:
    """xLSTM-125M through the Trainer with one bandwidth event, planned for
    the ``DEVICE_PROFILES[profile]`` devices this process sees."""
    import jax

    from repro.core import NetworkEvent, hetero_cluster
    from repro.runtime.trainer import Trainer

    class CheckedTrainer(Trainer):
        """Keeps host copies around the event to prove the restore exact."""

        restored_bitwise: bool | None = None

        def _handle_event(self, step, ev, state):
            saved = jax.device_get(state)
            restored = super()._handle_event(step, ev, state)
            got = jax.device_get(restored)
            self.restored_bitwise = (
                jax.tree.structure(saved) == jax.tree.structure(got)
                and all(a.dtype == b.dtype and a.tobytes() == b.tobytes()
                        for a, b in zip(jax.tree.leaves(saved),
                                        jax.tree.leaves(got))))
            return restored

    devs = jax.devices()
    arch = _arch(reduced)
    topo = hetero_cluster({profile: len(devs)}, gpus_per_node=len(devs))
    ev = NetworkEvent(0.0, "bandwidth", factor=0.5, selector="ici",
                      mode="scale")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        tcfg = _trainer_config(arch, seq=seq, batch=batch, steps=steps,
                               ckpt_dir=ckpt)
        tr = CheckedTrainer(tcfg, topo=topo, events=[(event_step, ev)])
        print(f"[train] {arch.name}: {arch.n_layers} layers d={arch.d_model} "
              f"heads={arch.n_heads} vocab={arch.vocab} {arch.dtype} "
              f"params={tr.model.n_params():,}; batch={batch} seq={seq} "
              f"remat=full; device profile {profile} "
              f"({devs[0].device_kind!r})", flush=True)
        _, hist = tr.run()
    losses = [h["loss"] for h in hist]
    step_s = [h["step_s"] for h in hist]
    med = statistics.median(step_s)
    mem = devs[0].memory_stats() or {}
    ev_log = tr.event_log[0]
    print(f"[train] losses={losses} (ln vocab={math.log(arch.vocab)})")
    print(f"[train] step_s={step_s}")
    print(f"[train] compile_s first={tr.compile_s[0]} "
          f"after_event={tr.compile_s[-1]} (apart from step times)")
    print(f"[train] median_step_s={med} tokens_per_s={batch * seq / med}")
    ma = tr._compiled.memory_analysis()
    print(f"[train] peak_bytes_in_use={mem.get('peak_bytes_in_use')}; "
          f"compiled step temp_bytes={ma.temp_size_in_bytes} "
          f"argument_bytes={ma.argument_size_in_bytes}")
    print(f"[train] event step={ev_log['step']} kind={ev_log['kind']} "
          f"stall_s={ev_log['stall_s']} restore_s={ev_log['restore_s']} "
          f"replans={tr.replans} "
          f"action={tr.adaptations[0].action} "
          f"restored_bitwise={tr.restored_bitwise}", flush=True)
    _check(len(losses) == steps and all(map(math.isfinite, losses)),
           "a finite loss for every step")
    _check(tr.replans == 1 and tr.restored_bitwise, "event restore")
    _check(len(tr.compile_s) == 2, "one compile before and one after the "
           "event")


def mesh_phase(*, reduced: bool = False, seq: int = SEQ, batch: int = BATCH,
               steps: int = MESH_STEPS) -> None:
    """The same steps on a (2, 2) data × model mesh and a (1, 1) mesh."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.runtime.trainer import Trainer

    arch = _arch(reduced)
    losses = {}
    for shape in ((2, 2), (1, 1)):
        mesh = make_mesh(shape, ("data", "model"))
        with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
            tr = Trainer(_trainer_config(arch, seq=seq, batch=batch,
                                         steps=steps, ckpt_dir=ckpt),
                         mesh=mesh)
            state, hist = tr.run()
        losses[shape] = [h["loss"] for h in hist]
        print(f"[mesh] {shape}: losses={losses[shape]} step_s="
              f"{[h['step_s'] for h in hist]} "
              f"compile_s={tr.compile_s[0]}", flush=True)
        if shape == (2, 2):
            leaves = jax.tree_util.tree_leaves_with_path(state)
            per_dev: dict[int, int] = {}
            for _, x in leaves:
                for sh in x.addressable_shards:
                    per_dev[sh.device.id] = (per_dev.get(sh.device.id, 0)
                                             + sh.data.nbytes)
            total = sum(x.nbytes for _, x in leaves)
            spec = [str(x.sharding.spec) for _, x in leaves]
            n_model = sum("model" in s for s in spec)
            n_data = sum("data" in s for s in spec)
            print(f"[mesh] state leaves={len(leaves)} on 4 devices: "
                  f"sharded over model={n_model} over data={n_data}; "
                  f"bytes per device={per_dev} of total={total}", flush=True)
            _check(all(len(x.sharding.device_set) == 4 for _, x in leaves)
                   and len(per_dev) == 4, "every leaf spans the 4 devices")
            _check(n_model > 0 and n_data > 0
                   and max(per_dev.values()) < total,
                   "state partitioned over both mesh axes")
            _check(min(per_dev.values()) > 0.5 * max(per_dev.values()),
                   "state balanced across devices")
        del state
    a, b = losses[(2, 2)], losses[(1, 1)]
    rel = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    print(f"[mesh] max relative loss difference 2x2 vs 1x1: {rel} "
          f"(tolerance {MESH_LOSS_RTOL})", flush=True)
    _check(all(map(math.isfinite, a + b)) and rel <= MESH_LOSS_RTOL,
           "2x2 and 1x1 losses agree")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found "
              f"{devs[0].platform!r} devices; there is no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} devices", file=sys.stderr)
        return 1
    from repro.core import profile_for_device_kind
    from repro.launch.train import enable_compile_cache
    profile = profile_for_device_kind(devs[0].device_kind)
    print(f"[setup] {len(devs)} x {devs[0].device_kind!r} -> profile "
          f"{profile}; compile cache: {enable_compile_cache(ROOT)}",
          flush=True)

    if args.chips == 4:
        mesh_phase()
    else:
        kernel_phase()
        train_phase(profile)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
