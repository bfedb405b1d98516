"""Roofline-term extraction from the compiled dry-run artifact.

Three terms per (arch × shape × mesh), in seconds (assignment §Roofline):

  compute    = per-device HLO FLOPs / peak_FLOP/s        (cost_analysis)
  memory     = per-device HLO bytes / HBM bandwidth       (cost_analysis)
  collective = per-device collective bytes / ICI link bw  (analytic + HLO)

``cost_analysis()`` reports the *per-device* partitioned module (verified
empirically: a 2×4-sharded matmul reports dense/8 flops), so terms divide by
per-chip peaks directly.

Collective bytes: collectives inside ``lax.scan`` bodies appear once in HLO
text but execute once per trip, so a static text sum undercounts by the
layer count.  We therefore compute the collective term *analytically* from
the sharding profile (the framework knows which collectives its shardings
induce — FSDP all-gathers, ZeRO-1 reduce-scatter+all-gather, TP activation
collectives, EP all-to-alls) and use the HLO text parse (op kinds + per-trip
bytes) as a cross-check recorded alongside.
"""

from __future__ import annotations

import math
import re
from dataclasses import asdict, dataclass, field

import jax

from repro.models.config import ArchConfig, ShapeSpec
from repro.models.layers import mlstm_chunk
from repro.models.lm import LM

# TPU v5e constants (assignment).
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s/link; 2 links/axis direction on a torus
DCI_BW = 12.5e9              # inter-pod


# ---------------------------------------------------------------------------
# HLO text parsing (cross-check)
# ---------------------------------------------------------------------------

_DT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
             "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_COLL_RE = re.compile(
    r"=\s*(?P<sig>[^=]*?)\s*"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _sig_bytes(sig: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(sig):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


@dataclass
class HloCollectives:
    """Static (per-trip) collective footprint of the compiled module."""

    counts: dict[str, int] = field(default_factory=dict)
    bytes_static: dict[str, float] = field(default_factory=dict)

    @property
    def total_static(self) -> float:
        return sum(self.bytes_static.values())


def parse_collectives(hlo_text: str) -> HloCollectives:
    out = HloCollectives()
    for m in _COLL_RE.finditer(hlo_text):
        kind = m.group("kind")
        b = _sig_bytes(m.group("sig"))
        out.counts[kind] = out.counts.get(kind, 0) + 1
        out.bytes_static[kind] = out.bytes_static.get(kind, 0.0) + b
    return out


# ---------------------------------------------------------------------------
# Analytic HBM traffic (fused-TPU view)
#
# The CPU-backend HLO "bytes accessed" counts every op's operands unfused
# (~50-100x what a fused TPU pass touches), so the memory term uses this
# analytic model instead; the HLO number is kept as an upper-bound
# cross-check.  ``attn_fused=False`` charges the S×S score round-trips of
# the unfused jnp attention path — the traffic the Pallas flash kernel
# (repro.kernels.flash_attention) eliminates.
# ---------------------------------------------------------------------------


def analytic_hbm_bytes(cfg: ArchConfig, shape: ShapeSpec,
                       mesh_shape: dict[str, int], *, zero3: bool,
                       microbatches: int, remat: str = "full",
                       attn_fused: bool = False) -> dict[str, float]:
    model = mesh_shape.get("model", 1)
    data = mesh_shape.get("data", 1) * mesh_shape.get("pod", 1)
    db = 2
    B, S = shape.global_batch, shape.seq_len
    Sq = 1 if shape.kind == "decode" else S
    L, d = cfg.n_layers, cfg.d_model
    M = microbatches
    train = shape.kind == "train"

    n = LM(cfg).n_params()
    p_shards = model * (data if zero3 else 1)
    p_loc = n * db / p_shards
    tok_loc = max(B // data, 1) * Sq              # per device per step
    # heads replicated over "model" when not divisible (fallback rule)
    H_loc = cfg.n_heads // model if cfg.n_heads % model == 0 else cfg.n_heads

    out: dict[str, float] = {}
    # parameters: fwd read ×M (+ bwd re-read, + remat re-read), optimizer r/w
    if train:
        reads = M * (2 + (1 if remat == "full" else 0))
        out["params_io"] = p_loc * reads
        n_opt_loc = n / (model * data)            # zero1: moments over data
        out["optimizer_io"] = n_opt_loc * (4 * 4 + 2 * db) + p_loc * 2
    else:
        out["params_io"] = p_loc
    # activations: residual stream + block internals, fwd (+bwd ~2x, remat +1)
    act_mult = (4.0 if remat == "full" else 3.0) if train else 1.0
    d_ff_eff = cfg.top_k * cfg.d_ff if cfg.n_experts else cfg.d_ff
    act_total = 0.0
    for i in range(L):
        kind = cfg.block_kind(i)
        if kind == "mamba":
            inner = 6 * cfg.ssm_expand * d          # z/x/conv/gate streams
        elif kind in ("mlstm", "slstm"):
            inner = 10 * d                          # qkv/gates at e≈2d
        else:
            inner = 4 * max(d_ff_eff, 2 * d)
        act_total += tok_loc * (8 * d + inner) * db
    out["activations_io"] = act_mult * act_total
    # unfused attention scores (the flash-kernel target)
    n_attn = sum(1 for i in range(L) if cfg.block_kind(i) in
                 ("attn", "cross_attn", "shared_attn"))
    if not attn_fused and n_attn:
        kv_avg = min(cfg.attn_window or S, S) if shape.kind != "decode" \
            else min(cfg.attn_window or S, S)
        causal_frac = 0.5 if (shape.kind != "decode"
                              and not cfg.attn_window) else 1.0
        B_loc = max(B // data, 1)
        score_rw = 3 * 4                           # write+read f32, + softmax
        out["attn_scores_io"] = (act_mult if train else 1.0) * n_attn * \
            B_loc * H_loc * Sq * kv_avg * causal_frac * score_rw
    # kv cache / recurrent state io (serving: the cache read dominates)
    if shape.kind == "decode":
        mdl = LM(cfg)
        cache = mdl.init_cache(B, S, abstract=True)
        total = sum(math.prod(x.shape) * x.dtype.itemsize
                    for x in jax.tree.leaves(cache))
        out["cache_io"] = total / (data * model) * 2   # read + write
    # lm head + embed
    V_loc = cfg.vocab / model if cfg.vocab % model == 0 else cfg.vocab
    if train:
        out["lm_head_io"] = tok_loc * V_loc * (db + 4) + \
            M * (cfg.vocab * d * db / p_shards) * 3
    else:
        out["lm_head_io"] = max(B // data, 1) * V_loc * 4
    return out


def _ring_ag_bytes(size_global: float, n: int) -> float:
    """Per-device wire bytes for a ring all-gather of a tensor whose global
    (gathered) size is ``size_global``, over ``n`` participants."""
    if n <= 1:
        return 0.0
    return (n - 1) / n * size_global


def analytic_collectives(cfg: ArchConfig, shape: ShapeSpec, mesh_shape:
                         dict[str, int], *, zero3: bool, zero1: bool,
                         microbatches: int = 1) -> dict[str, float]:
    """Per-device, per-step collective wire bytes by class.

    Classes map to mesh axes (multi-edge: different axes = different physical
    links, so only same-axis traffic serializes — DESIGN.md §3):
      * tp_*:   activation collectives on the "model" axis
      * dp_*:   gradient sync on "data" (+ "pod"): AR, or RS+AG (ZeRO-1),
                plus FSDP param all-gathers when zero3
      * ep_*:   MoE all-to-all on "model"
    """
    model = mesh_shape.get("model", 1)
    data = mesh_shape.get("data", 1) * mesh_shape.get("pod", 1)
    db = 2  # bf16
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        S = 1
    L = cfg.n_layers
    d = cfg.d_model
    act_global = B * S * d * db            # one residual-stream tensor
    m = LM(cfg)
    params_bytes = m.n_params() * db

    out: dict[str, float] = {}
    heads_shardable = cfg.n_heads % model == 0
    # TP activation collectives per layer (fwd; bwd doubles; train = 3x fwd
    # cost in flops but 2 passes of collectives).
    passes = 2.0 if shape.kind == "train" else 1.0
    n_attn = sum(1 for i in range(L)
                 if cfg.block_kind(i) in ("attn", "cross_attn", "shared_attn"))
    n_ffn = sum(1 for i in range(L) if cfg.block_kind(i) == "attn"
                and not cfg.n_experts) \
        + sum(1 for i in range(L) if cfg.block_kind(i) in
              ("cross_attn", "shared_attn"))
    if model > 1 and heads_shardable:
        # Megatron TP: each attn/ffn output row-parallel matmul ends in an
        # all-reduce of the activation (2 per transformer layer).
        n_coll = n_attn + n_ffn
        out["tp_allreduce_model"] = passes * n_coll * 2 * _ring_ag_bytes(
            act_global / max(data, 1), model)
    if cfg.n_experts and model > 1:
        # EP: dispatch+combine all-to-alls of the routed activations.
        moe_layers = sum(1 for i in range(L) if cfg.block_kind(i) == "attn")
        routed = act_global / max(data, 1) * cfg.top_k
        out["ep_alltoall_model"] = passes * moe_layers * 2 * routed / model
    if shape.kind == "train" and data > 1:
        if zero1 or zero3:
            out["dp_reduce_scatter_data"] = _ring_ag_bytes(params_bytes, data)
            out["dp_all_gather_data"] = _ring_ag_bytes(params_bytes, data)
        else:
            out["dp_allreduce_data"] = 2 * _ring_ag_bytes(params_bytes, data)
        if zero3:
            # params re-gathered each microbatch fwd+bwd.  Expert weights
            # use 2-D TP on the data axis instead of FSDP (layers.moe_defs),
            # so only the dense remainder is gathered.
            expert_bytes = cfg.n_layers * cfg.n_experts * 3 * cfg.d_model \
                * cfg.d_ff * db if cfg.n_experts else 0.0
            out["fsdp_all_gather_data"] = 2 * microbatches * _ring_ag_bytes(
                max(params_bytes - expert_bytes, 0.0), data)
    return out


def collective_seconds(vol: dict[str, float],
                       mesh_shape: dict[str, int]) -> float:
    """Serialize same-axis traffic; different axes ride different ICI links
    (multi-edge) — the slower of the two axis queues bounds the term when
    overlap is perfect, their sum when not.  We report the conservative
    no-overlap sum within an axis and max across axes."""
    per_axis: dict[str, float] = {}
    for k, v in vol.items():
        axis = k.rsplit("_", 1)[-1]
        bw = ICI_BW * 2  # bidirectional ring: 2 links per axis
        if axis == "data" and mesh_shape.get("pod", 1) > 1:
            bw = DCI_BW  # gradient ring crosses the pod boundary
        per_axis[axis] = per_axis.get(axis, 0.0) + v / bw
    return max(per_axis.values()) if per_axis else 0.0


# ---------------------------------------------------------------------------
# Probe-based cost scaling
#
# XLA's cost_analysis counts a while-loop body ONCE, not per trip, so the
# full-cell lowering (layers scanned, microbatches scanned) undercounts
# FLOPs/bytes by the trip counts.  We therefore lower the same step with 1
# and 2 layer-cycles (a 1- or 2-trip scan is counted exactly): the delta is
# the true per-cycle cost, and known static trip counts (n_cycles ×
# microbatches) scale it to the full model.  Attention chunk loops and the
# cross-entropy chunk loop are python-unrolled in the model, so probes count
# them exactly.  Recurrent *time* scans (mamba/mlstm/slstm, S trips) get an
# analytic correction below.
# ---------------------------------------------------------------------------


def combine_probe_costs(*, f1: dict[str, float], f2: dict[str, float],
                        n_cycles: int, microbatches: int,
                        f_enc1: dict[str, float] | None = None,
                        n_enc: int = 0) -> dict[str, float]:
    """Extrapolate per-device (flops, bytes) from 1-/2-cycle probes."""
    out = {}
    for k in ("flops", "bytes"):
        d_cyc = max(f2[k] - f1[k], 0.0)
        base = max(f1[k] - d_cyc, 0.0)
        if f_enc1 is not None and n_enc > 0:
            d_enc = max(f_enc1[k] - f1[k], 0.0)   # probe3: one extra enc layer
            base_total = base + d_cyc * n_cycles + d_enc * (n_enc - 1)
        else:
            base_total = base + d_cyc * n_cycles
        out[k] = base_total * microbatches
        out[f"{k}_per_cycle"] = d_cyc
        out[f"{k}_base"] = base
    return out


def recurrent_correction(cfg: ArchConfig, shape: ShapeSpec,
                         mesh_shape: dict[str, int]) -> dict[str, float]:
    """Analytic per-device flops/bytes of the sequential time scans, which
    probes count once instead of S times (decode: S=1, nothing to fix)."""
    if shape.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    model = mesh_shape.get("model", 1)
    data = mesh_shape.get("data", 1) * mesh_shape.get("pod", 1)
    B = shape.global_batch
    S = shape.seq_len
    B_loc = max(B // data, 1)
    mult = 3.0 if shape.kind == "train" else 1.0      # bwd re-runs the scan
    d = cfg.d_model
    flops = byts = 0.0
    for kind in cfg.pattern:          # one occurrence per cycle per position
        n_occ = cfg.n_cycles
        if kind == "mamba":
            # chunkwise-parallel SSD: the big intra-chunk einsums sit
            # OUTSIDE the chunk loop and the boundary step unrolls in the
            # probes, so probe costs are already exact — no correction.
            continue
        elif kind == "mlstm":
            H = cfg.n_heads
            hd = 2 * d // H
            c = mlstm_chunk(S, hd)
            if c and S // c <= 128:
                continue      # probes unroll the chunk loop: counted exactly
            if c:
                # chunkwise analytic: intra matmuls + per-chunk state io
                flops += n_occ * B_loc * H * (4 * S * c * hd + 8 * (S // c)
                                              * hd * hd)
                byts += n_occ * (S // c) * B_loc * 2 * H * hd * hd * 4
            else:
                st = H * hd * hd
                flops += n_occ * S * B_loc * 8 * st
                byts += n_occ * S * B_loc * 2 * st * 4
        elif kind == "slstm":
            H = cfg.n_heads
            hd = d // H
            rec = H * hd * 4 * hd
            flops += n_occ * S * B_loc * 2 * rec
            byts += n_occ * S * (rec * 2 + B_loc * 8 * H * hd * 4)
    return {"flops": flops * mult, "bytes": byts * mult}


# ---------------------------------------------------------------------------
# Cell report
# ---------------------------------------------------------------------------


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-device.  hlo_* are the raw cost_analysis numbers of the full-cell
    # module (loop bodies counted once); flops/bytes are the probe-scaled
    # true per-step costs used for the terms.
    hlo_flops_static: float
    hlo_bytes_static: float
    flops: float
    bytes: float
    collective_bytes: float
    # terms (seconds)
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float
    useful_ratio: float
    # memory fit
    arg_bytes: float
    temp_bytes: float
    fits: bool
    hlo_coll_counts: dict[str, int] = field(default_factory=dict)
    hlo_coll_bytes_static: float = 0.0
    analytic_detail: dict[str, float] = field(default_factory=dict)
    probe_detail: dict[str, float] = field(default_factory=dict)
    note: str = ""

    def to_dict(self) -> dict:
        return asdict(self)


def model_flops_estimate(cfg: ArchConfig, shape: ShapeSpec) -> float:
    """MODEL_FLOPS = 6·N·D (train), 2·N·D (prefill/decode); N = active."""
    m = LM(cfg)
    n = m.n_params()
    if cfg.n_experts:
        dense_ffn = cfg.n_layers * cfg.n_experts * (
            3 * cfg.d_model * cfg.d_ff)
        active_ffn = cfg.n_layers * cfg.top_k * (3 * cfg.d_model * cfg.d_ff)
        n = n - dense_ffn + active_ffn
    tokens = shape.global_batch * (1 if shape.kind == "decode"
                                   else shape.seq_len)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def build_report(*, arch: str, shape: ShapeSpec, mesh_name: str,
                 mesh_shape: dict[str, int], cfg: ArchConfig,
                 compiled, hlo_text: str | None, zero3: bool, zero1: bool,
                 microbatches: int, probe: dict[str, float] | None = None,
                 remat_policy: str = "full", attn_fused: bool = False,
                 note: str = "") -> RooflineReport:
    chips = math.prod(mesh_shape.values())
    ca = compiled.cost_analysis()
    flops_static = float(ca.get("flops", 0.0))
    bytes_static = float(ca.get("bytes accessed", 0.0))
    if probe is not None:
        corr = recurrent_correction(cfg, shape, mesh_shape)
        flops = probe["flops"] + corr["flops"]
        probe = {**probe, "recurrent_corr_flops": corr["flops"],
                 "recurrent_corr_bytes": corr["bytes"]}
    else:
        corr = recurrent_correction(cfg, shape, mesh_shape)
        flops = flops_static
    # memory term: analytic fused-TPU traffic (HLO bytes kept as the
    # unfused upper bound in hlo_bytes_static)
    hbm = analytic_hbm_bytes(cfg, shape, mesh_shape, zero3=zero3,
                             microbatches=microbatches, remat=remat_policy,
                             attn_fused=attn_fused)
    byts = sum(hbm.values()) + corr["bytes"]
    vol = analytic_collectives(cfg, shape, mesh_shape, zero3=zero3,
                               zero1=zero1, microbatches=microbatches)
    t_comp = flops / PEAK_FLOPS
    t_mem = byts / HBM_BW
    t_coll = collective_seconds(vol, mesh_shape)
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops_estimate(cfg, shape)
    ma = compiled.memory_analysis()
    arg = float(getattr(ma, "argument_size_in_bytes", 0))
    tmp = float(getattr(ma, "temp_size_in_bytes", 0))
    out_b = float(getattr(ma, "output_size_in_bytes", 0))
    alias = float(getattr(ma, "alias_size_in_bytes", 0))
    hc = parse_collectives(hlo_text) if hlo_text else HloCollectives()
    return RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_flops_static=flops_static, hlo_bytes_static=bytes_static,
        flops=flops, bytes=byts,
        collective_bytes=sum(vol.values()),
        t_compute=t_comp, t_memory=t_mem, t_collective=t_coll,
        bottleneck=bottleneck, model_flops=mf,
        useful_ratio=(mf / (flops * chips)) if flops else 0.0,
        arg_bytes=arg, temp_bytes=tmp,
        fits=(arg + tmp + out_b - alias) <= 16e9,
        hlo_coll_counts=hc.counts, hlo_coll_bytes_static=hc.total_static,
        analytic_detail={**vol, **{f"hbm_{k}": v for k, v in hbm.items()}},
        probe_detail=probe or {}, note=note)
