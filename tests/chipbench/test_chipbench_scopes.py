"""Device self time per named scope of the program: the op_names the
profiler records, the reduction per scope, and the readers."""

import re
from pathlib import Path

import pytest

from chipbench import scopes, trace

HERE = Path(__file__).resolve().parent
RECORDED = HERE / "data" / "small_trace"

MS = 1_000_000
# ``%fusion.3 = ... metadata={op_type="dot_general" op_name="jit(f)/..."}``
INSTRUCTION = re.compile(r'\s*(?:ROOT )?%?([^\s=]+) = .*?op_name="([^"]*)"')


def test_scope_self_times_sum_to_busy():
    """Nested ops under named scopes: a loop under ``slstm_scan`` keeps
    what its body leaves, its body ops count under their own scopes, an op
    the program does not name is unscoped, and the scopes sum to busy."""
    spans = [("chipbench.traced", 0, 40 * MS)]
    ops = {"/device:TPU:0": [("slstm_scan", 0, 10 * MS),
                             ("slstm_scan", 1 * MS, 4 * MS),
                             ("slstm", 5 * MS, 9 * MS),
                             ("mlstm_cell", 12 * MS, 20 * MS),
                             ("unscoped", 20 * MS, 21 * MS),
                             ("unscoped", 30 * MS, 32 * MS),
                             ("mlstm_cell", 39 * MS, 45 * MS)]}
    by = scopes.scope_times(spans, ops)
    assert by == pytest.approx({"slstm_scan": 0.006, "slstm": 0.004,
                                "mlstm_cell": 0.009, "unscoped": 0.003})
    busy = trace.reduce(spans, ops, window="chipbench.traced")["busy_s"]
    assert sum(by.values()) == pytest.approx(busy)
    assert "head" not in by


def test_scope_times_average_over_devices():
    spans = [("chipbench.traced", 0, 10 * MS)]
    ops = {"/device:TPU:0": [("head", 0, 4 * MS)],
           "/device:TPU:1": [("head", 0, 2 * MS), ("adamw", 2 * MS, 3 * MS)]}
    by = scopes.scope_times(spans, ops)
    assert by == pytest.approx({"head": 0.003, "adamw": 0.0005,
                                "unscoped": 0.0})


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/jvp(head)/jit(_take)/gather", "head"),
    ("jit(f)/transpose(jvp(head))/bsd,vd->bsv/dot_general", "head"),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlstm/mlstm_cell/while/body/exp", "mlstm_cell"),
    ("jit(f)/jvp()/while/body/closed_call/mlstm/bse,ef->bsf/dot_general",
     "mlstm"),
    ("jit(f)/adamw/jit(clip)/max", "adamw"),
    ("jit(f)/jvp()/while", "unscoped"),
])
def test_scope_of_reads_through_wrappers(op_name, scope):
    assert scopes.scope_of(op_name) == scope


SSD = ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
       "rematted_computation/mamba/mamba2_ssd/while/body/dot_general")


@pytest.mark.parametrize("config, scope", [
    ({"scopes": ["mamba2_ssd"]}, "mamba2_ssd"),
    ({}, "mamba"),
    ({"scopes": ["other_kernel"]}, "mamba"),
])
def test_declared_scope_is_filed_under_itself(config, scope):
    """A kernel scope that the configuration's file declares is a layer of
    its own; undeclared, its ops stay with the enclosing block."""
    assert scopes.scope_of(SSD, scopes.scope_set(config)) == scope
    assert scopes.scope_set({}) == scopes.SCOPES


def test_op_names_of_the_recorded_trace():
    """The op_names of the recorded trace's device ops, from the program's
    HLO that the profiler keeps beside them: the two matmul fusions of
    ``tanh(x @ x) @ x`` carry one, the copies the compiler added do not."""
    xplane = trace.find_xplane(RECORDED)
    names = scopes.op_names(xplane.read_bytes())
    assert set(names) == {"/device:TPU:0"}
    got = {trace.op_name(k): v for k, v in names["/device:TPU:0"].items()}
    assert got == {"convolution_tanh_fusion": "jit(<lambda>)/dot_general",
                   "fusion": "jit(<lambda>)/dot_general"}


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """Protobuf wire bytes of (field number, int, str or bytes) pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, stats, events, lines=()):
    """An XPlane: stat metadata {id: name}, event metadata as (id, name,
    [XStat]), and lines as (name, [(metadata id, start ms, length ms)])
    (XLine: name 2, timestamp_ns 3, events 4; XEvent: metadata_id 1,
    offset_ps 2, duration_ps 3)."""
    ps = 10 ** 9
    return _msg((2, name),
                *[(3, _msg((2, ln), (3, 1000),
                           *[(4, _msg((1, i), (2, a * ps), (3, d * ps)))
                             for i, a, d in evs]))
                  for ln, evs in lines],
                *[(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
                  for i, n in stats.items()],
                *[(4, _msg((1, i), (2, _msg((1, i), (2, n),
                                            *[(5, st) for st in sts]))))
                  for i, n, sts in events])


def _hlo(*instructions):
    """An HloProto of one computation: (instruction name, op_name)."""
    return _msg((1, _msg((3, _msg(*[
        (2, _msg((1, ins), (7, _msg((1, "mul"), (2, op)))))
        for ins, op in instructions])))))


def test_op_names_follow_the_program_id():
    """Two programs with an instruction of the same name: each device op
    takes the op_name of the program it ran in."""
    meta = _plane("/host:metadata", {1: "Hlo Proto"}, [
        (7, "jit_f(7)", [_msg((1, 1), (6, _hlo(("fusion.1",
                                                 "jit(f)/head"))))]),
        (9, "jit_g(9)", [_msg((1, 1), (6, _hlo(("fusion.1",
                                                 "jit(g)/adamw"))))]),
    ])
    f7, f9, c = ("%fusion.1 = f32[] fusion()", "%fusion.1 = f32[2] fusion()",
                 "%copy.2 = f32[] copy()")
    dev = _plane("/device:TPU:0", {3: "program_id"}, [
        (1, f7, [_msg((1, 3), (3, 7))]), (2, f9, [_msg((1, 3), (3, 9))]),
        (3, c, [_msg((1, 3), (3, 7))])])
    names = scopes.op_names(_msg((1, meta), (1, dev)))
    assert names == {"/device:TPU:0": {f7: "jit(f)/head",
                                       f9: "jit(g)/adamw"}}


def test_declared_scope_in_a_trace(tmp_path):
    """A written trace of one Mamba layer whose SSD kernel has a named
    scope: declared, the kernel's self time is its own and the block's is
    the rest; undeclared, the block holds both, as before.  The scopes
    sum to busy either way."""
    fusions = [("fusion.1", "jit(f)/jvp(mamba)/in_proj/dot_general"),
               ("fusion.2", SSD), ("fusion.3", "jit(f)/jvp()/while")]
    meta = _plane("/host:metadata", {1: "Hlo Proto"}, [
        (7, "jit_f(7)", [_msg((1, 1), (6, _hlo(*fusions)))])])
    host = _plane("/host:CPU", {}, [(1, scopes.WINDOW, [])],
                  [("python", [(1, 0, 20)])])
    dev = _plane("/device:TPU:0", {3: "program_id"},
                 [(i, f"%{n} = f32[] fusion()", [_msg((1, 3), (3, 7))])
                  for i, (n, _) in enumerate(fusions, start=1)],
                 [(trace.OPS_LINE, [(1, 1, 4), (2, 5, 6), (3, 12, 2)])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, meta), (1, host), (1, dev)))
    plain = scopes.read(path)
    declared = scopes.read(path, scopes=scopes.scope_set(
        {"scopes": ["mamba2_ssd"]}))
    assert plain == pytest.approx({"mamba": 0.010, "unscoped": 0.002})
    assert declared == pytest.approx({"mamba": 0.004, "mamba2_ssd": 0.006,
                                      "unscoped": 0.002})
    busy = trace.reduce(*trace.read(path), window=scopes.WINDOW)["busy_s"]
    assert sum(declared.values()) == pytest.approx(busy)


def test_recorded_trace_per_scope():
    """A program with no named scope: every op is unscoped, and the one
    scope holds the whole busy time of the window."""
    by = scopes.read(RECORDED)
    s, o = trace.read(RECORDED)
    busy = trace.reduce(s, o, window="chipbench.traced")["busy_s"]
    assert set(by) == {"unscoped"}
    assert by["unscoped"] == pytest.approx(busy) and busy > 0


def test_scopes_of_a_compiled_xlstm_step():
    """The train step of a two-layer xLSTM (mLSTM then sLSTM) compiled on
    the CPU: every scope the per-layer metrics read names ops of the
    optimized HLO, the backward pass's among them."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.lm import LM
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.trainstep import abstract_train_state, make_train_step

    arch = get_config("xlstm_125m").reduced(
        n_layers=2, d_model=64, vocab=128, block_pattern=("mlstm", "slstm"))
    model = LM(arch)
    step = make_train_step(model, AdamWConfig(), remat="full")
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = jax.jit(step).lower(abstract_train_state(model),
                               {"tokens": tokens, "labels": tokens}
                               ).compile().as_text()
    names = [m.group(2) for m in map(INSTRUCTION.match, text.splitlines())
             if m]
    wanted = {"mlstm", "mlstm_cell", "slstm", "slstm_scan", "head", "adamw"}
    assert wanted <= {scopes.scope_of(n) for n in names}
    backward = {scopes.scope_of(n) for n in names if "transpose(" in n}
    assert wanted - {"adamw"} <= backward


def test_scope_readers(tmp_path, monkeypatch):
    """Per traced step, in ms; None in an untraced run and where no op of
    the scope ran."""
    from chipbench import cell, metrics

    run = cell.RunRecord(chips=1, flops_per_token=1.0, peak_flops=1.0,
                         hbm_bytes_per_s=1.0, config={}, seq_len=1)
    run.chunks = [{"kind": "steady", "steps": 2, "traced": True},
                  {"kind": "steady", "steps": 7, "traced": False}]
    assert metrics.read("unscoped_ms", run) is None
    monkeypatch.setattr(cell, "RUN_DIR", tmp_path)
    xplane = tmp_path / "trace" / "host.xplane.pb"
    xplane.parent.mkdir()
    xplane.write_bytes(trace.find_xplane(RECORDED).read_bytes())
    run.trace = {"busy_s": 1.0, "window_s": 1.0}
    busy = scopes.read(RECORDED)["unscoped"]
    assert metrics.read("unscoped_ms", run) == pytest.approx(500.0 * busy)
    for name in ("mlstm_proj_ms", "mlstm_cell_ms", "slstm_proj_ms",
                 "slstm_scan_ms", "head_ms", "adamw_ms"):
        assert metrics.read(name, run) is None
