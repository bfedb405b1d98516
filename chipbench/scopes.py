"""Device self time per named scope of the program, from a profiler trace.

Each operation on a device (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) has a layer: the innermost of the program's
``jax.named_scope`` names on its ``op_name`` (``SCOPES``, and those that
the configuration's file lists under ``"scopes"``: ``scope_set``), read
through the wrappers that differentiation and recomputation put around a
scope, so that the forward pass, its recomputation and the backward pass
all count under it.  An operation under none of them is ``UNSCOPED``.  Self times
are taken as in ``chipbench/trace.py`` (a loop keeps what its body leaves,
and counts under the scope it was built in), inside the host span named
``window``, averaged over the devices: the scopes sum to its ``busy_s``.

The ``op_name`` comes from the program's optimized HLO, which the
profiler keeps in the trace (``Hlo Proto`` in the ``/host:metadata``
plane, one per program); each operation's event metadata names its
program (``program_id``) and its instruction.  ``jax.profiler.ProfileData``
exposes neither, so they are decoded here from the protobuf wire format of
the ``.xplane.pb`` (``XSpace``).

    python -m chipbench.scopes <dir or .xplane.pb>  # self seconds per scope
"""

from __future__ import annotations

import functools
import json
import sys
from collections import defaultdict

from chipbench import trace as tracing

# the program's jax.named_scope names: one per block kind, and the parts
# inside a block or around it that the per-layer metrics read; a
# configuration adds its kernels' scopes in its own file
SCOPES = ("attn", "cross_attn", "shared_attn", "mamba", "mlstm", "slstm",
          "mlstm_cell", "slstm_scan", "head", "adamw")
UNSCOPED = "unscoped"
WRAPPERS = ("jvp(", "transpose(", "checkpoint(", "remat(")
WINDOW = "chipbench.traced"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = b"Hlo Proto"
PROGRAM_ID_STAT = b"program_id"


def scope_set(config: dict) -> tuple:
    """``SCOPES`` and the scopes a configuration (its file, as run) lists
    under ``"scopes"``: the named scopes of its own kernels."""
    return SCOPES + tuple(config.get("scopes", ()))


def scope_of(op_name: str, scopes: tuple = SCOPES) -> str:
    """The innermost of ``scopes`` on an ``op_name`` path, or
    ``UNSCOPED``: ``jit(f)/transpose(jvp())/while/body/mlstm/mlstm_cell/
    while/body/exp`` -> ``mlstm_cell``, ``jit(f)/transpose(jvp(head))/dot``
    -> ``head``."""
    found = UNSCOPED
    for part in op_name.split("/"):
        while part.startswith(WRAPPERS) and part.endswith(")"):
            part = part[part.index("(") + 1:-1]
        if part in scopes:
            found = part
    return found


# -- the protobuf wire format, as far as XSpace's metadata needs it ----------

def _varint(buf, i):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of a message: an int for varints and fixed
    widths, a memoryview for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _map_values(entries):
    """The values (field 2) of a protobuf map's entries."""
    return [v for e in entries for f, v in _fields(e) if f == 2]


def _stat_id(parts, name: bytes):
    """The id of the plane's stat named ``name`` (XStatMetadata: id 1,
    name 2), or None."""
    for sm in _map_values(parts[5]):
        d = dict(_fields(sm))
        if bytes(d.get(2, b"")) == name:
            return d.get(1, 0)
    return None


def _stats(event_metadata, stat_id) -> list:
    """The values of an XEventMetadata's stats (field 5) whose
    metadata_id is ``stat_id``, each as {field: value} (XStat: uint64
    3, bytes 6)."""
    return [d for g, v in _fields(event_metadata) if g == 5
            for d in [dict(_fields(v))] if d.get(1, 0) == stat_id]


def _module_op_names(hlo_proto) -> dict:
    """Instruction name -> ``op_name`` of an ``HloProto``'s module
    (HloProto: hlo_module 1; HloModuleProto: computations 3;
    HloComputationProto: instructions 2; HloInstructionProto: name 1,
    metadata 7; OpMetadata: op_name 2)."""
    out = {}
    module = dict(_fields(hlo_proto)).get(1, b"")
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g != 2:
                continue
            d = dict(_fields(ins))
            op = dict(_fields(d.get(7, b""))).get(2)
            if op:
                out[bytes(d[1]).decode()] = bytes(op).decode()
    return out


def op_names(xspace: bytes) -> dict:
    """{device plane: {event name: op_name}} of every device operation
    whose HLO instruction carries an ``op_name``.  The profiler keeps each
    program's ``HloProto`` in the ``/host:metadata`` plane under the
    program's id, and gives each device operation's event metadata the
    ``program_id`` it ran in (XSpace: planes 1; XPlane: name 2,
    event_metadata 4, stat_metadata 5; XEventMetadata: id 1, name 2)."""
    planes = []
    for f, plane in _fields(memoryview(xspace)):
        if f == 1:
            parts = defaultdict(list)
            for g, v in _fields(plane):
                if g in (2, 4, 5):
                    parts[g].append(v)
            planes.append((bytes(parts[2][0]).decode() if parts[2] else "",
                           parts))
    programs = {}
    for name, parts in planes:
        key = _stat_id(parts, HLO_PROTO_STAT)
        if name == METADATA_PLANE and key is not None:
            for em in _map_values(parts[4]):
                for st in _stats(em, key):
                    programs[dict(_fields(em)).get(1)] = \
                        _module_op_names(st.get(6, b""))
    out = {}
    for name, parts in planes:
        if not name.startswith(tracing.DEVICE_PREFIX):
            continue
        found = out.setdefault(name, {})
        key = _stat_id(parts, PROGRAM_ID_STAT)
        for em in _map_values(parts[4]) if key is not None else ():
            for st in _stats(em, key):
                ev_name = bytes(dict(_fields(em)).get(2, b"")).decode()
                op = programs.get(st.get(3), {}).get(tracing.op_name(ev_name))
                if op:
                    found[ev_name] = op
    return out


# -- per-scope self time -----------------------------------------------------

def scope_times(spans, ops, *, window: str = WINDOW) -> dict:
    """Self seconds per scope inside the host span ``window``, averaged
    over the devices, of ``ops`` as {device plane: [(scope, start, end)]}
    in nanoseconds; ``spans`` as ``chipbench.trace.read`` gives them.
    ``UNSCOPED`` is always a key; another scope is one only where one of
    its operations ran in the window."""
    win = [s for s in spans if s[0] == window]
    if not win:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = win[0][1], win[0][2]
    out = defaultdict(float, {UNSCOPED: 0.0})
    for evs in ops.values():
        clipped = [(scope, max(a, w0), min(b, w1)) for scope, a, b in evs
                   if b > w0 and a < w1]
        for scope, t in tracing.self_times(clipped):
            out[scope] += t * 1e-9
    n = max(len(ops), 1)
    return {k: v / n for k, v in out.items()}


def read(path, *, window: str = WINDOW, scopes: tuple = SCOPES) -> dict:
    """Self seconds per scope of ``scopes`` (``scope_times``) of a
    trace."""
    from jax.profiler import ProfileData
    xplane = tracing.find_xplane(path)
    names = op_names(xplane.read_bytes())
    pd = ProfileData.from_file(str(xplane))
    spans, ops = [], {}
    for plane in pd.planes:
        if plane.name.startswith(tracing.DEVICE_PREFIX):
            found = {k: scope_of(v, scopes)
                     for k, v in names.get(plane.name, {}).items()}
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    evs.extend((found.get(e.name, UNSCOPED), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events)
    return scope_times(spans, ops, window=window)


@functools.lru_cache(maxsize=1)
def _read_once(path: str, mtime_ns: int, scopes: tuple) -> dict:
    return read(path, scopes=scopes)


def per_step_ms(run, scope: str):
    """Device self milliseconds per traced step of ``scope`` in a finished
    run (:class:`chipbench.cell.RunRecord`), read from the trace the run
    left, over the scope set of the run's configuration (``scope_set``);
    None in an untraced run or where no operation of ``scope`` ran."""
    if run.trace is None:
        return None
    from chipbench.cell import RUN_DIR
    xplane = tracing.find_xplane(RUN_DIR / "trace")
    seconds = _read_once(str(xplane), xplane.stat().st_mtime_ns,
                         scope_set(run.config))
    steps = sum(c["steps"] for c in run.chunks if c["traced"])
    if scope not in seconds or not steps:
        return None
    return 1000.0 * seconds[scope] / steps


if __name__ == "__main__":
    print(json.dumps(read(sys.argv[1], window=sys.argv[2] if len(sys.argv) > 2
                          else WINDOW), indent=1))
