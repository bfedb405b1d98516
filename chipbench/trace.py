"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside the
traced window, averaged over the devices.  An operation is named by its HLO
instruction (``fusion.12``, ``while.573``) and credited with its self time:
a loop's line also holds the operations of its body, inside the loop's own
interval, and those are subtracted from it.  The window is the host span
named ``window`` (a ``jax.profiler.TraceAnnotation`` of the harness).
Each idle gap is named by the innermost other harness span (a name that
starts with ``prefix``) that holds the gap's midpoint: what the host was
doing while the device waited.

    python chipbench/trace.py <dir or .xplane.pb>    # prints the reduction
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10


def find_xplane(path: str | Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def self_times(events):
    """(name, self seconds) of nested (name, start, end) intervals: each
    interval less the intervals directly inside it."""
    out, stack = [], []            # stack of [name, start, end, child time]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            n, a0, b0, kids = stack.pop()
            out.append((n, b0 - a0 - kids))
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0])
    out.extend((n, b0 - a0 - kids) for n, a0, b0, kids in stack)
    return out


def read(path):
    """(host spans, device op events) of a trace, in nanoseconds:
    spans as (name, start, end); ops as {device plane: [(name, start,
    end)]}."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(find_xplane(path)))
    spans, ops = [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events)
    return spans, ops


def reduce(spans, ops, *, window: str, prefix: str = "chipbench.") -> dict:
    """busy_s, window_s, the top device ops and the longest idle gaps."""
    win = [s for s in spans if s[0] == window]
    if not win:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = win[0][1], win[0][2]
    mine = [s for s in spans if s[0].startswith(prefix) and s[0] != window]
    busy, per_op, gaps = [], defaultdict(float), []
    for evs in ops.values():
        clipped = [(name, max(a, w0), min(b, w1)) for name, a, b in evs
                   if b > w0 and a < w1]
        for name, t in self_times(clipped):
            per_op[name] += t * 1e-9
        merged = _union((a, b) for _, a, b in clipped)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [t for ab in merged for t in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    n = max(len(ops), 1)
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        holders = [s for s in mine if s[1] <= mid <= s[2]]
        name = min(holders, key=lambda s: s[2] - s[1])[0] if holders \
            else "outside the harness's spans"
        named.append([name, (b - a) * 1e-9])
    named.sort(key=lambda g: -g[1])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / n, "window_s": (w1 - w0) * 1e-9,
            "devices": len(ops),
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": named[:TOP]}


if __name__ == "__main__":
    s, o = read(sys.argv[1])
    print(json.dumps(reduce(s, o, window=sys.argv[2] if len(sys.argv) > 2
                            else "chipbench.traced"), indent=1))
