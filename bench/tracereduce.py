"""From a profiler trace to the numbers the per-layer metrics read.

`load` reads an `.xplane.pb` into plain lists: for every chip the
events of its "XLA Ops" line as (op name, start_ns, end_ns, opcode),
and the host's events as (name, start_ns, end_ns).  On a TPU an XLA op
event is named by its HLO text ("%fusion.3 = f32[8]{0} fusion(...)");
`load` keeps the op's name and opcode.  Control-flow ops (`while`)
enclose the ops of their bodies on the same line.

`summarize` reduces that to, per chip and inside the traced window:
busy time (the union of op intervals), the epoch kernel's time and the
collectives' time (unions of their events), the rest of busy time, and
the window's length; and a `breakdown` of the ops with the most self
time (their time less that of the ops they enclose) and of the longest
idle gaps, each named by the host event that covered it.  `load` is the
only part that touches the trace format, so the reduction is tested on
a small recorded chip trace kept as JSON.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from pathlib import Path

# opcodes and op name prefixes of cross-chip collectives
COLLECTIVE = re.compile(r"(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")
# a chip's plane; the profiler adds others ("/device:CUSTOM:...")
CHIP_PLANE = re.compile(r"/device:(?!CPU)[A-Z]+:(\d+)")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
TOP = 10


def find_xplane(trace_dir) -> str:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def parse_op(text: str):
    """(name, opcode) of an XLA op event named by its HLO text."""
    name, sep, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest) if sep else None
    return name.lstrip("%"), (m.group(1) if m else "")


def load(path) -> dict:
    """{"devices": {plane: [[name, start_ns, end_ns, opcode], ...]},
    "host": [[name, start_ns, end_ns], ...]} from an .xplane.pb."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        m = CHIP_PLANE.fullmatch(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    name, opcode = parse_op(e.name)
                    ops.append([name, float(e.start_ns), float(e.end_ns),
                                opcode])
            devices[int(m.group(1))] = (plane.name,
                                        sorted(ops, key=lambda o: o[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append([e.name, float(e.start_ns),
                                     float(e.end_ns)])
    return {"devices": dict(devices[i] for i in sorted(devices)),
            "host": host}


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _clip(ops, lo: float, hi: float):
    return [[n, max(s, lo), min(e, hi), c] for n, s, e, c in ops
            if e > lo and s < hi]


def window_of(trace: dict, window: str):
    """(start, end) of the host annotation named `window`; where the
    trace holds none, the span of all device ops."""
    marks = [(s, e) for n, s, e in trace["host"] if n == window]
    if marks:
        return min(s for s, _ in marks), max(e for _, e in marks)
    ops = [o for ops in trace["devices"].values() for o in ops]
    return min(o[1] for o in ops), max(o[2] for o in ops)


def summarize(trace: dict, window: str, kernel: str) -> dict:
    """Per-chip times inside the window and the breakdown of the chip
    with the most busy time.  `kernel` is a substring of the epoch
    kernel's op name or category."""
    lo, hi = window_of(trace, window)
    chips = []
    for plane, ops in trace["devices"].items():
        ops = _clip(ops, lo, hi)
        kern = union([(s, e) for n, s, e, _ in ops if kernel in n])
        coll = union([(s, e) for n, s, e, c in ops if kernel not in n
                      and (COLLECTIVE.match(n) or COLLECTIVE.match(c))])
        busy = union([(s, e) for _, s, e, _ in ops])
        chips.append({"plane": plane, "busy_ns": _length(busy),
                      "kernel_ns": _length(kern),
                      "collective_ns": _length(coll),
                      "other_ns": _length(busy) - _length(union(kern + coll)),
                      "window_ns": hi - lo, "_ops": ops, "_busy": busy})
    if not chips:
        raise ValueError("the trace holds no device plane")
    top = max(chips, key=lambda c: c["busy_ns"])
    breakdown = {"device_ops": _top_ops(top["_ops"]),
                 "idle_gaps": _idle_gaps(top["_busy"], lo, hi,
                                         trace["host"], window)}
    for c in chips:
        del c["_ops"], c["_busy"]
    return {"chips": chips, "breakdown": breakdown}


def self_times(ops):
    """{name: summed self time}: each op's time less that of the ops it
    encloses (ops on one line nest or are disjoint)."""
    total, stack = {}, []
    for n, s, e, c in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] < e:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            total[parent] = total.get(parent, 0.0) - (e - s)
        label = f"{n} {c}".strip()
        total[label] = total.get(label, 0.0) + (e - s)
        stack.append((label, s, e))
    return total


def _top_ops(ops):
    ranked = sorted(self_times(ops).items(), key=lambda kv: -kv[1])[:TOP]
    return [[n, ns / 1e9] for n, ns in ranked]


def _idle_gaps(busy, lo: float, hi: float, host, window: str):
    """The longest stretches of the window with no device op, each named
    by the innermost host event that covers at least half of it, or,
    where none does, by the one that covers most of it."""
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    out = []
    for s, e in gaps:
        best, key = "no host event", (False, 0.0)
        for n, hs, he in host:
            cover = min(e, he) - max(s, hs)
            if n == window or cover <= 0:
                continue
            half = cover >= 0.5 * (e - s)
            k = (half, -(he - hs) if half else cover)
            if k > key:
                best, key = n, k
        out.append([best, (e - s) / 1e9])
    return out


def save(trace: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read_saved(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
