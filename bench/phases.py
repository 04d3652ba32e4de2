"""The program's own names in a profiler trace: the named scopes of an
outer round's phases on the device ops, and the program's host spans.

`repro.core.pscope` wraps each phase of an outer round in a
`jax.named_scope` (`pscope.anchor_grad`, `pscope.plan`, `pscope.gather`,
`pscope.average`, `pscope.objective`); XLA keeps the scope path in each
op's `op_name` metadata, and the profiler writes it beside the op's
event.  `repro.obs` spans (`solve.prepare`, `mesh.shards`, ...) enter a
`jax.profiler.TraceAnnotation` of their name, so they are host events
of the same trace, on the device's clock.

`load` reads an `.xplane.pb` as `tracereduce.load` does, and keeps each
device op's scope path as a fifth field; `summarize` reduces it, inside
the traced window, to:

  * per chip, `scope_ns`: for each phase the union of its ops' time,
    less the kernels' and the collectives' (an op goes to the innermost
    `pscope.*` scope of its path);
  * `spans`: for each program span, its count and summed time, and
    `solves`: the number of `solve.<solver>` spans (those inside no
    other `solve.*` span);
  * `idle_by_span`: the idle time of the busiest chip, summed by the
    innermost program span that covers it.

`traced_window` gives the per-layer readers of `bench/metrics/` the
summary of the trace that `bench/run.py --trace 1` has just written,
reduced once per run.  A trace of a program without the scopes or the
spans gives zeros and empty tables, and the readers then report
nothing.
"""
from __future__ import annotations

import collections
import functools
import os
import re
from pathlib import Path

import tracereduce

SCOPES = ("pscope.anchor_grad", "pscope.plan", "pscope.gather",
          "pscope.average", "pscope.objective")
_SCOPE = re.compile(r"pscope\.[A-Za-z_]+")
# the program's host spans: dotted names under its layers
# (docs/observability.md); JAX's own host events are named otherwise
PROGRAM_SPAN = re.compile(
    r"(solve|mesh|ingest|partition|elastic)\.[A-Za-z0-9_.]+")
# the stat of an XLA op's event metadata that carries its HLO `op_name`
SCOPE_STAT = "tf_op"
NO_SPAN = "no program span"
# where `bench/run.py --trace 1` writes its trace, and its window
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_out" / "trace"
WINDOW = "bench_window"


def scope_of(path: str) -> str:
    """The innermost `pscope.*` scope of an op_name path, or ""."""
    found = _SCOPE.findall(path or "")
    return found[-1] if found else ""


def _varint(buf, i: int):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of a protobuf message: an int for a varint,
    a memoryview for the other wire types."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in an .xplane.pb")
        yield key >> 3, value


def _str(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _ids(value) -> list:
    """A repeated int64 field's values, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def _plane(plane):
    """(name, [XEventMetadata], {stat id: stat name}) of an XPlane:
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps:
    key 1, value 2); XStatMetadata.name = 2."""
    name, events, stat_names = "", [], {}
    for field, value in _fields(plane):
        if field == 2:
            name = _str(value)
        elif field == 4:
            events.append(dict(_fields(value)).get(2, b""))
        elif field == 5:
            meta = dict(_fields(dict(_fields(value)).get(2, b"")))
            stat_names[meta.get(1)] = _str(meta.get(2, b""))
    return name, events, stat_names


def _event(entry, stat_names):
    """(name, display name, {stat name: value}) of an XEventMetadata:
    .name = 2, .display_name = 4, .stats = 5; XStat.metadata_id = 1,
    .uint64_value = 3, .int64_value = 4, .str_value = 5,
    .bytes_value = 6, .ref_value = 7 (a stat name that holds the
    string)."""
    name, display, stats = "", "", {}
    for field, value in _fields(entry):
        if field == 2:
            name = _str(value)
        elif field == 4:
            display = _str(value)
        elif field == 5:
            stat = dict(_fields(value))
            key = stat_names.get(stat.get(1))
            if 7 in stat:
                stats[key] = stat_names.get(stat[7], "")
            elif 5 in stat:
                stats[key] = _str(stat[5])
            else:
                stats[key] = stat.get(6, stat.get(3, stat.get(4)))
    return name, display, stats


def _fused_op_names(hlo_proto) -> dict:
    """{fusion: op_name} of an HloProto's fusions that carry no phase of
    their own, each given the op_name of the instructions it fuses with
    the commonest phase.  (The TPU compiler builds a scatter into a
    fusion of no op_name; the reshapes and adds fused with it keep
    theirs.)  HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2, .id = 5;
    HloInstructionProto.name = 1, .opcode = 2, .metadata = 7
    (OpMetadata.op_name = 2), .called_computation_ids = 38."""
    module = dict(_fields(hlo_proto)).get(1, b"")
    comps = {}
    for field, comp in _fields(module):
        if field != 3:
            continue
        cid, body = None, []
        for cf, value in _fields(comp):
            if cf == 5:
                cid = value
            elif cf == 2:
                ins = {"name": "", "opcode": "", "op_name": "", "called": []}
                for inf, v in _fields(value):
                    if inf == 1:
                        ins["name"] = _str(v)
                    elif inf == 2:
                        ins["opcode"] = _str(v)
                    elif inf == 7:
                        ins["op_name"] = _str(dict(_fields(v)).get(2, b""))
                    elif inf == 38:
                        ins["called"] += _ids(v)
                body.append(ins)
        comps[cid] = body

    def nested(cid, seen):
        if cid in seen or cid not in comps:
            return []
        seen.add(cid)
        out = []
        for ins in comps[cid]:
            if scope_of(ins["op_name"]):
                out.append(ins["op_name"])
            for c in ins["called"]:
                out += nested(c, seen)
        return out

    table = {}
    for body in comps.values():
        for ins in body:
            if ins["opcode"] == "fusion" and not scope_of(ins["op_name"]):
                paths = nested(ins["called"][0], set())
                if paths:
                    counts = collections.Counter(scope_of(x) for x in paths)
                    top = counts.most_common(1)[0][0]
                    table[ins["name"]] = next(x for x in paths
                                              if scope_of(x) == top)
    return table


def op_names(path) -> dict:
    """{chip plane: {op event name: op_name path}} from an .xplane.pb.

    The profiler keeps an op's `op_name` as the `tf_op` stat of the
    op's event metadata, which `jax.profiler.ProfileData` does not
    expose (it gives an event's own stats), so the planes' metadata are
    read from the protobuf (XSpace.planes = 1).  A fusion with no phase
    in its own op_name takes that of the instructions it fuses, read
    from its program's HloProto, which the `/host:metadata` plane keeps
    as the "Hlo Proto" stat of an event named `<module>(<program id>)`.
    """
    with open(path, "rb") as f:
        space = memoryview(f.read())
    chips, protos = {}, {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = _plane(plane)
        if name == "/host:metadata":
            for entry in events:
                ev, _, stats = _event(entry, stat_names)
                m = re.search(r"\((\d+)\)$", ev)
                if m and stats.get("Hlo Proto") is not None:
                    protos[int(m.group(1))] = stats["Hlo Proto"]
        elif tracereduce.CHIP_PLANE.fullmatch(name):
            chips[name] = [_event(entry, stat_names) for entry in events]
    fused = {}
    out = {}
    for plane, events in chips.items():
        table = out[plane] = {}
        for ev, display, stats in events:
            path_ = stats.get(SCOPE_STAT) or ""
            program = stats.get("program_id")
            if not scope_of(path_) and program in protos:
                if program not in fused:
                    fused[program] = _fused_op_names(protos[program])
                path_ = fused[program].get(display, path_)
            if path_:
                table[ev] = str(path_)
    return out


def load(path) -> dict:
    """{"devices": {plane: [[name, start_ns, end_ns, opcode, scope path],
    ...]}, "host": [[name, start_ns, end_ns], ...]} from an .xplane.pb."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    names = op_names(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = tracereduce.CHIP_PLANE.fullmatch(plane.name)
        if m:
            ops = []
            scopes = names.get(plane.name, {})
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    name, opcode = tracereduce.parse_op(e.name)
                    ops.append([name, float(e.start_ns), float(e.end_ns),
                                opcode, scopes.get(e.name, "")])
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


def _excluded(name: str, opcode: str) -> bool:
    """A kernel (a custom call) or a cross-chip collective."""
    return (opcode == "custom-call"
            or bool(tracereduce.COLLECTIVE.match(name))
            or bool(tracereduce.COLLECTIVE.match(opcode)))


def scope_ns(ops) -> dict:
    """{scope: ns} of one chip's ops: the union of each phase's ops less
    the union of the kernels and the collectives."""
    out = [tuple(x) for x in tracereduce.union(
        [(o[1], o[2]) for o in ops if _excluded(o[0], o[3])])]
    out_ns = tracereduce._length(out)
    result = {}
    for scope in SCOPES:
        mine = [(o[1], o[2]) for o in ops
                if len(o) > 4 and scope_of(o[4]) == scope]
        result[scope] = (tracereduce._length(tracereduce.union(mine + out))
                         - out_ns)
    return result


def program_spans(host, lo: float, hi: float):
    """The program's host spans that overlap the window, clipped to it."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in host
            if PROGRAM_SPAN.fullmatch(n) and e > lo and s < hi]


def span_table(spans) -> dict:
    """{name: {"count", "ns"}} of clipped program spans."""
    table = {}
    for n, s, e in spans:
        row = table.setdefault(n, {"count": 0, "ns": 0.0})
        row["count"] += 1
        row["ns"] += e - s
    return table


def idle_by_span(busy, lo: float, hi: float, spans) -> list:
    """[[span, seconds], ...], longest first: the window's idle time (no
    device op) summed by the innermost program span that covers it."""
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    cuts = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e)})
    segments = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(e - s, n) for n, s, e in spans if s <= mid < e]
        segments.append((a, b, min(cover)[1] if cover else NO_SPAN))
    total, i = {}, 0
    for s, e in gaps:
        while i < len(segments) and segments[i][1] <= s:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < e:
            a, b, name = segments[j]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                total[name] = total.get(name, 0.0) + overlap
            j += 1
    return [[n, ns / 1e9] for n, ns in sorted(total.items(),
                                               key=lambda kv: -kv[1])]


def summarize(trace: dict, window: str = WINDOW) -> dict:
    """{"chips": {plane: {"scope_ns": {...}}}, "spans": {...},
    "idle_by_span": [...]} inside the window (see the module doc)."""
    lo, hi = tracereduce.window_of(trace, window)
    spans = program_spans(trace["host"], lo, hi)
    chips, top = {}, None
    for plane, ops in trace["devices"].items():
        ops = [[o[0], max(o[1], lo), min(o[2], hi), *o[3:]] for o in ops
               if o[2] > lo and o[1] < hi]
        busy = tracereduce.union([(o[1], o[2]) for o in ops])
        chips[plane] = {"scope_ns": scope_ns(ops)}
        length = tracereduce._length(busy)
        if top is None or length > top[0]:
            top = (length, busy)
    if top is None:
        raise ValueError("the trace holds no device plane")
    return {"chips": chips, "spans": span_table(spans),
            "solves": _outermost(spans, "solve."),
            "idle_by_span": idle_by_span(top[1], lo, hi, spans)}


def _outermost(spans, prefix: str) -> int:
    """The number of spans named `prefix...` inside no other such span."""
    mine = [x for x in spans if x[0].startswith(prefix)]
    return sum(not any(o is not x and o[1] <= x[1] and x[2] <= o[2]
                       for o in mine) for x in mine)


@functools.lru_cache(maxsize=4)
def _summary_of(path: str, mtime_ns: int) -> dict:
    return summarize(load(path))


def traced_window() -> dict:
    """The summary of the trace of the run in progress."""
    path = tracereduce.find_xplane(TRACE_DIR)
    return _summary_of(path, os.stat(path).st_mtime_ns)


def ms_per_round(ctx: dict, scope: str):
    """A phase's device time per outer round on the reader's chip."""
    chip = traced_window()["chips"].get(ctx["chip"]["plane"], {})
    ns = chip.get("scope_ns", {}).get(scope)
    return ns / ctx["rounds"] / 1e6 if ns else None


# host spans of a solve's fixed work before its trajectory is dispatched:
# the CSR view and shard statics, the shards' placement on the mesh
PREPARE = ("solve.prepare", "mesh.shards", "mesh.prepare")


def prepare_ms_per_solve():
    summary = traced_window()
    ns = sum(summary["spans"].get(n, {}).get("ns", 0.0) for n in PREPARE)
    return ns / summary["solves"] / 1e6 if ns and summary["solves"] else None
