"""In-process span/counter telemetry with Chrome-trace export.

The zero-sync solvers admit no per-round host instrumentation — a whole
trajectory is ONE compiled scan — so the observable structure of a run
lives at the host level: ingest phases, partition builds, shard
registration, compiled-solve calls, re-mesh barriers, elastic recovery
events.  This module records exactly that as spans (`ph: "X"` complete
events), counters (`ph: "C"`) and instants (`ph: "i"`) in the Chrome
trace-event format, so one run renders as a timeline in Perfetto /
`chrome://tracing`.

Design constraints, in order:

  * **Zero-sync compatible.**  Nothing here ever touches device state
    or forces a transfer; a span is two `perf_counter` reads and one
    locked list append.  The device-side per-round counters
    (`core.pscope.run_scanned(counters=True)`) ride the existing scan
    carry and arrive in the SAME single host transfer as the
    value/NNZ history — this module only receives them post-hoc.
  * **Thread-safe.**  The elastic driver records from background
    builder threads; a single lock guards the event list and thread
    ids map to stable `tid`s.
  * **Multi-process mergeable.**  Timestamps are `perf_counter`-based
    (monotonic, per-process).  Each collector remembers the unix time
    of its perf_counter zero (`unix_offset_s`), so per-rank spool
    files merge into one clock-aligned timeline (`merge_spools`):
    every event's `pid` becomes its rank and all clocks rebase to the
    earliest rank's first event.

The module is stdlib-only and never imports jax: importing it costs
nothing, and the recording path stays cheap enough to leave on always
(events are bounded by `MAX_EVENTS`; overflow increments a drop
counter instead of growing without bound).  A span opened in a process
that has imported jax also enters `jax.profiler.TraceAnnotation`: with
no profiler active that costs about a microsecond, and under
`jax.profiler.trace` the span appears by name in the `.xplane.pb`,
on the clock of the device's ops.
"""
from __future__ import annotations

import glob as _glob
import json
import os
import sys
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Union

SPOOL_SCHEMA = "repro-obs-spool/v1"
MAX_EVENTS = 200_000


class Span:
    """One open span; a context manager emitting a `ph: "X"` event.

    Exposes `t0` (perf_counter seconds at entry) so callers can stamp
    derived events — e.g. per-round counter series linearly attributed
    inside a compiled-solve span — onto the same clock.

    Where jax is already imported, the span also opens a
    `jax.profiler.TraceAnnotation` of its name, so that a profiler
    trace holds it on the device's clock; its args stay here.
    """

    __slots__ = ("_col", "name", "args", "t0", "_annotation")

    def __init__(self, col: "Collector", name: str, args: Dict[str, Any]):
        self._col = col
        self.name = name
        self.args = args
        self.t0 = 0.0
        self._annotation = None

    def __enter__(self) -> "Span":
        jax = sys.modules.get("jax")
        if jax is not None:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        args = dict(self.args)
        if exc_type is not None:
            args["error"] = exc_type.__name__
        self._col._add({
            "ph": "X", "name": self.name, "cat": self.name.split(".")[0],
            "ts": self.t0 * 1e6, "dur": (t1 - self.t0) * 1e6,
            "args": args,
        })


class Collector:
    """Thread-safe in-process trace-event collector."""

    def __init__(self, rank: int = 0, process_name: Optional[str] = None):
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._tids: Dict[int, int] = {}
        self.rank = int(rank)
        self.process_name = process_name
        self.dropped = 0
        # unix wall-clock time of this process's perf_counter zero:
        # the clock-alignment key for cross-rank merges
        self.unix_offset_s = time.time() - time.perf_counter()

    # -- recording --------------------------------------------------------

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids)
        return tid

    def _add(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= MAX_EVENTS:
                self.dropped += 1
                return
            ev.setdefault("tid", self._tid())
            self._events.append(ev)

    def span(self, name: str, **args: Any) -> Span:
        return Span(self, name, args)

    def counter(self, name: str, value: float,
                ts_s: Optional[float] = None) -> None:
        """One sample of a counter series (`ph: "C"`).  `ts_s` is an
        explicit perf_counter-based timestamp in seconds; default now."""
        ts = (time.perf_counter() if ts_s is None else float(ts_s)) * 1e6
        self._add({"ph": "C", "name": name, "cat": "counter", "ts": ts,
                   "args": {name: float(value)}})

    def instant(self, name: str, ts_s: Optional[float] = None,
                **args: Any) -> None:
        """A zero-duration marker (`ph: "i"`, global scope)."""
        ts = (time.perf_counter() if ts_s is None else float(ts_s)) * 1e6
        self._add({"ph": "i", "s": "g", "name": name,
                   "cat": name.split(".")[0], "ts": ts, "args": args})

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    # -- export -----------------------------------------------------------

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def _metadata(self, pid: int) -> List[Dict[str, Any]]:
        name = self.process_name or f"rank {self.rank}"
        meta = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                 "ts": 0, "args": {"name": name}}]
        for ident, tid in sorted(self._tids.items(), key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                         "tid": tid, "ts": 0,
                         "args": {"name": "main" if tid == 0
                                  else f"thread-{tid}"}})
        return meta

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The single-process timeline as a Chrome trace-event document.

        Timestamps rebase to the first event so the viewer opens at
        t=0; every event carries `pid = rank`.
        """
        evs = self.events()
        base = min((e["ts"] for e in evs), default=0.0)
        out = []
        for e in evs:
            e = dict(e)
            e["ts"] = e["ts"] - base
            e["pid"] = self.rank
            out.append(e)
        return {"traceEvents": self._metadata(self.rank) + out,
                "displayTimeUnit": "ms",
                "metadata": {"rank": self.rank, "dropped": self.dropped}}

    def write(self, path: Union[str, os.PathLike]) -> str:
        """Write the Chrome-trace JSON (loadable in Perfetto)."""
        path = os.fspath(path)
        _ensure_dir(path)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
            f.write("\n")
        return path

    def write_spool(self, path: Union[str, os.PathLike]) -> str:
        """Write this rank's raw event spool for a later cross-rank
        merge (`merge_spools`).  Unlike `write`, timestamps stay on the
        local perf_counter clock; `unix_offset_s` carries the alignment
        key."""
        path = os.fspath(path)
        _ensure_dir(path)
        doc = {"schema": SPOOL_SCHEMA, "rank": self.rank,
               "process_name": self.process_name or f"rank {self.rank}",
               "unix_offset_s": self.unix_offset_s,
               "dropped": self.dropped,
               "tids": sorted(self._tids.values()),
               "events": self.events()}
        with open(path, "w") as f:
            json.dump(doc, f)
            f.write("\n")
        return path


def _ensure_dir(path: str) -> None:
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)


def spool_path(trace_out: Union[str, os.PathLike], rank: int) -> str:
    """The per-rank spool file backing a merged `trace_out` timeline."""
    return f"{os.fspath(trace_out)}.rank{int(rank)}.spool.json"


def merge_spools(spools: Union[str, Iterable[Union[str, os.PathLike]]],
                 out: Optional[Union[str, os.PathLike]] = None
                 ) -> Dict[str, Any]:
    """Merge per-rank spool files into one clock-aligned timeline.

    `spools` is either a glob pattern (e.g. ``trace.json.rank*.spool
    .json``) or an iterable of paths.  Each rank's perf_counter clock
    is mapped onto the unix wall clock via its recorded
    `unix_offset_s`, then every timestamp rebases to the earliest
    event across all ranks — so cross-rank ordering (rank 0's
    all-reduce vs rank 1's, a survivor's re-mesh barrier vs the
    killed rank's last span) is faithful up to host wall-clock skew
    (sub-ms for the single-node spawner; NTP-grade across real
    hosts).  Events keep `pid = rank`.  Returns the merged document;
    writes it to `out` when given.
    """
    if isinstance(spools, (str, os.PathLike)):
        paths = sorted(_glob.glob(os.fspath(spools)))
    else:
        paths = [os.fspath(p) for p in spools]
    docs = []
    for p in paths:
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue          # a killed rank's partial spool: skip
        if doc.get("schema") == SPOOL_SCHEMA:
            docs.append(doc)
    if not docs:
        raise ValueError(f"no readable spool files among {paths!r}")

    base_unix_us = min(
        (e["ts"] + d["unix_offset_s"] * 1e6)
        for d in docs for e in d["events"]) if any(
            d["events"] for d in docs) else 0.0
    events: List[Dict[str, Any]] = []
    ranks = []
    for d in docs:
        rank = int(d["rank"])
        ranks.append(rank)
        off_us = d["unix_offset_s"] * 1e6
        events.append({"ph": "M", "name": "process_name", "pid": rank,
                       "tid": 0, "ts": 0,
                       "args": {"name": d.get("process_name",
                                              f"rank {rank}")}})
        for e in d["events"]:
            e = dict(e)
            # same association as the base computation above, so the
            # earliest event lands on exactly 0.0 (epoch-scale floats
            # round at ~0.25us; a different grouping can go negative)
            e["ts"] = (e["ts"] + off_us) - base_unix_us
            e["pid"] = rank
            events.append(e)
    merged = {"traceEvents": events, "displayTimeUnit": "ms",
              "metadata": {"ranks": sorted(ranks),
                           "spools": [os.path.basename(p) for p in paths]}}
    if out is not None:
        out = os.fspath(out)
        _ensure_dir(out)
        with open(out, "w") as f:
            json.dump(merged, f)
            f.write("\n")
    return merged


def validate_chrome_trace(doc: Dict[str, Any]) -> None:
    """Raise ValueError unless `doc` is a well-formed Chrome trace.

    The schema the exporter (and CI) holds itself to: a `traceEvents`
    list whose members carry the per-phase required keys with sane
    types — what Perfetto's JSON importer requires to load the file.
    """
    evs = doc.get("traceEvents")
    if not isinstance(evs, list):
        raise ValueError("traceEvents must be a list")
    for i, e in enumerate(evs):
        ph = e.get("ph")
        if ph not in ("X", "C", "i", "M", "B", "E"):
            raise ValueError(f"event {i}: unknown ph {ph!r}")
        if not isinstance(e.get("name"), str):
            raise ValueError(f"event {i}: missing name")
        if ph == "M":
            continue
        if not isinstance(e.get("ts"), (int, float)):
            raise ValueError(f"event {i}: missing numeric ts")
        if not isinstance(e.get("pid"), int):
            raise ValueError(f"event {i}: missing integer pid")
        if ph == "X":
            if not isinstance(e.get("dur"), (int, float)) or e["dur"] < 0:
                raise ValueError(f"event {i}: span needs dur >= 0")
        if ph == "C":
            args = e.get("args")
            if not args or not all(isinstance(v, (int, float))
                                   for v in args.values()):
                raise ValueError(f"event {i}: counter needs numeric args")


# ---------------------------------------------------------------------------
# The process-global default collector (what `repro.obs.span` etc. use)
# ---------------------------------------------------------------------------

_default: Collector = Collector()
_default_lock = threading.Lock()


def get_collector() -> Collector:
    return _default


def set_collector(col: Collector) -> Collector:
    global _default
    with _default_lock:
        _default = col
    return col


def set_rank(rank: int, process_name: Optional[str] = None) -> None:
    """Stamp the default collector with this process's rank (call after
    `jax.distributed` bring-up; single-process runs stay rank 0)."""
    _default.rank = int(rank)
    if process_name is not None:
        _default.process_name = process_name


def reset() -> None:
    _default.clear()


def span(name: str, **args: Any) -> Span:
    return _default.span(name, **args)


def counter(name: str, value: float, ts_s: Optional[float] = None) -> None:
    _default.counter(name, value, ts_s=ts_s)


def instant(name: str, ts_s: Optional[float] = None, **args: Any) -> None:
    _default.instant(name, ts_s=ts_s, **args)


def write_trace(path: Union[str, os.PathLike]) -> str:
    return _default.write(path)


def write_spool(path: Union[str, os.PathLike]) -> str:
    return _default.write_spool(path)
