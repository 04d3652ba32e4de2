"""The reader of `plan_wide_ms_per_round` on hand-made trace events:
an op under `pscope.plan_wide` counts, an op under the round's other
scopes or under none does not, nor does the time of a kernel or a
collective inside the scope; a trace without the scope reports
nothing."""
from __future__ import annotations

from small import REPO

import loader
import phases
import tracereduce

PLANE = "/device:TPU:0"
BODY = "jit(trajectory)/while/body/closed_call/"
SCOPED = REPO / "bench" / "tests" / "data" / "rcv1.uniform.scoped.trace.json.gz"


def _trace():
    # window 0..100.  The wide plan's sort 10-30 (an all-reduce 25-28
    # inside it), its gather 40-45 and a fusion 44-50; the packed plan's
    # sort 50-60; the kernel 60-80 under the wide scope's path; an
    # unscoped copy 85-90; a wide-plan op 95-105 cut by the window.
    wide = BODY + "vmap(pscope.plan)/vmap(pscope.plan_wide)/"
    return {
        "devices": {PLANE: [
            ["sort.1", 10.0, 30.0, "sort", wide + "sort"],
            ["all-reduce.2", 25.0, 28.0, "all-reduce", wide + "psum"],
            ["gather.3", 40.0, 45.0, "gather", wide + "jit(_take)/gather"],
            ["fusion.4", 44.0, 50.0, "fusion", wide + "select_n"],
            ["sort.5", 50.0, 60.0, "sort", BODY + "vmap(pscope.plan)/sort"],
            ["fused_lazy_epoch.6", 60.0, 80.0, "custom-call", wide + "k"],
            ["copy.7", 85.0, 90.0, "copy", ""],
            ["fusion.8", 95.0, 105.0, "fusion", wide + "rem"],
        ]},
        "host": [["bench_window", 0.0, 100.0]],
    }


def _read(monkeypatch, tmp_path, trace, rounds=2):
    xplane = tmp_path / "t.xplane.pb"
    xplane.write_bytes(b"")
    monkeypatch.setattr(tracereduce, "find_xplane", lambda _: str(xplane))
    monkeypatch.setattr(phases, "load", lambda _: trace)
    read = loader.Benchmark(REPO).reader("plan_wide_ms_per_round")
    return read({"chip": {"plane": PLANE}, "rounds": rounds})


def test_scoped_ops_count_and_others_do_not(monkeypatch, tmp_path):
    # 10-30 less the all-reduce 25-28, 40-50, 95-100: 17 + 10 + 5 ns
    assert _read(monkeypatch, tmp_path, _trace()) == 32 / 2 / 1e6


def test_an_op_outside_the_scope_reports_nothing(monkeypatch, tmp_path):
    trace = _trace()
    trace["devices"][PLANE] = [o for o in trace["devices"][PLANE]
                               if "plan_wide" not in o[4]
                               or o[0].startswith("fused")]
    assert _read(monkeypatch, tmp_path, trace) is None


def test_the_recorded_rcv1_trace_has_no_wide_plan(monkeypatch, tmp_path):
    """rcv1's plan fits the packed key: its recorded chip trace, whose
    ops carry their scopes, reads nothing."""
    trace = tracereduce.read_saved(SCOPED)
    assert any(phases.scope_of(o[4]) == "pscope.plan"
               for o in trace["devices"][PLANE])
    assert _read(monkeypatch, tmp_path, trace) is None
