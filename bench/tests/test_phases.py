"""The round's named phases and the program's host spans, read from a
profiler trace: on hand-made events, on the recorded rcv1.uniform chip
trace (which predates the scopes and the spans), and through the
per-layer readers."""
from __future__ import annotations

import pytest

from small import REPO

import loader
import phases
import tracereduce

FIXTURE = REPO / "bench" / "tests" / "data" / "rcv1.uniform.trace.json.gz"
PLANE = "/device:TPU:0"
BODY = "jit(trajectory)/while/body/closed_call/"


def _trace():
    # window 0..100.  Device: an objective fusion 2-8, the plan 10-30
    # with the anchor gradient's all-reduce 25-28 inside the
    # anchor-gradient scope, the kernel 30-60 (no scope), a gather fusion
    # nested in an unscoped while 62-70, an unscoped copy 95-99.
    # Host: two solves, each with prepare / dispatch / fetch.
    return {
        "devices": {PLANE: [
            ["fusion.1", 2.0, 8.0, "fusion", "jit(trajectory)/"
             "pscope.objective/reduce_sum"],
            ["fusion.2", 10.0, 30.0, "fusion", BODY + "vmap(pscope.plan)/"
             "sort"],
            ["all-reduce.3", 25.0, 28.0, "all-reduce",
             BODY + "shard_map/pscope.anchor_grad/psum"],
            ["fused_lazy_epoch.4", 30.0, 60.0, "custom-call", BODY + "vmap"],
            ["while.5", 61.0, 90.0, "while", "jit(trajectory)/while"],
            ["fusion.6", 62.0, 70.0, "fusion",
             BODY + "vmap(pscope.gather)/gather"],
            ["copy.7", 95.0, 99.0, "copy", ""],
        ]},
        "host": [["bench_window", 0.0, 100.0],
                 ["solve.pscope_lazy", 0.0, 60.0],
                 ["solve.prepare", 0.0, 2.0],
                 ["solve.dispatch", 2.0, 3.0],
                 ["solve.fetch", 3.0, 58.0],
                 ["$pscope.py:736 run_scanned", 0.0, 59.0],
                 ["solve.pscope_lazy", 61.0, 100.0],
                 ["solve.prepare", 90.0, 94.0],
                 ["mesh.shards", 120.0, 130.0]],
    }


def test_scope_of_takes_the_innermost_phase():
    assert phases.scope_of(BODY + "vmap(pscope.plan)/sort") == "pscope.plan"
    assert phases.scope_of(
        "pscope.objective/x/pscope.gather/y") == "pscope.gather"
    assert phases.scope_of("jit(trajectory)/while") == ""
    assert phases.scope_of("") == ""


def test_scope_time_leaves_out_kernels_and_collectives():
    chip = phases.summarize(_trace())["chips"][PLANE]
    assert chip["scope_ns"] == {
        "pscope.anchor_grad": 0.0,      # all of it was its all-reduce
        "pscope.plan": 17.0,            # 10-30 less the all-reduce 25-28
        "pscope.gather": 8.0,
        "pscope.average": 0.0,
        "pscope.objective": 6.0,
    }


def test_program_spans_are_counted_and_summed_inside_the_window():
    summary = phases.summarize(_trace())
    assert summary["spans"] == {
        "solve.pscope_lazy": {"count": 2, "ns": 99.0},
        "solve.prepare": {"count": 2, "ns": 6.0},
        "solve.dispatch": {"count": 1, "ns": 1.0},
        "solve.fetch": {"count": 1, "ns": 55.0},
    }
    assert summary["solves"] == 2


def test_idle_is_named_by_the_innermost_program_span():
    # idle: 0-2 (prepare), 8-10 (fetch), 60-61 (between the solves),
    # 90-95 (prepare 90-94, then the solve), 99-100 (the solve)
    idle = dict(phases.summarize(_trace())["idle_by_span"])
    assert idle == pytest.approx({"solve.prepare": 6e-9,
                                  "solve.fetch": 2e-9,
                                  "solve.pscope_lazy": 2e-9,
                                  phases.NO_SPAN: 1e-9})
    # all of the window's idle time: 100 less the busy 2-8, 10-60,
    # 61-90 and 95-99
    assert sum(idle.values()) == pytest.approx(11e-9)


def test_readers_report_nothing_on_a_trace_without_scopes_or_spans(
        monkeypatch):
    """The recorded rcv1.uniform trace was taken before the program
    named its phases: every new reader reports nothing, and raises
    nothing."""
    trace = tracereduce.read_saved(FIXTURE)
    summary = phases.summarize(trace)
    chip = summary["chips"][PLANE]
    assert set(chip["scope_ns"].values()) == {0.0}
    assert summary["spans"] == {} and summary["solves"] == 0
    assert summary["idle_by_span"][0][0] == phases.NO_SPAN
    monkeypatch.setattr(phases, "traced_window", lambda: summary)
    bench = loader.Benchmark(REPO)
    ctx = {"chip": {"plane": PLANE}, "rounds": 2}
    new = ("anchor_grad_ms_per_round", "plan_ms_per_round",
           "gather_ms_per_round", "average_ms_per_round",
           "objective_ms_per_round", "prepare_ms_per_solve")
    for name in new:
        assert bench.reader(name)(ctx) is None, name


def test_readers_divide_by_rounds_and_solves(monkeypatch):
    monkeypatch.setattr(phases, "traced_window",
                        lambda: phases.summarize(_trace()))
    bench = loader.Benchmark(REPO)
    ctx = {"chip": {"plane": PLANE}, "rounds": 2}
    read = {m: bench.reader(m)(ctx) for m in (
        "plan_ms_per_round", "gather_ms_per_round",
        "objective_ms_per_round", "anchor_grad_ms_per_round",
        "prepare_ms_per_solve")}
    assert read == pytest.approx({
        "plan_ms_per_round": 8.5e-6, "gather_ms_per_round": 4e-6,
        "objective_ms_per_round": 3e-6, "anchor_grad_ms_per_round": None,
        "prepare_ms_per_solve": 3e-6})


def test_existing_readers_read_the_recorded_trace_as_before():
    """The five readers of the accepted benchmark, on the recorded
    rcv1.uniform trace of two rounds."""
    trace = tracereduce.read_saved(FIXTURE)
    stats = tracereduce.summarize(trace, "bench_window", "fused_lazy_epoch")
    chip, = stats["chips"]
    ctx = {"chip": chip, "rounds": 2, "rounds_to_gap": 5.05,
           "epoch_bytes_per_chip_round": 8 * 4_331_472,
           "peak": {"hbm_bytes_per_s": 819e9}}
    bench = loader.Benchmark(REPO)
    read = {m["name"]: bench.reader(m["name"])(ctx)
            for m in bench.cell_metrics("rcv1.uniform", "per_layer")
            if m["name"] in ("rounds_to_gap", "epoch_kernel_ms_per_round",
                             "fused_lazy_epoch_roofline",
                             "xla_ops_ms_per_round", "idle_pct")}
    assert read == pytest.approx({
        "rounds_to_gap": 5.05,
        "epoch_kernel_ms_per_round": 683.7748095,
        "fused_lazy_epoch_roofline": 100 * 2 * 8 * 4_331_472 / 819e9
        / (1_367_549_619e-9),
        "xla_ops_ms_per_round": (2_202_488_215.0 - 1_367_549_619.0) / 2e6,
        "idle_pct": 100 * (1 - 2_202_488_215.0 / 2_207_304_721.0)},
        rel=1e-6)


SCOPED = FIXTURE.with_name("rcv1.uniform.scoped.trace.json.gz")


def test_recorded_chip_trace_with_scopes_and_spans():
    """The first two rounds of an rcv1.uniform solve on a TPU v5e, from
    the window's start (`phases.load` of the run's trace, trimmed as the
    older fixture was): 1,559 XLA ops with their op_name paths, the
    program spans and the host events of 0.2 ms or more.  Recorded with
    `solve.<solver>` still opened by the adapter, after the solve's
    first ~1 ms, which is why some idle time has no program span.

    To record one anew, on the chip: keep the trace that `run.main(...,
    "--trace", "1")` writes under `phases.TRACE_DIR`, `phases.load` it,
    keep the events up to the end of round 2's `pscope.average` ops and
    cut the `bench_window` event there, and `tracereduce.save` it."""
    trace = tracereduce.read_saved(SCOPED)
    ops = trace["devices"][PLANE]
    summary = phases.summarize(trace)
    scope = summary["chips"][PLANE]["scope_ns"]
    assert scope == {"pscope.anchor_grad": 40_117_826.0,
                     "pscope.plan": 577_765_473.0,
                     "pscope.gather": 40_498_139.0,
                     "pscope.average": 903.0,
                     "pscope.objective": 19_989_027.0}
    # the compiler's fusions of a scatter have no op_name of their own:
    # they take the phase of the instructions they fuse
    assert {phases.scope_of(o[4]) for o in ops
            if o[0] == "fusion.196"} == {"pscope.anchor_grad"}
    assert {phases.scope_of(o[4]) for o in ops
            if o[0] == "fusion.208"} == {"pscope.plan"}
    # busy time outside the kernel and the collectives, as
    # `xla_ops_ms_per_round` reads it: the five phases, and the shard
    # statics that `solve.prepare` dispatches op by op, outside the
    # trajectory's program and so outside any scope
    plain = {"devices": {PLANE: [o[:4] for o in ops]},
             "host": trace["host"]}
    chip, = tracereduce.summarize(plain, "bench_window",
                                  "fused_lazy_epoch")["chips"]
    rest = chip["other_ns"] - sum(scope.values())
    assert rest == pytest.approx(155_556_511.0)
    outside = tracereduce.union([(o[1], o[2]) for o in ops
                                 if not o[4].startswith("jit(trajectory)")
                                 and not phases.scope_of(o[4])])
    assert sum(e - s for s, e in outside) > 0.99 * rest
    assert summary["spans"]["solve.prepare"] == {"count": 1,
                                                 "ns": 39_555_736.0}
    assert summary["solves"] == 1
    assert summary["idle_by_span"] == [
        ["solve.prepare", pytest.approx(0.00312661)],
        [phases.NO_SPAN, pytest.approx(0.001191105)],
        ["solve.fetch", pytest.approx(0.000107155)],
        ["solve.pscope_lazy", pytest.approx(9.62e-06)]]
