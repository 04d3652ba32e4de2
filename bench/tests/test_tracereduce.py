"""The reduction from a profiler trace to per-chip times and the
breakdown, on hand-made events and on a small trimmed chip trace."""
from __future__ import annotations

import pytest

from small import REPO

import tracereduce

FIXTURE = REPO / "bench" / "tests" / "data" / "rcv1.uniform.trace.json.gz"


def _trace():
    # window 0..100; ops: kernel 10-50, a fusion 45-60 (overlaps the
    # kernel), an all-reduce 70-75, one op half outside the window
    return {
        "devices": {"/device:TPU:0": [
            ["fused_lazy_epoch.1", 10.0, 50.0, "custom-call"],
            ["fusion.3", 45.0, 60.0, "loop fusion"],
            ["all-reduce.2", 70.0, 75.0, "all-reduce"],
            ["copy.1", 95.0, 120.0, "data formatting"],
        ]},
        "host": [["bench_window", 0.0, 100.0],
                 ["run", -5.0, 130.0],
                 ["_prepare_sim", 0.0, 9.0],
                 ["device_put", 60.0, 72.0]],
    }


def test_busy_is_the_union_inside_the_window():
    chip = tracereduce.summarize(_trace(), "bench_window",
                                 "fused_lazy_epoch")["chips"][0]
    assert chip["window_ns"] == 100.0
    assert chip["busy_ns"] == 50.0 + 5.0 + 5.0   # 10-60, 70-75, 95-100
    assert chip["kernel_ns"] == 40.0
    assert chip["collective_ns"] == 5.0
    assert chip["other_ns"] == 10.0 + 5.0        # fusion 50-60, copy 95-100


def test_breakdown_names_ops_and_idle_gaps():
    bd = tracereduce.summarize(_trace(), "bench_window",
                               "fused_lazy_epoch")["breakdown"]
    assert bd["device_ops"][0] == ["fused_lazy_epoch.1 custom-call", 40e-9]
    gaps = [(n, round(s * 1e9)) for n, s in bd["idle_gaps"]]
    # longest first: 75-95 only the outer frame covers; 0-10 and 60-70
    # are named by the innermost event covering at least half of each
    assert gaps == [("run", 20), ("_prepare_sim", 10), ("device_put", 10)]


def test_self_time_excludes_enclosed_ops():
    ops = [["while.1", 0.0, 100.0, "while"],
           ["fusion.2", 10.0, 30.0, "fusion"],
           ["fused_lazy_epoch.3", 40.0, 90.0, "custom-call"],
           ["copy.4", 120.0, 125.0, "copy"]]
    assert tracereduce.self_times(ops) == {
        "while.1 while": 30.0, "fusion.2 fusion": 20.0,
        "fused_lazy_epoch.3 custom-call": 50.0, "copy.4 copy": 5.0}


def test_parse_op_reads_hlo_text():
    assert tracereduce.parse_op(
        "%fusion.208 = s32[1875648]{0:T(1024)S(1)} fusion(s32[8,187220]"
        "{1,0:T(8,128)S(1)} %get-tuple-element.685), kind=kCustom") == (
        "fusion.208", "fusion")
    assert tracereduce.parse_op(
        "%while.21 = (s32[]{:T(128)}, f32[47236]{0:T(1024)}) while((s32[]"
        "{:T(128)}, f32[47236]{0:T(1024)}) %tuple.168), condition=%c") == (
        "while.21", "while")
    assert tracereduce.parse_op("all-reduce.1") == ("all-reduce.1", "")


def test_union_merges_overlaps():
    assert tracereduce.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4],
                                                                  [5, 8]]


def test_recorded_chip_trace():
    """Two rounds of an rcv1.uniform solve on a TPU v5e, trimmed: 1,573
    XLA ops, the host events of 0.2 ms or more, and the window.  It was
    recorded on an earlier version of the cell's data (columns drawn
    uniformly); the reduction it checks does not depend on the data.

    To record one anew, on the chip: build the cell as `run.main` does,
    `_, xplane = run.traced_window(cell, 5.0)`, `trace =
    tracereduce.load(xplane)`, keep two rounds' events of
    `trace["devices"]` and the host events of 0.2 ms or more, and
    `tracereduce.save(trace, FIXTURE)`."""
    trace = tracereduce.read_saved(FIXTURE)
    stats = tracereduce.summarize(trace, "bench_window", "fused_lazy_epoch")
    chip, = stats["chips"]
    kernels = [e - s for n, s, e, _ in trace["devices"]["/device:TPU:0"]
               if n.startswith("fused_lazy_epoch")]
    # one kernel launch a round runs all 8 workers' epochs
    assert kernels == [683_869_103.0, 683_680_516.0]
    assert chip["kernel_ns"] == sum(kernels)
    assert chip["collective_ns"] == 0
    assert chip["window_ns"] == pytest.approx(2_207_304_721.0)
    assert chip["busy_ns"] == pytest.approx(2_202_488_215.0)
    assert chip["other_ns"] == chip["busy_ns"] - chip["kernel_ns"]
    ops = stats["breakdown"]["device_ops"]
    assert ops[0] == ["fused_lazy_epoch.8 custom-call", sum(kernels) / 1e9]
    # the enclosing while loop has almost no time of its own
    assert all(not n.startswith("while") for n, _ in ops[:3])
    gaps = stats["breakdown"]["idle_gaps"]
    assert len(gaps) == 10 and gaps[0][1] == pytest.approx(0.002807254)
