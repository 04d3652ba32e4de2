"""The four-chip cell `rcv1-mesh4.uniform` through the harness, and the
readers of its two per-layer metrics.

Run explicitly (tier-1 does not collect bench/):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

`run.main` runs the cell at the small size of `small.py` over 4
virtual CPU devices, in a child process (jax pins the device count at
first use): a sound run reads correct, and a run with the iterates'
`pmean` dropped (the fault `pmean_dropped` of
tests/test_rcv1_mesh4.py) reads not correct.  Without the average each
chip still converges to w*, only slower, so at this size the fault's
`final_gap` depends on the seed (2.2e-9 to 2.6e-7 over ten seeds, six
of them above the cell's limit of 2e-8, against 2e-14 to 6e-13 for the
sound program): the seed is one at which it is above, the one
tests/test_rcv1_mesh4.py uses.  The readers are checked
on hand-made trace summaries, and report nothing on the recorded
one-chip trace, which has neither collectives nor shard placement.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from small import REPO

import loader
import phases
import tracereduce

CELL = "rcv1-mesh4.uniform"
SEED = 2**31 + 29
FIXTURE = REPO / "bench" / "tests" / "data" / "rcv1.uniform.trace.json.gz"

_CODE = """
    import json, sys
    sys.path[:0] = [{bench!r}, {bench_tests!r}, {tests!r}]
    import jax
    from small import last_json, small_root
    import io, contextlib
    import run
    from repro.core import pscope
    from test_rcv1_mesh4 import pmean_dropped

    root = small_root({tmp!r})

    def once(seed):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", {cell!r}, "--seed", str(seed),
                           "--seconds", "0.5", "--trace", "0"],
                          root=root, devices=jax.devices())
        assert rc == 0
        return last_json(out.getvalue())

    sound = once({seed})
    pmean_dropped(setattr)
    pscope._distributed_trajectory_fn.cache_clear()
    print(json.dumps({{"sound": sound, "faulted": once({seed})}}))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh4")
    code = _CODE.format(bench=str(REPO / "bench"),
                        bench_tests=str(REPO / "bench" / "tests"),
                        tests=str(REPO / "tests"), tmp=str(tmp), cell=CELL,
                        seed=SEED)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2500:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_mesh_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"time_to_gap_s", "round_s", "setup_s"}


def test_dropped_iterate_pmean_is_not_correct(runs):
    out = runs["faulted"]
    assert out["correct"] is False, out["checks"]


def test_allreduce_reader_divides_the_chips_collective_time_by_rounds():
    read = loader.Benchmark(REPO).reader("allreduce_ms_per_round")
    assert read({"chip": {"collective_ns": 6e6}, "rounds": 3}) == \
        pytest.approx(2.0)
    assert read({"chip": {"collective_ns": 0.0}, "rounds": 3}) is None


def _trace():
    # two mesh solves in the window 0..100, each placing its shards
    # (10 ns, then 6 ns) before its prepare; a third placement outside
    # the window is not counted
    plane = "/device:TPU:0"
    return {"devices": {plane: [["fusion.1", 20.0, 40.0, "fusion", ""]]},
            "host": [["bench_window", 0.0, 100.0],
                     ["solve.pscope_mesh", 0.0, 50.0],
                     ["mesh.shards", 1.0, 11.0],
                     ["mesh.prepare", 11.0, 15.0],
                     ["mesh.statics", 12.0, 14.0],
                     ["solve.pscope_mesh", 50.0, 100.0],
                     ["mesh.shards", 51.0, 57.0],
                     ["mesh.shards", 120.0, 130.0]]}


def test_shard_placement_reader_divides_by_solves(monkeypatch):
    summary = phases.summarize(_trace())
    assert summary["spans"]["mesh.shards"] == {"count": 2, "ns": 16.0}
    assert summary["spans"]["mesh.statics"] == {"count": 1, "ns": 2.0}
    monkeypatch.setattr(phases, "traced_window", lambda: summary)
    read = loader.Benchmark(REPO).reader("shard_placement_ms_per_solve")
    assert read({"chip": {"plane": "/device:TPU:0"}, "rounds": 2}) == \
        pytest.approx(8e-6)


def test_readers_report_nothing_on_a_one_chip_trace(monkeypatch):
    """The recorded rcv1.uniform trace: one chip, no collectives, no
    shard placement."""
    trace = tracereduce.read_saved(FIXTURE)
    chip, = tracereduce.summarize(trace, "bench_window",
                                  "fused_lazy_epoch")["chips"]
    monkeypatch.setattr(phases, "traced_window",
                        lambda: phases.summarize(trace))
    bench = loader.Benchmark(REPO)
    ctx = {"chip": chip, "rounds": 2}
    for name in ("allreduce_ms_per_round", "shard_placement_ms_per_solve"):
        assert bench.reader(name)(ctx) is None, name
