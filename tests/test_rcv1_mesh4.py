"""The four-chip CALL deployment (`rcv1-mesh4.uniform`) against the plain
reference, on 4 virtual CPU devices.

The cell's own rounds, gap and limits; the configuration at the small
size of `bench/tests/small.py` (rows, features, nonzeros and weights),
one pSCOPE worker a device.  A child process (jax pins the device count
at first use) builds the cell through the benchmark's own code
(`bench/cell.py`), so each solve is `solvers.run("pscope_mesh")` as the
benchmark times it, and holds it to `bench/reference.py`:

  * a sound solve reaches P* within the cell's `final_gap` limit and
    records P(w_0) and P(w_T) within its `value_err` limit;
  * with `pmean_dropped` planted the check fails;
  * every solve opens one `mesh.statics` span, inside its
    `mesh.prepare`.

`pmean_dropped` is also the fault that `bench/calibrate.py` reads at the
cell's own size on the chip (PERF.md, section 4, gives the command).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "rcv1-mesh4.uniform"
SEED = 2**31 + 29


def pmean_dropped(patch):
    """The iterates' `pmean` in `pscope.average` left out: each chip
    keeps its own iterate, and the replicated result is one chip's.
    Takes `patch(obj, name, value)` as the faults of `bench/faults.py`
    do; plant it before the trajectory is traced."""
    import jax
    from jax._src import source_info_util
    real = jax.lax.pmean

    def pmean(x, axis_name, **kw):
        if "pscope.average" in str(source_info_util.current_name_stack()):
            return x
        return real(x, axis_name, **kw)
    patch(jax.lax, "pmean", pmean)


_CODE = """
    import json, sys
    sys.path[:0] = [{bench!r}, {bench_tests!r}, {tests!r}]
    from small import SMALL
    import cell as cell_mod
    import loader
    from repro import obs
    from repro.core import pscope
    from test_rcv1_mesh4 import pmean_dropped

    bench = loader.Benchmark({root!r})
    spec = bench.cell({cell!r})
    config = {{**bench.config(spec["config"]), **SMALL}}
    cell = cell_mod.Cell.build(spec, config, {seed})
    obs.reset()
    solves = [cell.solve(), cell.solve()]
    spans = [[e["name"], e["ts"], e["ts"] + e["dur"]]
             for e in obs.get_collector().events() if e["ph"] == "X"]
    _, p_star, _ = cell.reference()
    sound = cell.compare(solves, p_star)
    pmean_dropped(setattr)
    pscope._distributed_trajectory_fn.cache_clear()
    faulted = cell.compare([cell.solve()], p_star)
    print(json.dumps({{"limits": spec["limits"], "rounds": spec["rounds"],
                      "solve_rounds": [s.rounds for s in solves],
                      "recorded": [len(s.values) for s in solves],
                      "sound": sound, "faulted": faulted,
                      "spans": spans}}))
"""


@pytest.fixture(scope="module")
def mesh_cell():
    code = _CODE.format(root=ROOT, bench=os.path.join(ROOT, "bench"),
                        bench_tests=os.path.join(ROOT, "bench", "tests"),
                        tests=os.path.join(ROOT, "tests"), cell=CELL,
                        seed=SEED)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-2500:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mesh_solve_reaches_the_reference_within_the_cell_limits(mesh_cell):
    rounds = mesh_cell["rounds"]
    assert mesh_cell["solve_rounds"] == [rounds, rounds]
    assert mesh_cell["recorded"] == [rounds + 1, rounds + 1]
    sound, limits = mesh_cell["sound"], mesh_cell["limits"]
    assert set(limits) == {"final_gap", "value_err"}
    for k, limit in limits.items():
        assert sound[k] <= limit, (k, sound[k], limit)


def test_dropped_iterate_pmean_fails_the_check(mesh_cell):
    faulted, limits = mesh_cell["faulted"], mesh_cell["limits"]
    assert any(faulted[k] > limit for k, limit in limits.items()), faulted


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_each_solve_opens_one_statics_span_inside_its_prepare(mesh_cell):
    spans = sorted(mesh_cell["spans"], key=lambda s: s[1])
    solves = [s for s in spans if s[0] == "solve.pscope_mesh"]
    prepares = [s for s in spans if s[0] == "mesh.prepare"]
    statics = [s for s in spans if s[0] == "mesh.statics"]
    assert len(solves) == len(prepares) == len(statics) == 2
    for solve, prepare in zip(solves, prepares):
        inside = [s for s in statics if _within(s, prepare)]
        assert len(inside) == 1 and _within(prepare, solve)
