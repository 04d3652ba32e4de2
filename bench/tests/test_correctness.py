"""The check that decides `correct` fails what it has to fail.

Run explicitly (tier-1 does not collect bench/):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

At a small size on the CPU: a sound run reads correct; the control (the
plain reference in the program's place, in bfloat16) and each fault a
cell can have, planted under the timed path, read not correct.  On the
chip the control and the faults are read at each cell's own size by
bench/calibrate.py.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from small import REPO, last_json, small_root

import cell as cell_mod
import faults
import loader


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run(root, capsys, workload="rcv1.uniform", seed=2**31 + 11):
    import jax
    import run
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.5", "--trace", "0"],
                  root=root, devices=jax.devices())
    assert rc == 0
    return last_json(capsys.readouterr().out)


def _clear_program_caches():
    from repro.core import pscope
    pscope._sim_trajectory_fn.cache_clear()
    pscope._distributed_trajectory_fn.cache_clear()


def test_sound_run_is_correct(root, capsys):
    out = _run(root, capsys)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"time_to_gap_s", "round_s", "setup_s"}


def test_control_is_not_correct(root):
    """The reference in bfloat16, put in the program's place."""
    bench = loader.Benchmark(root)
    spec = bench.cell("rcv1.uniform")
    cell = cell_mod.Cell.build(spec, bench.config(spec["config"]), 5)
    _, p_star, _ = cell.reference()
    w, hist = cell.control()
    numbers = cell.compare([cell_mod.Solve(0.0, 0, hist, w)], p_star)
    assert any(numbers[k] > v for k, v in spec["limits"].items()), numbers


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_is_not_correct(root, capsys, monkeypatch, fault):
    faults.FAULTS[fault](monkeypatch.setattr)
    _clear_program_caches()
    try:
        out = _run(root, capsys)
    finally:
        monkeypatch.undo()
        _clear_program_caches()
    assert out["correct"] is False, out["checks"]


def test_cpu_platform_is_refused_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "bench/run.py", "--workload",
                          "rcv1.uniform", "--seed", "1", "--seconds", "1",
                          "--trace", "0"], env=env, capture_output=True,
                         text=True, timeout=300, cwd=REPO)
    assert res.returncode != 0
    assert "platform 'cpu'" in res.stderr
    assert res.stdout.strip() == ""
