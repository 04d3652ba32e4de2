"""The epoch plan past the packed int32 key, at news20.binary's width.

`core.plan._plan_from_sort` packs (column, step) into one int32 key,
which holds only while d * (M + 1) < 2^31.  news20.binary has d =
1,355,191 features, so one inner epoch of M = 1,600 steps already
crosses it (2.17e9), and `build_epoch_plan` takes the wide plan
(`_plan_from_sort_wide`) there.  The contracts:

  * past the bound the wide plan equals the literal replay of the
    per-step bookkeeping bit for bit, duplicates included;
  * below it the wide plan, forced, equals the packed one;
  * a `pscope_lazy` solve past the bound equals the Python-loop driver
    and reaches the plain reference's P* (`bench/reference.py`) within
    `news20.uniform`'s limits, and the half-batch fault of
    `bench/faults.py` planted under it does not.
"""
import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import LOGISTIC, PScopeConfig, Regularizer, pscope, solvers
from repro.core import plan as plan_mod
from repro.data.sparse import CSRMatrix
from repro.partition.container import make_partition
from test_fused_inner import _brute_plan, _random_shard

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D = 1_355_191               # news20.binary's features
M = 1_600                   # one epoch of a 1,600-row worker
SEED = 2**31 + 7


def _repeating_rows(rng, workers, n_k, k, d):
    """(workers, n_k, k) columns over all of [0, d), with a column
    repeated inside every row and a few hot columns shared by many rows,
    so that duplicates and long staleness both occur."""
    cols = rng.randint(0, d, size=(workers, n_k, k)).astype(np.int32)
    cols[:, :, -1] = cols[:, :, 0]
    cols[:, ::5, 1] = rng.randint(0, 16, size=(workers, 1))
    return cols


def test_wide_plan_matches_replay_past_int32():
    """2 workers vmapped, M = 1,600 steps of S = 4 slots: d * (M + 1) =
    2.17e9 >= 2^31, so `build_epoch_plan` takes the wide plan, under
    its own scope."""
    rng = np.random.RandomState(0)
    cols = _repeating_rows(rng, 2, 800, 4, D)
    idx = rng.randint(0, 800, size=(2, M, 1)).astype(np.int32)
    assert D * (M + 1) >= 2**31 and not plan_mod.packed_plan_fits(D, M, 4)
    build = jax.jit(jax.vmap(lambda c, i: plan_mod.build_epoch_plan(c, i, D)))
    hlo = build.lower(cols, idx).compile().as_text()
    assert plan_mod.SCOPE_PLAN_WIDE in hlo
    eplan = build(jnp.asarray(cols), jnp.asarray(idx))
    for w in range(2):
        cf, q, rep, qf = _brute_plan(cols[w], idx[w], D)
        assert (rep != np.arange(4)).any()          # duplicates occur
        np.testing.assert_array_equal(np.asarray(eplan.cflat[w]), cf)
        np.testing.assert_array_equal(np.asarray(eplan.q[w]), q)
        np.testing.assert_array_equal(np.asarray(eplan.rep[w]), rep)
        np.testing.assert_array_equal(np.asarray(eplan.qf[w]), qf)


@pytest.mark.parametrize("b", [1, 3])
def test_wide_plan_equals_packed_below_the_bound(b):
    rng = np.random.RandomState(5)
    n_k, d, k, m = 40, 3_001, 7, 64
    _, cols = _random_shard(rng, n_k, d, k)
    idx = jnp.asarray(rng.randint(0, n_k, size=(m, b)), jnp.int32)
    assert plan_mod.packed_plan_fits(d, m, b * k)
    packed = plan_mod.build_epoch_plan(cols, idx, d)
    wide = plan_mod._plan_from_sort_wide(cols, idx, d)
    for a, w in zip(packed, wide):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


def test_build_epoch_plan_takes_the_wide_plan_past_either_bound():
    """Where `_plan_from_sort` refuses a shape (its ValueError names the
    wide plan), `build_epoch_plan` traces it."""
    shapes = [(7, 1, 1 << 28), ((1 << 11), 1 << 10, 96)]   # (M, k, d)
    for m, k, d in shapes:
        args = (jax.ShapeDtypeStruct((4, k), jnp.int32),
                jax.ShapeDtypeStruct((m, 1), jnp.int32))
        with pytest.raises(ValueError, match="_plan_from_sort_wide"):
            jax.eval_shape(lambda c, i: plan_mod._plan_from_sort(c, i, d),
                           *args)
        out = jax.eval_shape(
            lambda c, i: plan_mod.build_epoch_plan(c, i, d), *args)
        assert out.qf.shape == (d,) and out.q.shape == (m, k)


def _bench(name):
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(scope="module")
def past_int32():
    """news20.binary's width over 2 workers of 1,600 rows, 8 nonzeros a
    row, from the benchmark's generator; the weights of the benchmark's
    small-size tests (`bench/tests/small.py`), at which 3 rounds converge
    as far as the full-size cells' rounds do.  A `solve()` through
    `solvers.run`, the Python-loop oracle, the reference's P*, and the
    check that decides the cell's `correct`."""
    traffic, reference = _bench("traffic"), _bench("reference")
    with open(os.path.join(ROOT, "bench", "configs", "news20.json")) as f:
        config = json.load(f)
    config.update(rows=3_200, nnz_per_row=8, lam1=1e-3, lam2=1e-4)
    vals, cols, y = traffic.make_rows(config, SEED)
    idx = traffic.assign(y, 2, {"rule": "uniform"}, SEED)
    csr = CSRMatrix(vals=jnp.asarray(vals), cols=jnp.asarray(cols),
                    row_nnz=jnp.full((len(y),), 8, jnp.int32), d=D)
    part = make_partition(csr, y, idx, name="uniform")
    reg = Regularizer(config["lam1"], config["lam2"])
    eta = 1 / (2 * (0.25 + config["lam1"]))

    def solve():
        return solvers.run("pscope_lazy", LOGISTIC, reg, part,
                           solvers.SolverConfig(
                               rounds=3, eta=eta, inner_epochs=1.0,
                               seed=SEED % 2**31,
                               extras={"inner_batch": 1}))

    oracle = pscope.run(LOGISTIC, reg, part.csr_p, part.yp, jnp.zeros(D),
                        PScopeConfig(eta=eta, inner_steps=M, outer_steps=3,
                                     seed=SEED % 2**31, inner_path="lazy"),
                        driver="python")
    held = idx.reshape(-1)
    rows = (vals[held], cols[held], y[held])
    w, _, step = reference.solve(*rows, D, reg.lam1, reg.lam2,
                                 dtype=np.float32, iters=200)
    w = reference.polish(*rows, w, reg.lam1, reg.lam2, step, 10)
    p_star = reference.objective64(*rows, w, reg.lam1, reg.lam2)

    def check(tr):
        return reference.compare([(np.asarray(tr.values),
                                   np.asarray(tr.w_final))], rows, p_star,
                                 reg.lam1, reg.lam2)

    with open(os.path.join(ROOT, "bench", "workloads",
                           "news20.uniform.json")) as f:
        limits = json.load(f)["limits"]
    return solve, oracle, check, limits


def test_pscope_lazy_past_int32_matches_oracle_and_reference(past_int32):
    solve, (w_p, h_p), check, limits = past_int32
    tr = solve()
    assert tr.rounds == 3
    np.testing.assert_allclose(tr.values, h_p, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(tr.w_final), np.asarray(w_p),
                               atol=1e-6, rtol=1e-5)
    checks = check(tr)
    assert all(checks[k] <= v for k, v in limits.items()), (checks, limits)


def test_half_batch_fault_fails_the_cell_limits(past_int32):
    """`bench/faults.py`'s half-batch anchor gradient, planted under the
    same solve, fails `news20.uniform`'s limits."""
    solve, _, check, limits = past_int32
    faults = _bench("faults")
    pscope._sim_trajectory_fn.cache_clear()
    try:
        with pytest.MonkeyPatch.context() as mp:
            faults.half_batch(mp.setattr)
            checks = check(solve())
    finally:
        pscope._sim_trajectory_fn.cache_clear()
    assert any(checks[k] > v for k, v in limits.items()), (checks, limits)
