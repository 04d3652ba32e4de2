"""The epoch-planned fused lazy engine: plans, kernel, auto path, driver.

Four contracts:
  * `core.plan` epoch plans (both builders) == a literal Python replay
    of the per-step `last` bookkeeping, duplicates included;
  * the fused inner loop == the PR-2 reference scan == the dense loop,
    over the regularizer/eta/seed/batch box, in both USE_PALLAS modes,
    and with the whole-epoch Pallas kernel forced on;
  * `inner_path="auto"` picks the measured winner on the
    BENCH_inner_loop.json grid corners (where the margin is decisive);
  * the scanned zero-sync driver reproduces the Python-loop driver's
    history exactly.
"""
import os
import dataclasses
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import LOGISTIC, LASSO, PScopeConfig, Regularizer
from repro.core import plan as plan_mod
from repro.core import pscope
from repro.core.partition import uniform_partition, stack_partition
from repro.core.pscope import _lazy_inner_loop, _lazy_inner_loop_ref
from repro.core.svrg import logistic_h_prime
from repro.data import dense_to_csr, csr_partition
from repro.data.sparse import make_csr_classification
from repro.data.synthetic import make_sparse_classification
from repro.kernels import ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# plan correctness vs literal replay
# ---------------------------------------------------------------------------

def _brute_plan(cols_k, idx, d):
    """Replay the PR-2 per-step `last` bookkeeping in Python."""
    cols_k = np.asarray(cols_k)
    idx = np.asarray(idx)
    M, b = idx.shape
    k = cols_k.shape[1]
    S = b * k
    last = np.zeros(d, np.int64)
    q = np.zeros((M, S), np.int64)
    cf = np.zeros((M, S), np.int64)
    rep = np.zeros((M, S), np.int64)
    for m in range(M):
        cols = cols_k[idx[m]].reshape(-1)
        cf[m] = cols
        q[m] = m - last[cols]
        last[cols] = m + 1
        for s in range(S):
            rep[m, s] = int(np.nonzero(cols == cols[s])[0][0])
    return cf, q, rep, M - last


def _random_shard(rng, n_k, d, k, dup_frac=0.3):
    """CSR cols with forced duplicate columns inside rows."""
    cols = rng.randint(0, d, size=(n_k, k)).astype(np.int32)
    ndup = max(1, int(dup_frac * k))
    for r in range(n_k):
        src = rng.choice(k, ndup)
        dst = rng.choice(k, ndup)
        cols[r, dst] = cols[r, src]
    vals = rng.randn(n_k, k).astype(np.float32)
    return jnp.asarray(vals), jnp.asarray(cols)


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("builder", ["membership", "sort"])
def test_epoch_plan_matches_replay(b, builder):
    rng = np.random.RandomState(0)
    n_k, d, k, M = 12, 97, 9, 20
    vals, cols = _random_shard(rng, n_k, d, k)
    idx = jnp.asarray(rng.randint(0, n_k, size=(M, b)), jnp.int32)
    if builder == "membership":
        if b != 1:
            pytest.skip("membership builder is b = 1 only")
        statics = plan_mod.shard_statics(vals, cols, with_member=True)
        assert statics.member is not None
        eplan = plan_mod._plan_from_membership(cols, idx, d, statics)
    else:
        eplan = plan_mod._plan_from_sort(cols, idx, d)
    cf, q, rep, qf = _brute_plan(cols, idx, d)
    np.testing.assert_array_equal(np.asarray(eplan.cflat), cf)
    np.testing.assert_array_equal(np.asarray(eplan.q), q)
    np.testing.assert_array_equal(np.asarray(eplan.rep), rep)
    np.testing.assert_array_equal(np.asarray(eplan.qf), qf)


def test_build_epoch_plan_dispatch_equivalence():
    """The two builders produce the same plan on the same inputs."""
    rng = np.random.RandomState(3)
    n_k, d, k, M = 16, 211, 7, 24
    vals, cols = _random_shard(rng, n_k, d, k)
    idx = jnp.asarray(rng.randint(0, n_k, size=(M, 1)), jnp.int32)
    statics = plan_mod.shard_statics(vals, cols, with_member=True)
    p_mem = plan_mod.build_epoch_plan(cols, idx, d, statics)
    p_sort = plan_mod._plan_from_sort(cols, idx, d)
    for a, b_ in zip(p_mem, p_sort):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_shard_statics_dup_sums():
    rng = np.random.RandomState(1)
    vals, cols = _random_shard(rng, 8, 50, 6, dup_frac=0.5)
    st_ = plan_mod.shard_statics(vals, cols, with_member=True)
    v, c = np.asarray(vals), np.asarray(cols)
    for r in range(8):
        for s in range(6):
            expect = v[r][c[r] == c[r, s]].sum()
            np.testing.assert_allclose(np.asarray(st_.xdup)[r, s], expect,
                                       rtol=1e-6)
            assert (np.asarray(st_.rep_row)[r, s]
                    == int(np.nonzero(c[r] == c[r, s])[0][0]))
            np.testing.assert_array_equal(
                np.asarray(st_.member)[r, s],
                np.array([c[r, s] in c[rr] for rr in range(8)]))


# ---------------------------------------------------------------------------
# capped (tabulated) catch-up == uncapped == sequential replay
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("regime", [(1e-4, 1e-4), (0.0, 1e-3), (1e-2, 1e-3),
                                    (1e-2, 0.0), (0.0, 0.0)],
                         ids=["paper", "pure_l1", "elastic", "ridge",
                              "unreg"])
def test_capped_catch_up_exact(regime):
    from repro.core.recovery import (recovery_catch_up,
                                     recovery_catch_up_capped,
                                     sequential_catch_up)
    lam1, lam2 = regime
    M = 48
    rng = np.random.RandomState(11)
    u = jnp.asarray(rng.randn(4096).astype(np.float32))
    z = jnp.asarray(rng.randn(4096).astype(np.float32) * 0.05)
    q = jnp.asarray(rng.randint(0, M + 1, 4096), jnp.int32)
    ref = recovery_catch_up(u, z, q, 0.3, lam1, lam2)
    capped = recovery_catch_up_capped(u, z, q, 0.3, lam1, lam2, q_cap=M)
    seq = sequential_catch_up(u, z, q, 0.3, lam1, lam2, M)
    # same table-free formulas evaluated through the table: bitwise
    np.testing.assert_array_equal(np.asarray(capped), np.asarray(ref))
    np.testing.assert_allclose(np.asarray(capped), np.asarray(seq),
                               atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused engine == reference scan == dense, incl. the Pallas epoch kernel
# ---------------------------------------------------------------------------

def _epoch_args(seed=0, n_k=24, d=160, density=0.06, M=32, b=1):
    csr, y, _ = make_csr_classification(n_k, d, density=density, seed=seed)
    rng = np.random.RandomState(seed + 7)
    w = jnp.asarray(rng.randn(d).astype(np.float32) * 0.1)
    z = jnp.asarray(rng.randn(d).astype(np.float32) * 0.02)
    idx = jnp.asarray(rng.randint(0, n_k, size=(M, b)), jnp.int32)
    return csr, jnp.asarray(y), w, z, idx


@pytest.mark.parametrize("regime", [(0.0, 1e-3), (1e-2, 1e-3), (1e-2, 0.0),
                                    (0.0, 0.0)],
                         ids=["pure_l1", "elastic", "ridge", "unreg"])
@pytest.mark.parametrize("b", [1, 2])
def test_fused_epoch_matches_reference(regime, b):
    lam1, lam2 = regime
    reg = Regularizer(lam1, lam2)
    csr, y, w, z, idx = _epoch_args(b=b)
    u_ref = _lazy_inner_loop_ref(logistic_h_prime, reg, 0.4, w, w, z,
                                 csr.vals, csr.cols, y, idx)
    u_fused = _lazy_inner_loop(logistic_h_prime, reg, 0.4, w, w, z,
                               csr.vals, csr.cols, y, idx)
    np.testing.assert_allclose(np.asarray(u_fused), np.asarray(u_ref),
                               atol=5e-6, rtol=1e-4)


@given(st.floats(1e-4, 5e-2), st.floats(0.0, 5e-2), st.floats(0.05, 0.8),
       st.integers(0, 3), st.sampled_from([1, 2]))
@settings(max_examples=10, deadline=None)
def test_fused_epoch_property(lam2, lam1, eta, seed, b):
    """Property check over the (lam1, lam2, eta, seed, b) box."""
    reg = Regularizer(lam1, lam2)
    csr, y, w, z, idx = _epoch_args(seed=seed, b=b)
    u_ref = _lazy_inner_loop_ref(logistic_h_prime, reg, eta, w, w, z,
                                 csr.vals, csr.cols, y, idx)
    u_fused = _lazy_inner_loop(logistic_h_prime, reg, eta, w, w, z,
                               csr.vals, csr.cols, y, idx)
    scale = float(np.max(np.abs(np.asarray(u_ref)))) + 1e-6
    np.testing.assert_allclose(np.asarray(u_fused), np.asarray(u_ref),
                               atol=2e-5 * scale, rtol=2e-4)


@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("regime", [(0.0, 1e-3), (1e-2, 1e-3)],
                         ids=["pure_l1", "elastic"])
def test_pallas_epoch_kernel_matches_jnp(monkeypatch, b, regime):
    """The whole-epoch Pallas kernel (interpret mode) == the jnp scan."""
    lam1, lam2 = regime
    reg = Regularizer(lam1, lam2)
    csr, y, w, z, idx = _epoch_args(b=b, density=0.1)
    ref = _lazy_inner_loop(logistic_h_prime, reg, 0.3, w, w, z,
                           csr.vals, csr.cols, y, idx)
    monkeypatch.setenv("USE_PALLAS", "1")
    monkeypatch.setenv("REPRO_SPARSE_INNER_KERNEL", "1")
    via_kernel = _lazy_inner_loop(logistic_h_prime, reg, 0.3, w, w, z,
                                  csr.vals, csr.cols, y, idx)
    np.testing.assert_allclose(np.asarray(via_kernel), np.asarray(ref),
                               atol=5e-6, rtol=1e-4)


@pytest.mark.parametrize("b", [1, 3])
def test_pallas_epoch_kernel_duplicate_columns(monkeypatch, b):
    """Rows with repeated columns: the kernel's fold into representative
    slots == the jnp scan's segment-sum."""
    rng = np.random.RandomState(5)
    n_k, d, k, M = 12, 97, 9, 20
    vals, cols = _random_shard(rng, n_k, d, k)
    y = jnp.asarray(np.sign(rng.randn(n_k)).astype(np.float32))
    w = jnp.asarray(rng.randn(d).astype(np.float32) * 0.1)
    z = jnp.asarray(rng.randn(d).astype(np.float32) * 0.02)
    idx = jnp.asarray(rng.randint(0, n_k, size=(M, b)), jnp.int32)
    run = lambda: _lazy_inner_loop(logistic_h_prime, Regularizer(1e-2, 1e-3),
                                   0.3, w, w, z, vals, cols, y, idx)
    ref = run()
    monkeypatch.setenv("USE_PALLAS", "1")
    monkeypatch.setenv("REPRO_SPARSE_INNER_KERNEL", "1")
    np.testing.assert_allclose(np.asarray(run()), np.asarray(ref),
                               atol=5e-6, rtol=1e-4)


@pytest.mark.parametrize("b", [1, 2])
def test_pallas_epoch_kernel_bf16_vals(monkeypatch, b):
    """Encoded shards: the kernel decodes uint16 bf16 bits in VMEM and
    matches the jnp scan, which decodes them at operand-pack time."""
    from repro.data.sparse import bf16_bits_to_f32
    from repro.datasets.codec import bf16_encode
    reg = Regularizer(1e-2, 1e-3)
    csr, y, w, z, idx = _epoch_args(b=b, density=0.1)
    bits = jnp.asarray(bf16_encode(np.asarray(csr.vals)))
    statics = plan_mod.shard_statics(bf16_bits_to_f32(bits), csr.cols,
                                     with_member=False)
    run = lambda: _lazy_inner_loop(logistic_h_prime, reg, 0.3, w, w, z,
                                   bits, csr.cols, y, idx, statics=statics)
    ref = run()
    monkeypatch.setenv("USE_PALLAS", "1")
    monkeypatch.setenv("REPRO_SPARSE_INNER_KERNEL", "1")
    np.testing.assert_allclose(np.asarray(run()), np.asarray(ref),
                               atol=5e-6, rtol=1e-4)


def test_use_pallas_modes_agree(monkeypatch):
    """USE_PALLAS=0 (pure jnp) and =1 produce the same fused trajectory."""
    reg = Regularizer(1e-3, 1e-3)
    csr, y, w, z, idx = _epoch_args()
    outs = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("USE_PALLAS", mode)
        outs[mode] = np.asarray(_lazy_inner_loop(
            logistic_h_prime, reg, 0.4, w, w, z, csr.vals, csr.cols, y, idx))
    np.testing.assert_allclose(outs["0"], outs["1"], atol=5e-6, rtol=1e-4)


# ---------------------------------------------------------------------------
# inner_path="auto"
# ---------------------------------------------------------------------------

def test_auto_picks_measured_winner_on_bench_grid():
    """The calibrated cost model agrees with BENCH_inner_loop.json
    wherever the measured dense/fused margin is decisive (>= 20%)."""
    path = os.path.join(ROOT, "BENCH_inner_loop.json")
    with open(path) as f:
        doc = json.load(f)
    us = doc["us_per_call"]
    nnz_by_tag = {}
    for row in doc["rows"]:
        tag = row["name"].split("/", 2)[-1]
        for part in row["derived"].split(";"):
            if part.startswith("nnz="):
                nnz_by_tag[tag] = int(part[4:])
    checked = 0
    for tag, k in nnz_by_tag.items():
        d = int(tag.split("/")[0][1:])
        t_dense = us.get(f"inner_loop/dense/{tag}")
        t_fused = us.get(f"inner_loop/fused/{tag}")
        if not t_dense or not t_fused:
            continue
        ratio = t_dense / t_fused
        if 0.8 < ratio < 1.2:
            continue  # near-tie: either choice defensible
        want = "lazy" if ratio > 1.0 else "dense"
        got = plan_mod.choose_inner_path(d, 64, 1, k)
        assert got == want, (tag, ratio, got)
        checked += 1
    assert checked >= 4  # the grid must actually exercise the model


def test_auto_falls_back_without_linear_model():
    assert plan_mod.choose_inner_path(1 << 16, 64, 1, 64,
                                      lazy_supported=False) == "dense"


def test_auto_picks_dense_for_dense_data():
    # ~25% density, low dim: the dense engine's regime
    assert plan_mod.choose_inner_path(256, 64, 2, 64) == "dense"


def test_auto_with_csr_input_resolves_to_lazy():
    """CSR data has no dense fallback: auto must resolve to lazy even
    where the cost model would prefer dense (regression: this used to
    raise 'dense inner_path cannot consume CSRMatrix data')."""
    csr, y, _ = make_csr_classification(32, 64, density=0.2, seed=0)
    from repro.data import csr_partition
    csr_p, yp = csr_partition(csr, y, np.arange(32).reshape(2, 16))
    cfg = PScopeConfig(eta=0.4, inner_steps=8, outer_steps=2,
                       inner_path="auto")
    w, hist = pscope.run(LOGISTIC, Regularizer(0.0, 1e-3), csr_p, yp,
                         jnp.zeros(64), cfg)
    assert np.isfinite(hist[-1]) and hist[-1] < hist[0]


def test_run_resolves_auto_path():
    X, y, _ = make_sparse_classification(96, 64, density=0.2, seed=0)
    Xp, yp = stack_partition(jnp.asarray(X), jnp.asarray(y),
                             uniform_partition(jax.random.PRNGKey(0), 96, 2))
    cfg = PScopeConfig(eta=0.4, inner_steps=16, outer_steps=2,
                       inner_path="auto")
    w, hist = pscope.run(LOGISTIC, Regularizer(1e-3, 1e-3), Xp, yp,
                         jnp.zeros(64), cfg)
    assert np.isfinite(hist[-1]) and hist[-1] < hist[0]


# ---------------------------------------------------------------------------
# scanned zero-sync driver
# ---------------------------------------------------------------------------

def _driver_pair(inner_path, participation=None, obj=LOGISTIC, seed=0):
    X, y, _ = make_sparse_classification(128, 96, density=0.05, seed=seed)
    idx = uniform_partition(jax.random.PRNGKey(seed), 128, 4)
    Xp, yp = stack_partition(jnp.asarray(X), jnp.asarray(y), idx)
    reg = Regularizer(1e-3, 1e-3)
    cfg = PScopeConfig(eta=0.4, inner_steps=24, outer_steps=4, seed=seed,
                      inner_path=inner_path)
    w_s, h_s = pscope.run(obj, reg, Xp, yp, jnp.zeros(96), cfg,
                          participation_schedule=participation,
                          driver="scan")
    w_p, h_p = pscope.run(obj, reg, Xp, yp, jnp.zeros(96), cfg,
                          participation_schedule=participation,
                          driver="python")
    return w_s, h_s, w_p, h_p


@pytest.mark.parametrize("inner_path", ["dense", "lazy"])
def test_scanned_history_equals_python_loop(inner_path):
    w_s, h_s, w_p, h_p = _driver_pair(inner_path)
    np.testing.assert_allclose(h_s, h_p, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(w_s), np.asarray(w_p),
                               atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("record_every", [2, 3, 7])
def test_scanned_record_every_matches_python_loop(record_every):
    """Chunked recording: the scan evaluates the objective only on the
    recorded rounds, and the kept history equals the Python driver's."""
    X, y, _ = make_sparse_classification(96, 64, density=0.06, seed=2)
    idx = uniform_partition(jax.random.PRNGKey(2), 96, 2)
    Xp, yp = stack_partition(jnp.asarray(X), jnp.asarray(y), idx)
    reg = Regularizer(1e-3, 1e-3)
    cfg = PScopeConfig(eta=0.4, inner_steps=16, outer_steps=5, seed=2)
    w_s, h_s = pscope.run(LOGISTIC, reg, Xp, yp, jnp.zeros(64), cfg,
                          record_every=record_every, driver="scan")
    w_p, h_p = pscope.run(LOGISTIC, reg, Xp, yp, jnp.zeros(64), cfg,
                          record_every=record_every, driver="python")
    assert len(h_s) == len(h_p) == 5 // record_every + 1
    np.testing.assert_allclose(h_s, h_p, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(w_s), np.asarray(w_p),
                               atol=1e-6, rtol=1e-5)


def test_scanned_driver_with_participation_schedule():
    sched = lambda t: jnp.asarray([1.0, 1.0, 0.0 if t % 2 else 1.0, 1.0])
    w_s, h_s, w_p, h_p = _driver_pair("dense", participation=sched)
    np.testing.assert_allclose(h_s, h_p, rtol=1e-6, atol=1e-7)


def test_run_scanned_returns_device_histories():
    X, y, _ = make_sparse_classification(96, 64, density=0.05, seed=1)
    idx = uniform_partition(jax.random.PRNGKey(1), 96, 2)
    Xp, yp = stack_partition(jnp.asarray(X), jnp.asarray(y), idx)
    cfg = PScopeConfig(eta=0.4, inner_steps=16, outer_steps=3,
                       inner_path="lazy")
    w, values, nnzs = pscope.run_scanned(LOGISTIC, Regularizer(0.0, 1e-3),
                                         Xp, yp, jnp.zeros(64), cfg)
    assert values.shape == (4,) and nnzs.shape == (4,)
    assert values[-1] < values[0]
    assert 0 <= nnzs[-1] <= 64
    # nnz history matches the final iterate's actual sparsity
    assert nnzs[-1] == int(np.sum(np.abs(w) > pscope.NNZ_TOL))


def test_scan_driver_rejects_on_record():
    X, y, _ = make_sparse_classification(32, 16, density=0.2, seed=0)
    Xp, yp = jnp.asarray(X)[None], jnp.asarray(y)[None]
    with pytest.raises(ValueError, match="on_record"):
        pscope.run(LOGISTIC, Regularizer(0.0, 1e-3), Xp, yp, jnp.zeros(16),
                   PScopeConfig(outer_steps=1), driver="scan",
                   on_record=lambda w, v: None)


# ---------------------------------------------------------------------------
# Trace wall-clock fix + post-hoc history feeding
# ---------------------------------------------------------------------------

def test_trace_subtracts_recording_overhead():
    from repro.core.solvers import Trace
    tr = Trace(solver="x", objective="o", partition="p", p=1, d=4).start()
    w = jnp.ones((200_000,))
    for i in range(3):
        tr.record(w, float(i), 1.0)
    assert tr.overhead_seconds > 0.0
    # the recorded solver time excludes the NNZ reductions done above
    import time as _time
    raw_elapsed = _time.perf_counter() - tr._t0
    assert tr.seconds[-1] <= raw_elapsed - tr.overhead_seconds + 1e-3
    tr.w_final = w
    tr.validate()


def test_trace_record_history_post_hoc():
    from repro.core.solvers import Trace
    tr = Trace(solver="x", objective="o", partition="p", p=2, d=8)
    values = [3.0, 2.0, 1.5]
    nnzs = [8, 6, 5]
    tr.record_history(values, nnzs, comm_per_record=2.0, total_seconds=1.0)
    assert tr.values == values and tr.nnz == nnzs
    assert tr.comm == [0.0, 2.0, 4.0]
    np.testing.assert_allclose(tr.seconds, [0.0, 0.5, 1.0])
    tr.w_final = jnp.zeros(8)
    tr.validate()


def test_solvers_pscope_runs_through_scanned_driver():
    """The registry pscope adapters feed the Trace from device history."""
    from repro.core import solvers
    from repro.core.partition import build_partition
    X, y, _ = make_sparse_classification(96, 48, density=0.1, seed=0)
    part = build_partition("uniform", X, y, 2)
    tr = solvers.run("pscope", LOGISTIC, Regularizer(1e-3, 1e-3), part,
                     solvers.SolverConfig(rounds=3, inner_epochs=0.5))
    assert tr.rounds == 3
    assert len(tr.nnz) == 4 and all(n >= 0 for n in tr.nnz)
    assert tr.values[-1] < tr.values[0]
