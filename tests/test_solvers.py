"""The unified solver registry: every registered solver runs through the
single `solvers.run` entry point, decreases the L1-regularized objective
on a small synthetic problem, and emits a well-formed `Trace`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LOGISTIC, Regularizer, solvers
from repro.core.partition import (PARTITION_SCHEMES, Partition,
                                  build_partition)
from repro.core.solvers import SolverConfig, Trace
from repro.data.synthetic import make_sparse_classification

ALL_SOLVERS = ("pscope", "pscope_lazy", "pscope_mesh", "pscope_elastic",
               "fista", "pgd", "prox_svrg", "dpsgd", "dpsvrg", "admm",
               "owlqn", "dbcd", "cocoa")

# per-solver budgets sized so each clearly decreases the objective while
# keeping the whole parametrized sweep CPU-cheap
CONFIGS = {
    "pscope": SolverConfig(rounds=5, inner_epochs=1.0),
    "pscope_lazy": SolverConfig(rounds=5, inner_epochs=1.0),
    "pscope_mesh": SolverConfig(rounds=5, inner_epochs=1.0),
    "pscope_elastic": SolverConfig(rounds=5, inner_epochs=1.0,
                                   extras={"hosts": 2, "fail_at": 2}),
    "fista": SolverConfig(rounds=40),
    "pgd": SolverConfig(rounds=40),
    "prox_svrg": SolverConfig(rounds=4, inner_epochs=0.5),
    "dpsgd": SolverConfig(rounds=10, record_every=10),
    "dpsvrg": SolverConfig(rounds=4),
    "admm": SolverConfig(rounds=25),
    "owlqn": SolverConfig(rounds=20),
    "dbcd": SolverConfig(rounds=40),
    "cocoa": SolverConfig(rounds=40),
}


@pytest.fixture(scope="module")
def prob():
    X, y, _ = make_sparse_classification(384, 32, density=0.3, seed=0)
    part = build_partition("uniform", X, y, 4)
    return LOGISTIC, Regularizer(1e-3, 1e-3), part


def test_registry_is_complete():
    """pSCOPE (both inner engines) + the 9 baselines are registered."""
    assert set(solvers.available()) == set(ALL_SOLVERS)
    assert solvers.available()[0] == "pscope"


def test_spec_metadata():
    for name in solvers.available():
        spec = solvers.get(name)
        assert spec.name == name
        assert spec.summary and spec.paper_ref and spec.comm_model


def test_unknown_solver_raises():
    with pytest.raises(KeyError, match="unknown solver"):
        solvers.get("nope")


@pytest.mark.parametrize("name", ALL_SOLVERS)
def test_solver_decreases_objective_and_traces(prob, name):
    obj, reg, part = prob
    if name == "pscope_mesh" and jax.device_count() < part.p:
        # needs one device per partition worker; the forced-device and
        # forked-process legs in tests/test_multihost.py cover it
        pytest.skip(f"pscope_mesh needs >= {part.p} devices")
    trace = solvers.run(name, obj, reg, part, CONFIGS[name])

    # objective decreases on the L1-regularized problem
    assert np.isfinite(trace.values[-1])
    assert trace.values[-1] < trace.values[0] - 0.02, trace.values[-3:]

    # well-formed Trace: aligned streams, identity fields, monotone
    # cumulative counters, plausible NNZ, final iterate attached
    n = len(trace.values)
    assert n >= 2
    assert len(trace.nnz) == len(trace.comm) == len(trace.seconds) == n
    assert trace.solver == name
    assert trace.objective == obj.name
    assert trace.partition == "uniform"
    assert trace.p == 4 and trace.d == 32
    assert all(np.isfinite(v) for v in trace.values)
    assert all(b >= a for a, b in zip(trace.comm, trace.comm[1:]))
    assert all(b >= a - 1e-6
               for a, b in zip(trace.seconds, trace.seconds[1:]))
    assert trace.comm[0] == 0.0
    assert all(0 <= z <= trace.d for z in trace.nnz)
    assert trace.w_final is not None and trace.w_final.shape == (trace.d,)
    # serial prox-SVRG is the only communication-free solver (Cor. 2)
    if name == "prox_svrg":
        assert trace.comm[-1] == 0.0
    else:
        assert trace.comm[-1] > 0.0


def test_trace_derived_metrics():
    tr = Trace(solver="s", objective="o", partition="pi", p=2, d=4)
    tr.start()
    w = jnp.asarray([1.0, 0.0, 0.5, 0.0])
    tr.record(w, 1.0, 0.0)
    tr.record(w, 0.1, 2.0)
    tr.record(w, 0.01, 2.0)
    tr.validate()
    assert tr.rounds == 2
    assert tr.nnz == [2, 2, 2]
    assert tr.gap(0.0) == pytest.approx(0.01)
    assert tr.rounds_to(0.0, eps=0.1) == 1
    assert tr.comm_to(0.0, eps=0.1) == 2.0
    assert np.isfinite(tr.time_to(0.0, eps=0.1))
    assert tr.time_to(0.0, eps=1e-9) == float("inf")


def test_trace_records_pytrees():
    """A pytree iterate records one NNZ over all of its leaves."""
    tr = Trace(solver="train", objective="lm", partition="pod", p=2, d=0)
    params = {"wq": jnp.asarray([1.0, 0.0]), "mlp": {"w": jnp.zeros((2, 2))}}
    tr.record(params, 3.5, 2.0)
    tr.validate()
    assert tr.nnz == [1]
    assert tr.comm == [2.0]


def test_trace_validate_rejects_malformed():
    tr = Trace(solver="s", objective="o", partition="pi", p=1, d=2)
    with pytest.raises(ValueError, match="empty"):
        tr.validate()
    tr.record(jnp.zeros(2), 1.0)
    tr.nnz.append(0)   # misalign
    with pytest.raises(ValueError, match="misaligned"):
        tr.validate()


def test_partition_schemes_registry(prob):
    """Every named scheme builds a valid Partition for this dataset."""
    obj, reg, part = prob
    X, y = part.X, part.y
    for scheme in PARTITION_SCHEMES:
        built = build_partition(scheme, X, y, 4)
        assert isinstance(built, Partition)
        assert built.name == scheme
        assert built.p == 4
        assert built.Xp.shape == (4, built.n_k, built.d)
        assert built.yp.shape == (4, built.n_k)
    with pytest.raises(KeyError, match="unknown partition scheme"):
        build_partition("nope", X, y, 4)
