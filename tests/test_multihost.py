"""Multi-host mesh layer tests: MeshSpec, per-host shard slicing, and
real forked-process `jax.distributed` runs.

The acceptance centerpiece: a REAL 2-process gloo run over a committed
`ShardStore` — each rank mapping only its worker extents — produces a
trace matching the single-process `run_scanned` trajectory within fp32
tolerance, with every rank's history bit-identical and per-round comm
bytes independent of n.  Device-count-dependent legs run in child
processes (jax pins the backend at first use); see
`tests/distributed_harness.py`.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from distributed_harness import (ROOT, multihost, run_forced_devices,
                                 run_multihost)

# Keep jax single-device in THIS process: everything device-shaped runs
# in children.  Importing repro modules here is fine (import is
# device-state free by design).
from repro.launch.mesh import MeshSpec, comm_bytes_per_round, solver_rules
from repro.core.pscope import COMM_ALLREDUCES_PER_ROUND

FIXTURE_D = 32
FIXTURE_KW = dict(eta=0.5, inner_steps=48, inner_batch=2, outer_steps=4)


# ---------------------------------------------------------------------------
# fixture stores
# ---------------------------------------------------------------------------

def _build_store(root, n=256, d=FIXTURE_D, p=4, density=0.3, seed=0):
    from repro.data.sparse import dense_to_csr
    from repro.data.synthetic import make_sparse_classification
    from repro.datasets.libsvm import write_libsvm
    from repro.datasets.shards import ingest_libsvm

    X, y, _ = make_sparse_classification(n, d, density=density, seed=seed)
    csr = dense_to_csr(np.asarray(X))
    os.makedirs(root, exist_ok=True)
    svm = os.path.join(root, "data.svm")
    write_libsvm(svm, np.asarray(csr.vals), np.asarray(csr.cols),
                 np.asarray(csr.row_nnz), np.asarray(y))
    return ingest_libsvm(svm, os.path.join(root, "shards"), p=p,
                         n_features=d)


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    return _build_store(str(tmp_path_factory.mktemp("mh-store")))


@pytest.fixture(scope="module")
def reference_trace(store):
    """Single-process run_scanned trajectory over the full store."""
    import jax.numpy as jnp

    from repro.core import LOGISTIC, PScopeConfig, Regularizer
    from repro.core.pscope import run_scanned

    cfg = PScopeConfig(**FIXTURE_KW, inner_path="lazy")
    _, values, nnz = run_scanned(LOGISTIC, Regularizer(1e-3, 1e-3),
                                 store.csr_p, np.asarray(store.yp),
                                 jnp.zeros(store.d), cfg)
    return values, nnz


@pytest.fixture(scope="module")
def codec_store_pair(tmp_path_factory):
    """raw/codec twin stores ingested from ONE bf16-representable
    LIBSVM text — their decoded views (and hence solver traces) are
    bitwise comparable."""
    from repro.data.sparse import dense_to_csr
    from repro.data.synthetic import make_sparse_classification
    from repro.datasets.codec import bf16_decode, bf16_encode
    from repro.datasets.libsvm import write_libsvm
    from repro.datasets.shards import ingest_libsvm

    root = tmp_path_factory.mktemp("mh-codec")
    X, y, _ = make_sparse_classification(256, FIXTURE_D, density=0.3,
                                         seed=1)
    X = bf16_decode(bf16_encode(np.asarray(X, np.float32)))
    csr = dense_to_csr(X)
    svm = root / "data.svm"
    write_libsvm(svm, np.asarray(csr.vals), np.asarray(csr.cols),
                 np.asarray(csr.row_nnz), np.asarray(y))
    raw = ingest_libsvm(svm, root / "raw", p=4, n_features=FIXTURE_D)
    enc = ingest_libsvm(svm, root / "enc", p=4, n_features=FIXTURE_D,
                        codec="delta+bf16")
    return raw, enc


# ---------------------------------------------------------------------------
# MeshSpec: declarative layout / mesh-shape separation
# ---------------------------------------------------------------------------

def test_meshspec_for_workers():
    spec = MeshSpec.for_workers(4)
    assert spec.shape == (4,) and spec.axes == ("workers",)
    assert spec.num_devices == 4 and spec.num_workers == 4
    assert spec.workers_axis == "workers"


def test_meshspec_pspec_maps_logical_axes():
    from jax.sharding import PartitionSpec as P
    spec = MeshSpec.for_workers(2, axis="data")
    assert spec.pspec("workers") == P("data")
    assert spec.pspec("features") == P(None)
    assert spec.pspec("workers", "features") == P("data", None)
    with pytest.raises(ValueError, match="unknown logical"):
        spec.pspec("heads")


def test_meshspec_rejects_bad_layout_axis():
    with pytest.raises(ValueError, match="unknown mesh axis"):
        MeshSpec(shape=(2,), axes=("workers",),
                 layout={"workers": "model"})


def test_meshspec_rejects_rank_mismatch():
    with pytest.raises(ValueError, match="disagree in rank"):
        MeshSpec(shape=(2, 2), axes=("workers",))
    with pytest.raises(ValueError, match="duplicate"):
        MeshSpec(shape=(2, 2), axes=("workers", "workers"))


def test_meshspec_workers_axis_required_for_call():
    spec = MeshSpec(shape=(2,), axes=("model",),
                    layout={"workers": None, "features": "model"})
    with pytest.raises(ValueError, match="replicates 'workers'"):
        spec.workers_axis


def test_meshspec_build_checks_device_count():
    out = run_forced_devices(4, """
        from repro.launch.mesh import MeshSpec
        mesh = MeshSpec.for_workers(4).build()
        assert mesh.shape == {"workers": 4}, mesh.shape
        try:
            MeshSpec.for_workers(8).build()
        except ValueError as e:
            assert "8 devices" in str(e), e
            print("OK")
    """)
    assert "OK" in out


def test_solver_rules_layout():
    rules = solver_rules()
    assert rules["workers"] == "workers" and rules["features"] is None
    assert solver_rules(workers_axis="data")["workers"] == "data"


def test_comm_bytes_per_round_is_o_d_only():
    """The analytic wire cost: 2 d-vector all-reduces, no n anywhere."""
    d = 1 << 14
    assert comm_bytes_per_round(d) == COMM_ALLREDUCES_PER_ROUND * d * 4
    assert comm_bytes_per_round(2 * d) == 2 * comm_bytes_per_round(d)


# ---------------------------------------------------------------------------
# ShardStore.local_slice: per-host mapping with offset accounting
# ---------------------------------------------------------------------------

SEG_KEYS = ("vals", "cols", "row_nnz", "labels", "members")
_VIEW = {"labels": "yp"}


def _slice_view(sl, key):
    return getattr(sl, _VIEW.get(key, key))


def _store_view(store, key):
    return np.asarray(getattr(store, _VIEW.get(key, key)))


def test_local_slice_round_trip_ingested(store):
    """Concatenating all hosts' slices reproduces every segment exactly."""
    hosts = [(0, 1), (2,), (3,)]
    for key in SEG_KEYS:
        cat = np.concatenate(
            [_slice_view(store.local_slice(ids), key) for ids in hosts])
        np.testing.assert_array_equal(cat, _store_view(store, key))
    # and the CSR view feeds the solver layout unchanged
    sl = store.local_slice((1, 2))
    assert sl.csr.d == store.d
    np.testing.assert_array_equal(sl.csr.vals, store.vals[1:3])


def test_local_slice_offset_accounting(store):
    """A host maps exactly its owned byte ranges — never a foreign one."""
    from repro.datasets.shards import _SEGMENTS
    sl = store.local_slice((1, 2))
    for key in SEG_KEYS:
        _slice_view(sl, key)             # materialize the mapping
        fname, _ = _SEGMENTS[key]
        owned = sl.owned_extents(key)
        assert sl.mapped_ranges[fname] == owned
        # owned ranges == exactly the extents of workers 1..2
        o1, s1 = store.segment_extent(key, 1)
        assert owned == [(o1, 2 * s1)]
        # and disjoint from every foreign worker's extent
        for w in (0, 3):
            off, ln = store.segment_extent(key, w)
            for mo, ml in sl.mapped_ranges[fname]:
                assert mo + ml <= off or mo >= off + ln


def test_local_slice_contiguous_run_is_zero_copy(store):
    sl = store.local_slice((2, 3))
    v = sl.vals
    assert isinstance(v, np.memmap)
    assert v.offset == store.segment_extent("vals", 2)[0]
    np.testing.assert_array_equal(v, store.vals[2:4])


def test_local_slice_noncontiguous_and_empty(store):
    sl = store.local_slice((0, 3))
    np.testing.assert_array_equal(sl.vals[0], store.vals[0])
    np.testing.assert_array_equal(sl.vals[1], store.vals[3])
    assert len(sl.mapped_ranges["vals.f32"]) == 2
    empty = store.local_slice(())
    assert empty.vals.shape == (0, store.n_k, store.max_nnz)
    assert empty.csr.vals.shape[0] == 0
    assert empty.mapped_ranges["vals.f32"] == []


def test_local_slice_validates_worker_ids(store):
    with pytest.raises(ValueError, match="strictly increasing"):
        store.local_slice((2, 1))
    with pytest.raises(ValueError, match="strictly increasing"):
        store.local_slice((1, 1))
    with pytest.raises(ValueError, match="outside"):
        store.local_slice((0, 17))
    with pytest.raises(ValueError, match="outside"):
        store.local_slice((-1,))


def _write_raw_store(root, vals, cols, row_nnz, labels, members):
    """Commit a store directly from arrays (manifest-last, as ingest)."""
    from repro.datasets.shards import MANIFEST, SCHEMA, _SEGMENTS, open_store
    os.makedirs(root, exist_ok=True)
    p, n_k, K = vals.shape
    arrays = {"vals": vals, "cols": cols, "row_nnz": row_nnz,
              "labels": labels, "members": members}
    for key, (fname, dtype) in _SEGMENTS.items():
        np.ascontiguousarray(arrays[key]).astype(dtype).tofile(
            os.path.join(root, fname))
    manifest = {"schema": SCHEMA, "p": p, "n_k": n_k,
                "d": int(cols.max(initial=0)) + 1, "max_nnz": K,
                "placement": "raw", "counts": [n_k] * p}
    with open(os.path.join(root, MANIFEST), "w") as f:
        json.dump(manifest, f)
    return open_store(root)


def _random_raw_store(root, rng, p, n_k, K):
    """Uneven row_nnz (incl. all-empty 'workers') + padding edges."""
    row_nnz = rng.integers(0, K + 1, size=(p, n_k)).astype(np.int32)
    if p > 1:
        row_nnz[rng.integers(0, p)] = 0          # an empty worker
    vals = rng.standard_normal((p, n_k, K)).astype(np.float32)
    cols = rng.integers(0, 64, size=(p, n_k, K)).astype(np.int32)
    mask = np.arange(K)[None, None, :] < row_nnz[..., None]
    vals *= mask
    cols *= mask
    labels = rng.choice([-1.0, 1.0], size=(p, n_k)).astype(np.float32)
    members = rng.permutation(p * n_k).reshape(p, n_k).astype(np.int64)
    return _write_raw_store(root, vals, cols, row_nnz, labels, members)


def _encode_raw_store(store, block_rows=2):
    """Re-encode a committed raw store in place with the segment codec
    and reopen it — the test-side analogue of `codec=` at ingest."""
    from repro.datasets.shards import MANIFEST, _encode_store, open_store
    mf = dict(store.manifest)
    mf["codec"] = _encode_store(store.root, mf["p"], mf["n_k"],
                                mf["max_nnz"], "delta+bf16", block_rows)
    with open(store.root / MANIFEST, "w") as f:
        json.dump(mf, f)
    return open_store(store.root)


def _host_partition(rng, p, hosts):
    ids = np.arange(p)
    cuts = np.sort(rng.choice(np.arange(1, p), size=hosts - 1,
                              replace=False)) if hosts > 1 else []
    return [tuple(int(w) for w in part)
            for part in np.split(ids, cuts)]


def _assert_slices_tile_store(st_obj):
    store, hosts = st_obj
    for key in SEG_KEYS:
        cat = np.concatenate(
            [_slice_view(store.local_slice(ids), key) for ids in hosts]
            or [np.zeros((0,))])
        np.testing.assert_array_equal(cat, _store_view(store, key))
    for ids in hosts:
        sl = store.local_slice(ids)
        for key in SEG_KEYS:
            _slice_view(sl, key)
            # codec-aware: packed segments live in their codec file
            fname = store._seg_info(key)[0]
            assert sl.mapped_ranges[fname] == sl.owned_extents(key)
            total = sum(ln for _, ln in sl.mapped_ranges[fname])
            assert total == sum(store.segment_extent(key, w)[1]
                                for w in ids)
            size = os.path.getsize(store.root / fname)
            assert all(0 <= off and off + ln <= size
                       for off, ln in sl.mapped_ranges[fname])


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 6), st.integers(1, 5), st.integers(1, 4),
       st.integers(0, 2 ** 31 - 1))
def test_local_slice_round_trip_property(p, n_k, K, seed):
    """Hypothesis: any worker-major manifest (uneven extents, empty
    workers, padding edges) round-trips — host slices tile the store
    exactly, and mapped bytes never exceed owned extents."""
    import tempfile
    rng = np.random.default_rng(seed)
    with tempfile.TemporaryDirectory() as tmp:
        store = _random_raw_store(tmp, rng, p, n_k, K)
        hosts = _host_partition(rng, p, hosts=int(rng.integers(1, p + 1)))
        _assert_slices_tile_store((store, hosts))
        # same invariants over the compressed extents of the codec store
        _assert_slices_tile_store((_encode_raw_store(store), hosts))


def test_local_slice_round_trip_seeded_sweep(tmp_path):
    """The deterministic leg of the property above (runs without
    hypothesis installed): a seeded sweep over shapes/partitions."""
    for i, (p, n_k, K) in enumerate([(1, 1, 1), (3, 2, 1), (5, 4, 3),
                                     (6, 1, 4), (4, 5, 2)]):
        rng = np.random.default_rng(100 + i)
        store = _random_raw_store(str(tmp_path / f"s{i}"), rng, p, n_k, K)
        partitions = [_host_partition(np.random.default_rng(i * 7 + h),
                                      p, h) for h in range(1, p + 1)]
        for hosts in partitions:
            _assert_slices_tile_store((store, hosts))
        enc = _encode_raw_store(store)       # mutates the dir in place
        for hosts in partitions:
            _assert_slices_tile_store((enc, hosts))


# ---------------------------------------------------------------------------
# In-process mesh legs (forced host devices, subprocess isolated)
# ---------------------------------------------------------------------------

def test_run_mesh_store_matches_run_scanned(store):
    """4 forced devices, single process: the mesh driver over the mmap
    store == run_scanned over csr_p (fp32 tol), nnz bit-equal."""
    out = run_forced_devices(4, f"""
        import numpy as np, jax.numpy as jnp
        from repro.core import Regularizer, LOGISTIC, PScopeConfig
        from repro.core.pscope import run_scanned
        from repro.launch.mesh import MeshSpec, run_mesh
        from repro.datasets.shards import open_store

        store = open_store({str(store.root)!r})
        reg = Regularizer(1e-3, 1e-3)
        cfg = PScopeConfig(**{FIXTURE_KW!r}, inner_path="lazy")
        res = run_mesh(LOGISTIC, reg, store, None, jnp.zeros(store.d), cfg,
                       MeshSpec.for_workers(store.p))
        _, v_ref, nnz_ref = run_scanned(LOGISTIC, reg, store.csr_p,
                                        np.asarray(store.yp),
                                        jnp.zeros(store.d), cfg)
        assert np.allclose(res.values, v_ref, rtol=1e-5, atol=1e-5), (
            res.values, v_ref)
        assert np.array_equal(res.nnz, nnz_ref)
        assert res.values[-1] < res.values[0] - 0.02
        print("OK", float(np.max(np.abs(res.values - v_ref))))
    """)
    assert "OK" in out


def test_run_mesh_dense_matches_run_scanned():
    """The dense inner path through the mesh driver (auto resolves to
    dense for dense worker-major blocks)."""
    out = run_forced_devices(4, f"""
        import numpy as np, jax.numpy as jnp
        from repro.core import Regularizer, LOGISTIC, PScopeConfig
        from repro.core.pscope import run_scanned
        from repro.launch.mesh import run_mesh
        from repro.data.synthetic import make_sparse_classification

        X, y, _ = make_sparse_classification(256, 32, density=0.3, seed=0)
        Xp = np.asarray(X).reshape(4, 64, 32)
        yp = np.asarray(y).reshape(4, 64)
        reg = Regularizer(1e-3, 1e-3)
        cfg = PScopeConfig(**{FIXTURE_KW!r}, inner_path="auto")
        res = run_mesh(LOGISTIC, reg, Xp, yp, jnp.zeros(32), cfg)
        _, v_ref, _ = run_scanned(LOGISTIC, reg, jnp.asarray(Xp),
                                  jnp.asarray(yp), jnp.zeros(32),
                                  PScopeConfig(**{FIXTURE_KW!r},
                                               inner_path="dense"))
        assert np.allclose(res.values, v_ref, rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_pscope_mesh_registry_comm_accounting():
    """`Trace.comm` under the mesh driver == analytic per-round bytes
    (one gradient psum + one iterate broadcast), values == pscope_lazy."""
    out = run_forced_devices(4, """
        import numpy as np
        from repro.core import solvers, Regularizer, LOGISTIC
        from repro.core.solvers import SolverConfig
        from repro.core.partition import build_partition
        from repro.data.synthetic import make_sparse_classification
        from repro.launch.mesh import comm_bytes_per_round

        X, y, _ = make_sparse_classification(256, 32, density=0.2, seed=0)
        part = build_partition("uniform", X, y, 4)
        reg = Regularizer(1e-3, 1e-3)
        cfg = SolverConfig(rounds=3, inner_epochs=0.5)
        tr = solvers.run("pscope_mesh", LOGISTIC, reg, part, cfg)
        per_round = comm_bytes_per_round(32)
        assert tr.meta["comm_units"] == "bytes"
        incs = np.diff(tr.comm)
        assert np.all(incs == per_round), tr.comm
        assert tr.comm[-1] == cfg.rounds * per_round
        assert tr.meta["mesh"]["devices"] == [0, 1, 2, 3], tr.meta
        tr_lazy = solvers.run("pscope_lazy", LOGISTIC, reg, part, cfg)
        assert np.allclose(tr.values, tr_lazy.values, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_init_retries_let_a_type_error_through(monkeypatch):
    """A wrong argument to the JAX initializer is raised at once, not
    retried and relabelled as an unreachable coordinator."""
    import jax
    from repro.launch import mesh as mesh_mod
    calls = []

    def bad_initialize(**kw):
        calls.append(kw)
        raise TypeError("unexpected keyword argument")

    monkeypatch.setattr(jax.distributed, "initialize", bad_initialize)
    with pytest.raises(TypeError, match="unexpected keyword"):
        mesh_mod._init_with_retries("localhost:1", 2, 0, timeout=1.0,
                                    retries=3, backoff_s=0.0, elastic=False,
                                    heartbeat_timeout_s=60)
    assert len(calls) == 1


def test_elastic_init_arguments_match_installed_jax(monkeypatch):
    """The elastic bring-up passes only arguments the installed JAX's
    distributed-state initializer accepts."""
    import inspect

    from jax._src import distributed as dist
    from repro.launch import mesh as mesh_mod
    sig = inspect.signature(dist.State.initialize)
    seen = {}

    def checked_initialize(**kw):
        sig.bind(dist.global_state, **kw)      # TypeError if JAX moved on
        seen.update(kw)

    monkeypatch.setattr(dist.global_state, "initialize", checked_initialize)
    mesh_mod._init_with_retries("localhost:1", 2, 0, timeout=1.0, retries=1,
                                backoff_s=0.0, elastic=True,
                                heartbeat_timeout_s=86400)
    assert seen["heartbeat_timeout_seconds"] == 86400


def test_distributed_runtime_constructors_exist():
    """The client-only and service-host paths build the runtime from
    these; each accepts the heartbeat timeout they pass."""
    from jax._src.lib import _jax
    for name in ("get_distributed_runtime_client",
                 "get_distributed_runtime_service"):
        assert "heartbeat_timeout" in getattr(_jax, name).__doc__, name


def test_comm_bytes_independent_of_n(tmp_path):
    """Regression pin of the paper's communication-efficiency claim:
    per-round bytes depend on d only — doubling n changes nothing."""
    small = _build_store(str(tmp_path / "small"), n=128)
    big = _build_store(str(tmp_path / "big"), n=512, seed=1)
    out = run_forced_devices(4, f"""
        import numpy as np, jax.numpy as jnp
        from repro.core import Regularizer, LOGISTIC, PScopeConfig
        from repro.launch.mesh import run_mesh
        from repro.datasets.shards import open_store

        reg = Regularizer(1e-3, 1e-3)
        cfg = PScopeConfig(**{FIXTURE_KW!r}, inner_path="lazy")
        comm = []
        for root in ({str(small.root)!r}, {str(big.root)!r}):
            store = open_store(root)
            res = run_mesh(LOGISTIC, reg, store, None,
                           jnp.zeros(store.d), cfg)
            comm.append(res.comm_bytes_per_round)
        assert comm[0] == comm[1], comm
        print("OK", comm[0])
    """)
    assert "OK" in out


def test_hlo_collective_bytes_independent_of_n():
    """Audit the analytic model against the COMPILED program: the outer
    step's all-reduce bytes (from HLO) are identical for n and 2n, and
    scale linearly in d — bytes-on-wire per round = O(d), not O(n)."""
    out = run_forced_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import Regularizer, LOGISTIC, PScopeConfig
        from repro.core.pscope import (make_distributed_outer_step_core,
                                       init_state)
        from repro.launch import roofline as rf

        mesh = jax.make_mesh((4,), ("workers",))
        reg = Regularizer(1e-3, 1e-3)

        def allreduce_bytes(n, d):
            cfg = PScopeConfig(eta=0.5, inner_steps=16, outer_steps=1)
            step = make_distributed_outer_step_core(LOGISTIC, reg, cfg,
                                                    mesh, "workers")
            X = jnp.zeros((n, d)); y = jnp.zeros((n,))
            c = (jax.jit(step)
                 .lower(init_state(jnp.zeros(d)), X, y, None).compile())
            costs = rf.analyze_hlo(c.as_text())
            return costs.op_bytes.get("all-reduce", 0.0)

        b_n = allreduce_bytes(256, 32)
        b_2n = allreduce_bytes(512, 32)
        b_2d = allreduce_bytes(256, 64)
        assert b_n > 0
        assert b_n == b_2n, (b_n, b_2n)            # independent of n
        assert abs(b_2d - 2 * b_n) <= 0.1 * b_n, (b_n, b_2d)   # O(d)
        print("OK", b_n, b_2d)
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Forked multi-process legs (real jax.distributed + gloo collectives)
# ---------------------------------------------------------------------------

def test_forked_2proc_psum_sanity(multihost):
    """Harness sanity: a cross-process psum returns the true global sum
    on every rank."""
    results = multihost(2, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        def main():
            mesh = Mesh(np.asarray(jax.devices()), ("workers",))
            me = jax.process_index()
            local = jnp.full((1,), float(me + 1))
            arr = jax.make_array_from_single_device_arrays(
                (2,), NamedSharding(mesh, P("workers")),
                [jax.device_put(local, jax.local_devices()[0])])
            total = jax.jit(jnp.sum,
                            out_shardings=NamedSharding(mesh, P()))(arr)
            return {"rank": me, "sum": float(total)}
    """, timeout=300)
    assert [r["rank"] for r in results] == [0, 1]
    assert all(r["sum"] == 3.0 for r in results)


def test_forked_2proc_mesh_matches_single_process(store, reference_trace,
                                                  multihost):
    """THE acceptance test: a real 2-process jax.distributed run (2
    forced devices per rank -> each host maps 2 of the 4 worker
    extents) reproduces the single-process run_scanned trace within
    fp32 tolerance; all ranks' traces are bit-identical; comm bytes
    per round are the analytic O(d) figure."""
    results = multihost(2, f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import Regularizer, LOGISTIC, PScopeConfig
        from repro.launch.mesh import MeshSpec, run_mesh
        from repro.datasets.shards import open_store

        def main():
            store = open_store({str(store.root)!r})
            cfg = PScopeConfig(**{FIXTURE_KW!r}, inner_path="lazy")
            res = run_mesh(LOGISTIC, Regularizer(1e-3, 1e-3), store, None,
                           jnp.zeros(store.d), cfg)
            return {{"rank": res.process_id,
                     "owned": list(res.worker_ids),
                     "values": res.values.tolist(),
                     "nnz": res.nnz.tolist(),
                     "comm": res.comm_bytes_per_round}}
    """, devices_per_process=2, timeout=600)
    v_ref, nnz_ref = reference_trace
    assert [r["rank"] for r in results] == [0, 1]
    # per-host shard mapping: disjoint cover of the 4 workers
    assert results[0]["owned"] == [0, 1] and results[1]["owned"] == [2, 3]
    # bit-identical across ranks
    assert results[0]["values"] == results[1]["values"]
    assert results[0]["nnz"] == results[1]["nnz"]
    # fp32-tolerance match of the single-process trajectory
    np.testing.assert_allclose(results[0]["values"], v_ref,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(results[0]["nnz"], nnz_ref)
    assert results[0]["comm"] == comm_bytes_per_round(FIXTURE_D)


def test_forked_2proc_mesh_codec_store(codec_store_pair, multihost):
    """A real 2-process jax.distributed run over a COMPRESSED store:
    each rank maps only its packed extents and decode happens inside
    the epoch gather, yet the trace matches the single-process
    run_scanned trajectory over the raw twin (same bf16-representable
    source text, so the decoded bits agree exactly)."""
    import jax.numpy as jnp
    from repro.core import LOGISTIC, PScopeConfig, Regularizer
    from repro.core.pscope import run_scanned

    raw, enc = codec_store_pair
    cfg = PScopeConfig(**FIXTURE_KW, inner_path="lazy")
    _, v_ref, nnz_ref = run_scanned(LOGISTIC, Regularizer(1e-3, 1e-3),
                                    raw.csr_p, np.asarray(raw.yp),
                                    jnp.zeros(raw.d), cfg)
    results = multihost(2, f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import Regularizer, LOGISTIC, PScopeConfig
        from repro.launch.mesh import run_mesh
        from repro.datasets.shards import open_store

        def main():
            store = open_store({str(enc.root)!r})
            assert store.codec is not None
            cfg = PScopeConfig(**{FIXTURE_KW!r}, inner_path="lazy")
            res = run_mesh(LOGISTIC, Regularizer(1e-3, 1e-3), store, None,
                           jnp.zeros(store.d), cfg)
            return {{"rank": res.process_id,
                     "owned": list(res.worker_ids),
                     "values": res.values.tolist(),
                     "nnz": res.nnz.tolist()}}
    """, devices_per_process=2, timeout=600)
    assert [r["rank"] for r in results] == [0, 1]
    assert results[0]["owned"] == [0, 1] and results[1]["owned"] == [2, 3]
    assert results[0]["values"] == results[1]["values"]
    np.testing.assert_allclose(results[0]["values"], v_ref,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(results[0]["nnz"], nnz_ref)


def test_forked_4proc_smoke(store, reference_trace, multihost):
    """4 real processes, one worker each: converges, ranks identical."""
    results = multihost(4, f"""
        import numpy as np, jax.numpy as jnp
        from repro.core import Regularizer, LOGISTIC, PScopeConfig
        from repro.launch.mesh import run_mesh
        from repro.datasets.shards import open_store

        def main():
            store = open_store({str(store.root)!r})
            cfg = PScopeConfig(**{FIXTURE_KW!r}, inner_path="lazy")
            res = run_mesh(LOGISTIC, Regularizer(1e-3, 1e-3), store, None,
                           jnp.zeros(store.d), cfg)
            return {{"owned": list(res.worker_ids),
                     "values": res.values.tolist()}}
    """, timeout=600)
    v_ref, _ = reference_trace
    assert [r["owned"] for r in results] == [[0], [1], [2], [3]]
    assert len({tuple(r["values"]) for r in results}) == 1
    np.testing.assert_allclose(results[0]["values"], v_ref,
                               rtol=1e-5, atol=1e-5)


def test_multihost_cli_spawn_demo(tmp_path):
    """The `python -m repro.launch.multihost --spawn` entry point:
    forks 2 ranks, ingests the demo fixture once (commit-marker wait),
    verifies against run_scanned, asserts bit-identical ranks."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.multihost", "--spawn", "2",
         "--demo", "--verify", "--rounds", "3",
         "--workdir", str(tmp_path / "demo"),
         "--out", str(tmp_path / "trace.json"), "--timeout", "420"],
        env=env, capture_output=True, text=True, timeout=480)
    assert proc.returncode == 0, proc.stdout[-2500:] + proc.stderr[-2500:]
    assert "VERIFY OK" in proc.stdout
    assert "SPAWN OK" in proc.stdout
    trace = json.loads((tmp_path / "trace.json").read_text())
    assert len(trace["values"]) == 4 and trace["num_processes"] == 2
