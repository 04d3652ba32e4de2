"""Multi-host mesh layer: `jax.distributed` launcher + per-host shards.

This is the CALL cluster story made literal.  The paper's framework
(Section 5) keeps each worker's data partition local for the whole run;
only the d-vector iterate crosses the network, twice per outer round
(one full-gradient all-reduce, one iterate average).  Everything below
exists to preserve that property across *real processes*:

  * `MeshSpec` — declarative layout/mesh-shape separation (the
    tensor2tensor idiom): a mesh *shape* over named device axes plus a
    logical->mesh layout for the solver's two logical axes
    (`workers` / `features`, `SOLVER_LOGICAL_AXES`).  Importing
    this module never touches jax device state; `spec.build()` does.
  * `init_distributed` — `jax.distributed.initialize` with the gloo
    CPU-collectives backend selected, idempotent, env-var defaulted, so
    one entry point serves srun/mpirun-style launchers, the `--spawn`
    convenience forker in `launch.multihost`, and the forked-process
    test harness.
  * per-host shard mapping — `local_worker_ids(mesh)` computes which
    partition workers this process's devices own; the host opens ONLY
    those extents of a PR-5 `ShardStore` (`store.local_slice`, offset
    mmaps: no foreign bytes are ever mapped) and registers each
    worker's block on its device via
    `jax.make_array_from_single_device_arrays`.  The resulting global
    arrays feed the unchanged `pscope.run_distributed_scanned` — the
    outer-round `psum`s lower to real cross-process collectives and the
    zero-sync scanned driver keeps its one-host-transfer-per-run
    property on every host.
  * `comm_bytes_per_round` — the analytic bytes-on-wire of one outer
    round (2 all-reduces of the d-vector): O(d), independent of n.
    `Trace.comm` under the mesh driver records these bytes
    (`core.solvers` "pscope_mesh"); benchmarks/bench_comm.py audits the
    compiled HLO against it.

Hardware constants (TPU v5e-class) used by the roofline stay here.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Mapping, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs

# The solver's two logical axes: `workers` (the partition axis: shard
# rows, labels, statics) and `features` (the d coordinates of w and z,
# unsharded today).
SOLVER_LOGICAL_AXES = ("workers", "features")


def solver_rules(workers_axis: str = "workers",
                 features_axis: Optional[str] = None
                 ) -> Dict[Optional[str], Optional[str]]:
    """Logical->mesh layout for the CALL solver arrays."""
    return {None: None, "workers": workers_axis, "features": features_axis}


# Hardware constants (TPU v5e-class, per chip) used by the roofline —
# re-exported from the canonical machine model in `repro.obs.roofline`
# (same numbers the HLO analyzer and the bench %-of-peak stamps use).
PEAK_FLOPS_BF16 = obs.roofline.TPU_V5E.peak_flops
HBM_BW = obs.roofline.TPU_V5E.hbm_bw
ICI_LINK_BW = obs.roofline.TPU_V5E.ici_bw
DCI_BW = obs.roofline.TPU_V5E.dci_bw
HBM_BYTES = obs.roofline.TPU_V5E.hbm_bytes


# ---------------------------------------------------------------------------
# Declarative mesh layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Mesh shape + logical layout, separated (and device-state free).

    `shape`/`axes` declare the device mesh; `layout` maps the solver's
    logical axes onto mesh axes (None = replicated).  The default
    layout shards `workers` over the first mesh axis and replicates
    `features` — the paper's data-parallel CALL setting.

        spec = MeshSpec.for_workers(8)            # (8,) over "workers"
        mesh = spec.build()                       # uses jax.devices()
        P_rows = spec.pspec("workers")            # rows sharded
        P_w    = spec.pspec("features")           # iterate replicated
    """

    shape: Tuple[int, ...]
    axes: Tuple[str, ...] = ("workers",)
    layout: Optional[Mapping[str, Optional[str]]] = None

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"mesh shape {self.shape} and axes "
                             f"{self.axes} disagree in rank")
        if len(set(self.axes)) != len(self.axes):
            raise ValueError(f"duplicate mesh axis in {self.axes}")
        if any(s < 1 for s in self.shape):
            raise ValueError(f"mesh shape {self.shape} has empty axes")
        for logical, axis in self.resolved_layout.items():
            if axis is not None and axis not in self.axes:
                raise ValueError(
                    f"layout maps logical axis {logical!r} to unknown "
                    f"mesh axis {axis!r} (have {self.axes})")

    @classmethod
    def for_workers(cls, p: int, axis: str = "workers") -> "MeshSpec":
        """The 1-D CALL mesh: p devices, one partition worker each."""
        return cls(shape=(p,), axes=(axis,),
                   layout=solver_rules(workers_axis=axis))

    @property
    def resolved_layout(self) -> Dict[Optional[str], Optional[str]]:
        if self.layout is not None:
            return {None: None, **dict(self.layout)}
        return solver_rules(workers_axis=self.axes[0])

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64))

    @property
    def workers_axis(self) -> str:
        """The mesh axis the `workers` logical axis lives on."""
        axis = self.resolved_layout.get("workers")
        if axis is None:
            raise ValueError("this MeshSpec replicates 'workers'; the CALL "
                             "drivers need it sharded over a mesh axis")
        return axis

    @property
    def num_workers(self) -> int:
        return self.shape[self.axes.index(self.workers_axis)]

    def pspec(self, *logical: Optional[str]) -> P:
        """PartitionSpec for an array whose dims carry `logical` axes."""
        lay = self.resolved_layout
        unknown = [a for a in logical
                   if a is not None and a not in lay]
        if unknown:
            raise ValueError(f"unknown logical axes {unknown}; have "
                             f"{sorted(k for k in lay if k)} "
                             f"(solver axes: {SOLVER_LOGICAL_AXES})")
        return P(*(lay[a] for a in logical))

    def build(self, devices: Optional[Sequence] = None) -> Mesh:
        """Materialize the Mesh over `devices` (default: all global
        devices, in `jax.devices()` order — identical on every process
        of a `jax.distributed` job)."""
        devs = np.asarray(devices if devices is not None else jax.devices())
        if devs.size != self.num_devices:
            raise ValueError(
                f"MeshSpec wants {self.num_devices} devices "
                f"({dict(zip(self.axes, self.shape))}), have {devs.size}")
        return Mesh(devs.reshape(self.shape), self.axes)


# ---------------------------------------------------------------------------
# Process bring-up
# ---------------------------------------------------------------------------

def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None, *,
                     cpu_collectives: str = "gloo",
                     initialization_timeout: Optional[float] = None,
                     retries: Optional[int] = None,
                     backoff_s: float = 1.0,
                     elastic: bool = False,
                     heartbeat_timeout_s: int = 86400,
                     external_service: Optional[bool] = None
                     ) -> Dict[str, int]:
    """Bring this process into the `jax.distributed` job (idempotent).

    Selects the CPU collectives implementation (gloo: real TCP
    cross-process all-reduces on the host platform) BEFORE backend
    initialization, then calls `jax.distributed.initialize`.  Arguments
    default to the REPRO_COORDINATOR / REPRO_NUM_PROCESSES /
    REPRO_PROCESS_ID environment variables (set by `launch.multihost
    --spawn` and the test harness), and to jax's own cluster
    auto-detection when neither is given.

    Bring-up is bounded and retried rather than hanging forever on a
    dead coordinator: each attempt gets `initialization_timeout`
    seconds (env-defaulted via ``REPRO_INIT_TIMEOUT``, default 120),
    failures back off exponentially from `backoff_s`, and after
    `retries` attempts (env ``REPRO_INIT_RETRIES``, default 3) a
    RuntimeError NAMING THE COORDINATOR ADDRESS is raised — transient
    coordinator hiccups are absorbed, a truly dead one is diagnosed.

    `elastic=True` routes through the lower-level distributed-state
    initializer so the coordination-service liveness knobs can be
    raised: by default the service declares a silent task dead after
    100 s and then TERMINATES every other process — exactly what an
    elastic run must prevent, because `launch.elastic` does its own
    KV-store heartbeat detection and keeps the survivors alive.
    `heartbeat_timeout_s` (default 86400 == one silent day) is the
    override.

    `external_service=True` (env ``REPRO_SERVICE_EXTERNAL=1``) declares
    that the coordination service is hosted OUTSIDE the mesh ranks (a
    `launch.control.run_service_host` / ``--service-host`` process at
    `coordinator`).  Rank 0 then brings up a *client only*, like every
    other rank — the full bring-up path that jax's default initializer
    cannot express, because it always starts the service inside
    process 0.  This is what makes coordinator-rank death survivable:
    the service socket (and with it the KV control plane and gloo's
    communicator rendezvous) no longer dies with rank 0.

    Returns {"process_id": ..., "num_processes": ...} for convenience.
    A second call is a no-op (jax pins distributed state at first use),
    so library code can call this defensively.
    """
    coordinator = coordinator or os.environ.get("REPRO_COORDINATOR")
    if num_processes is None and "REPRO_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["REPRO_NUM_PROCESSES"])
    if process_id is None and "REPRO_PROCESS_ID" in os.environ:
        process_id = int(os.environ["REPRO_PROCESS_ID"])
    if initialization_timeout is None:
        initialization_timeout = float(os.environ.get("REPRO_INIT_TIMEOUT",
                                                      120.0))
    if retries is None:
        retries = int(os.environ.get("REPRO_INIT_RETRIES", 3))
    if external_service is None:
        external_service = bool(int(os.environ.get(
            "REPRO_SERVICE_EXTERNAL", "0")))

    from jax._src import distributed as _dist
    already = getattr(_dist.global_state, "client", None) is not None
    if not already:
        if cpu_collectives and "jax_cpu_collectives_implementation" in \
                jax.config.values:
            jax.config.update("jax_cpu_collectives_implementation",
                              cpu_collectives)
        if coordinator is not None:
            if external_service:
                _init_client_only(coordinator, num_processes, process_id,
                                  timeout=initialization_timeout,
                                  heartbeat_timeout_s=heartbeat_timeout_s)
            else:
                _init_with_retries(coordinator, num_processes, process_id,
                                   timeout=initialization_timeout,
                                   retries=max(1, retries),
                                   backoff_s=backoff_s, elastic=elastic,
                                   heartbeat_timeout_s=heartbeat_timeout_s)
        elif num_processes is not None and num_processes > 1:
            raise ValueError("multi-process init needs a coordinator "
                             "address (host:port)")
    info = {"process_id": jax.process_index(),
            "num_processes": jax.process_count()}
    # stamp this process's telemetry collector with its rank so spool
    # files merge into a per-rank timeline (single-process runs stay 0)
    obs.set_rank(info["process_id"])
    return info


def _init_client_only(coordinator: str, num_processes, process_id, *,
                      timeout: float, heartbeat_timeout_s: int) -> None:
    """Join an EXTERNALLY-hosted coordination service: build only the
    distributed-runtime client and hand it to `global_state`, so this
    process — rank 0 included — is a peer like any other and its death
    cannot take the service (KV store, gloo rendezvous) down with it.

    Client heartbeat tolerance is raised to match the service's: the
    default client would fatally terminate the process when the service
    reports a peer failure, which is exactly the error propagation the
    elastic layer replaces with its own chunk-boundary verdicts.
    """
    from jax._src import distributed as _dist
    from jax._src.lib import _jax

    if num_processes is None or process_id is None:
        raise ValueError("external-service init needs explicit "
                         "num_processes and process_id")
    gs = _dist.global_state
    gs.coordinator_address = coordinator
    gs.num_processes = int(num_processes)
    gs.process_id = int(process_id)
    gs.client = _jax.get_distributed_runtime_client(
        coordinator, int(process_id),
        init_timeout=int(timeout),
        heartbeat_timeout=int(heartbeat_timeout_s),
        use_compression=True)
    try:
        gs.client.connect()
    except Exception as e:         # noqa: BLE001 — diagnose, then re-raise
        gs.client = None
        raise RuntimeError(
            f"init_distributed: process {process_id} could not join the "
            f"EXTERNAL coordination service at {coordinator!r} within "
            f"{timeout:.0f}s — is the --service-host process up?") from e


def _init_with_retries(coordinator: str, num_processes, process_id, *,
                       timeout: float, retries: int, backoff_s: float,
                       elastic: bool, heartbeat_timeout_s: int) -> None:
    """Bounded-retry `jax.distributed` bring-up (see `init_distributed`)."""
    from jax._src import distributed as _dist
    last: Optional[BaseException] = None
    for attempt in range(retries):
        try:
            if elastic:
                _dist.global_state.initialize(
                    coordinator_address=coordinator,
                    num_processes=num_processes, process_id=process_id,
                    initialization_timeout=int(timeout),
                    heartbeat_timeout_seconds=int(heartbeat_timeout_s))
            else:
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=num_processes, process_id=process_id,
                    initialization_timeout=int(timeout))
            return
        except TypeError:
            # a wrong argument is a programming error, not a slow
            # coordinator: retrying it would only mislabel it
            raise
        except Exception as e:     # noqa: BLE001 — retried, then re-raised
            last = e
            try:                   # drop any partially-initialized state
                _dist.global_state.shutdown()
            except Exception:      # noqa: BLE001
                pass
            if attempt + 1 < retries:
                time.sleep(backoff_s * (2.0 ** attempt))
    raise RuntimeError(
        f"init_distributed: process {process_id} could not join the "
        f"jax.distributed job at coordinator {coordinator!r} after "
        f"{retries} attempt(s) of {timeout:.0f}s each — is the "
        f"coordinator (rank 0) up and reachable?") from last


def local_worker_ids(mesh: Mesh, axis: Optional[str] = None
                     ) -> Tuple[int, ...]:
    """Partition workers owned by this process, in ascending order.

    Worker i is the i-th coordinate along the workers mesh axis; it is
    "owned" here iff any of its devices is addressable from this
    process (with the 1-D one-worker-per-device CALL mesh this is
    exactly the process's local devices).  The manifest's worker-major
    extents make each owned id one contiguous byte range per segment —
    `ShardStore.local_slice` maps precisely those.
    """
    axis = axis or mesh.axis_names[0]
    ax = mesh.axis_names.index(axis)
    me = jax.process_index()
    devs = np.moveaxis(mesh.devices, ax, 0).reshape(mesh.shape[axis], -1)
    return tuple(int(i) for i in range(devs.shape[0])
                 if any(d.process_index == me for d in devs[i]))


def _worker_devices(mesh: Mesh, axis: str):
    """worker id -> the devices holding its slice (other axes raveled)."""
    ax = mesh.axis_names.index(axis)
    devs = np.moveaxis(mesh.devices, ax, 0).reshape(mesh.shape[axis], -1)
    return devs


def global_worker_array(mesh: Mesh, axis: str,
                        blocks: Mapping[int, np.ndarray],
                        dtype=None) -> jax.Array:
    """Assemble a global row-sharded array from per-worker host blocks.

    `blocks` maps every LOCALLY-OWNED worker id to its (n_k, ...) block
    (a `LocalShardSlice` view, an in-memory shard, ...).  Each block is
    `device_put` onto its worker's device and the global (p * n_k, ...)
    array is registered via `jax.make_array_from_single_device_arrays`
    — no host ever materializes rows it does not own.  All processes
    must call this with consistent shapes (it is collective-free but
    shape-synchronous).
    """
    owned = local_worker_ids(mesh, axis)
    missing = [i for i in owned if i not in blocks]
    if missing:
        raise ValueError(f"missing blocks for owned workers {missing}")
    p = mesh.shape[axis]
    sample = blocks[owned[0]] if owned else None
    if sample is None:
        raise ValueError("process owns no workers; a zero-device process "
                         "cannot participate in the mesh run")
    n_k, tail = sample.shape[0], sample.shape[1:]
    sharding = NamedSharding(mesh, P(axis))
    shards = []
    for i in owned:
        blk = np.asarray(blocks[i], dtype=dtype)
        if blk.shape != (n_k,) + tail:
            raise ValueError(f"worker {i} block shape {blk.shape} != "
                             f"{(n_k,) + tail}")
        for dev in _worker_devices(mesh, axis)[i]:
            if dev.process_index == jax.process_index():
                shards.append(jax.device_put(blk, dev))
    return jax.make_array_from_single_device_arrays(
        (p * n_k,) + tail, sharding, shards)


def prepare_stacked_host_blocks(ownership: Mapping[int, Sequence[int]],
                                data, y=None, *,
                                ranks: Optional[Sequence[int]] = None):
    """The HOST half of `stacked_worker_arrays`: open the owned shard
    extents (`ShardStore.local_slice` offset mmaps — orphan adoption is
    just a bigger slice), stack each rank's workers into a zero-padded
    (W_max, n_k, ...) block, and build the -1-padded slot rows.

    Pure numpy, no jax device state touched — safe to run on a
    background thread.  The elastic driver exploits exactly that:
    survivors kick this off the moment the re-mesh verdict lands, so
    the mmap + pad work overlaps the mesh rebuild and the remesh
    barrier wait instead of serializing after them
    (`remesh_overlap_saved_s` in the recovery events).

    `ranks` limits the build to the given ranks' blocks (default: every
    rank in `ownership` — the single-process case).  Returns an opaque
    dict for `stacked_worker_arrays(..., host_blocks=...)`.
    """
    from repro.data.sparse import CSRMatrix
    from repro.datasets.shards import ShardStore
    from repro.train.elastic import slot_table

    slots = slot_table(ownership)
    W = len(next(iter(slots.values())))
    p_total = sum(len(tuple(ws)) for ws in ownership.values())
    build = sorted(int(r) for r in (ranks if ranks is not None
                                    else ownership))

    if isinstance(data, ShardStore):
        n_k, K = int(data.n_k), int(data.max_nnz)

        def blocks_for(ws):
            sl = data.local_slice(tuple(ws))
            return (np.asarray(sl.vals), np.asarray(sl.cols),
                    np.asarray(sl.yp))
    elif isinstance(data, CSRMatrix):
        if y is None:
            raise ValueError("worker-major CSR data needs labels yp")
        yp = np.asarray(y)
        _, n_k, K = data.vals.shape

        def blocks_for(ws):
            ws = list(ws)
            return (np.asarray(data.vals)[ws], np.asarray(data.cols)[ws],
                    yp[ws])
    else:
        raise ValueError("stacked_worker_arrays needs a ShardStore or a "
                         f"worker-major CSRMatrix, got {type(data)!r}")

    blocks = {}
    for rank in build:
        ws = [w for w in slots[rank] if w >= 0]
        v, c, yk = blocks_for(ws)
        pad = lambda a, fill, dt: np.concatenate(
            [np.asarray(a, dt),
             np.full((W - len(ws),) + a.shape[1:], fill, dt)])[None]
        blocks[rank] = {
            "vals": pad(v, 0, np.float32),
            "cols": pad(c, 0, np.int32),
            # pad labels with a FINITE value so h'(margin, y) stays
            # finite on the throwaway pad-slot inner loops (phase 3
            # masks them out)
            "y": pad(yk, 1.0, np.float32),
            "slots": np.asarray(slots[rank], np.int32)[None],
        }
    return {"blocks": blocks, "W": W, "n_k": n_k, "K": K,
            "p_total": p_total,
            "ownership": {int(r): tuple(int(w) for w in ws)
                          for r, ws in ownership.items()}}


def stacked_worker_arrays(mesh: Mesh, axis: str,
                          ownership: Mapping[int, Sequence[int]],
                          data=None, y=None, *, host_blocks=None):
    """Assemble the stacked uneven-ownership operands for
    `pscope.run_stacked_scanned`.

    `ownership` maps each SURVIVING rank to the worker ids it owns
    (`train.elastic.failure_plan` output); `mesh` is the 1-D survivor
    mesh, one device per surviving rank, in ascending-rank order (the
    order `jax.devices()` preserves when the dead rank's devices are
    filtered out).  `data` is a `ShardStore` (each host maps only the
    extents it owns) or a worker-major `CSRMatrix` + labels.

    Every device's owned shards are stacked into a zero-padded
    (W_max, n_k, ...) block plus an int32 slot→worker-id row (-1 pad);
    the global (s, W_max, ...) arrays are registered via
    `jax.make_array_from_single_device_arrays`, so no host ever
    materializes rows it does not own.  Returns
    (vals, cols, yg, slots, p_total).

    `host_blocks` (from `prepare_stacked_host_blocks`, possibly built
    on a background thread) skips the host-side mmap + pad work; it
    must have been prepared from the SAME ownership map.
    """
    ranks = sorted(int(r) for r in ownership)
    ax = mesh.axis_names.index(axis)
    devs = np.moveaxis(mesh.devices, ax, 0).reshape(mesh.shape[axis], -1)
    if devs.shape != (len(ranks), 1):
        raise ValueError(
            f"the stacked layout needs a 1-D mesh with one device per "
            f"surviving rank ({len(ranks)} ranks, mesh axis {axis} has "
            f"shape {devs.shape})")
    me = jax.process_index()
    if host_blocks is None:
        need = [r for i, r in enumerate(ranks)
                if devs[i, 0].process_index == me]
        host_blocks = prepare_stacked_host_blocks(ownership, data, y,
                                                  ranks=need)
    else:
        want = {int(r): tuple(int(w) for w in sorted(tuple(ws)))
                for r, ws in ownership.items()}
        if host_blocks["ownership"] != want:
            raise ValueError("host_blocks were prepared for a different "
                             "ownership map — stale background build?")
    W, n_k, K = host_blocks["W"], host_blocks["n_k"], host_blocks["K"]
    p_total = host_blocks["p_total"]

    sharding = NamedSharding(mesh, P(axis))
    shards = {"vals": [], "cols": [], "y": [], "slots": []}
    for i, rank in enumerate(ranks):
        dev = devs[i, 0]
        if dev.process_index != me:
            continue
        if rank not in host_blocks["blocks"]:
            raise ValueError(f"host_blocks missing locally-hosted rank "
                             f"{rank} (have "
                             f"{sorted(host_blocks['blocks'])})")
        blk = host_blocks["blocks"][rank]
        for name in ("vals", "cols", "y", "slots"):
            shards[name].append(jax.device_put(blk[name], dev))

    s = len(ranks)
    mk = jax.make_array_from_single_device_arrays
    return (mk((s, W, n_k, K), sharding, shards["vals"]),
            mk((s, W, n_k, K), sharding, shards["cols"]),
            mk((s, W, n_k), sharding, shards["y"]),
            mk((s, W), sharding, shards["slots"]),
            p_total)


def comm_bytes_per_round(d: int, itemsize: int = 4) -> float:
    """Analytic bytes-on-wire of one CALL outer round.

    Two d-vector all-reduces — the anchor-gradient psum (phase 1) and
    the iterate broadcast/average (phase 3); the inner loop is
    collective-free.  O(d), independent of n: the property the paper's
    communication-efficiency claim rests on and the comm-accounting
    test regression-pins.
    """
    return 2.0 * float(d) * itemsize


# ---------------------------------------------------------------------------
# The mesh driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshRunResult:
    """One `run_mesh` trajectory, plus its communication accounting."""

    w: np.ndarray
    values: np.ndarray
    nnz: np.ndarray
    comm_bytes_per_round: float
    worker_ids: Tuple[int, ...]       # workers this process owned
    worker_devices: Tuple[int, ...]   # device id holding each of them
    seconds: float
    process_id: int
    num_processes: int


def _worker_blocks_from(data, y):
    """Normalize `data` into per-worker host blocks + metadata.

    Accepts a `ShardStore` (multi-host path: only the owned extents are
    mmapped), a worker-major `CSRMatrix` (p, n_k, k) + labels (p, n_k),
    or a dense worker-major array (p, n_k, d) + labels.
    Returns (kind, blocks dict per segment, d, p).
    """
    from repro.data.sparse import CSRMatrix
    from repro.datasets.shards import ShardStore

    if isinstance(data, ShardStore):
        return "store", data, int(data.d), int(data.p)
    if isinstance(data, CSRMatrix):
        if data.vals.ndim != 3:
            raise ValueError("run_mesh needs worker-major (p, n_k, k) CSR "
                             f"shards, got vals shape {data.vals.shape}")
        if y is None:
            raise ValueError("worker-major CSR data needs labels yp")
        return "csr", (data, np.asarray(y)), int(data.d), data.vals.shape[0]
    arr = np.asarray(data)
    if arr.ndim != 3:
        raise ValueError("run_mesh needs worker-major (p, n_k, d) dense "
                         f"data, got shape {arr.shape}")
    if y is None:
        raise ValueError("dense worker-major data needs labels yp")
    return "dense", (arr, np.asarray(y)), arr.shape[-1], arr.shape[0]


def run_mesh(obj, reg, data, y, w0, cfg, spec: Optional[MeshSpec] = None, *,
             record_every: int = 1,
             devices: Optional[Sequence] = None) -> MeshRunResult:
    """pSCOPE over a (possibly multi-process) device mesh.

    Every process of the `jax.distributed` job calls this with the SAME
    arguments; `data` is a `ShardStore` (each host maps only its worker
    slice), a worker-major `CSRMatrix`, or dense (p, n_k, d) shards.
    The trajectory runs through the unchanged zero-sync
    `pscope.run_distributed_scanned` — outer rounds are mesh psums, the
    inner loops collective-free, ONE host transfer per process at the
    end.  The returned histories are replicated: every rank holds the
    bit-identical trace (the harness asserts it).

    `cfg.inner_path="auto"` resolves layout-locally ("lazy" for
    CSR-backed data, "dense" for dense): the cost model's O(n*d) nnz
    probe would require materializing remote rows, which this driver
    exists to avoid.
    """
    import dataclasses as _dc

    from repro.core import pscope
    from repro.data.sparse import CSRMatrix

    kind, payload, d, p = _worker_blocks_from(data, y)
    spec = spec or MeshSpec.for_workers(p)
    if spec.num_workers != p:
        raise ValueError(f"MeshSpec workers axis has size "
                         f"{spec.num_workers}, data has p={p} workers")
    mesh = spec.build(devices)
    axis = spec.workers_axis
    owned = local_worker_ids(mesh, axis)

    if cfg.inner_path == "auto":
        cfg = _dc.replace(cfg,
                          inner_path="dense" if kind == "dense" else "lazy")

    with obs.span("mesh.shards", p=p, kind=kind,
                  owned=[int(w) for w in owned]):
        if kind == "store":
            store = payload
            sl = store.local_slice(owned)
            pos = {w: i for i, w in enumerate(sl.worker_ids)}
            if store.codec is not None:
                # codec store: register the ENCODED leaves (uint16 bf16
                # bits, delta columns — about half the raw CSR bytes on
                # device) and let the solve path fuse the decode into
                # the epoch gather (pscope's EncodedCSR branch).  Each
                # host still decodes only the byte extents of the
                # workers it owns (`LocalShardSlice._packed_decoded`).
                from repro.data.sparse import EncodedCSR
                X = EncodedCSR(
                    vals16=global_worker_array(
                        mesh, axis, {w: sl.vals16[pos[w]] for w in owned}),
                    colb=global_worker_array(
                        mesh, axis, {w: sl.colb[pos[w]] for w in owned}),
                    dcols=global_worker_array(
                        mesh, axis, {w: sl.dcols[pos[w]] for w in owned}),
                    row_nnz=global_worker_array(
                        mesh, axis,
                        {w: sl.row_nnz[pos[w]] for w in owned}),
                    d=d)
            else:
                X = CSRMatrix(
                    vals=global_worker_array(mesh, axis,
                                             {w: sl.vals[pos[w]]
                                              for w in owned}),
                    cols=global_worker_array(mesh, axis,
                                             {w: sl.cols[pos[w]]
                                              for w in owned}),
                    row_nnz=global_worker_array(mesh, axis,
                                                {w: sl.row_nnz[pos[w]]
                                                 for w in owned}),
                    d=d)
            yg = global_worker_array(mesh, axis,
                                     {w: sl.yp[pos[w]] for w in owned})
        elif kind == "csr":
            csr, yp = payload
            X = CSRMatrix(
                vals=global_worker_array(mesh, axis,
                                         {w: np.asarray(csr.vals[w])
                                          for w in owned}),
                cols=global_worker_array(mesh, axis,
                                         {w: np.asarray(csr.cols[w])
                                          for w in owned}),
                row_nnz=global_worker_array(mesh, axis,
                                            {w: np.asarray(csr.row_nnz[w])
                                             for w in owned}),
                d=d)
            yg = global_worker_array(mesh, axis, {w: yp[w] for w in owned})
        else:
            Xp, yp = payload
            X = global_worker_array(mesh, axis, {w: Xp[w] for w in owned})
            yg = global_worker_array(mesh, axis,
                                     {w: yp[w] for w in owned})

    t0 = time.perf_counter()
    with obs.span("mesh.solve", p=p, d=d, rounds=cfg.outer_steps,
                  inner_path=cfg.inner_path) as solve_span:
        w, values, nnzs = pscope.run_distributed_scanned(
            obj, reg, X, yg, w0, cfg, mesh, axis=axis,
            record_every=record_every)
    # cumulative bytes-on-wire per recorded round as counter events,
    # spread across the solve span (the scanned driver runs all rounds
    # in one jit, so per-round on-device timestamps don't exist)
    seconds = time.perf_counter() - t0
    per_rec = comm_bytes_per_round(d) * record_every
    n_rec = len(values)
    for i in range(n_rec):
        obs.counter("comm_bytes", per_rec * i,
                    ts_s=solve_span.t0 + seconds * i / max(1, n_rec - 1))
    return MeshRunResult(
        w=np.asarray(w), values=np.asarray(values), nnz=np.asarray(nnzs),
        comm_bytes_per_round=comm_bytes_per_round(d),
        worker_ids=owned,
        worker_devices=tuple(int(_worker_devices(mesh, axis)[w][0].id)
                             for w in owned),
        seconds=seconds,
        process_id=jax.process_index(), num_processes=jax.process_count())
