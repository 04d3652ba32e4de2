"""Unified solver registry and the traced CALL benchmark harness.

The paper's headline comparison (Section 7, Figure 1 / Table 2) pits
pSCOPE — Algorithm 1 under the cooperative autonomous local learning
(CALL) framework — against nine baselines.  This module gives all ten a
single instrumented entry point:

    trace = solvers.run("pscope", objective, regularizer, partition)

Every solver is described by a `SolverSpec` (registered via
`@register`) whose adapter maps the shared `SolverConfig` onto the
solver's native signature, and every run returns a `Trace`: a streaming
metrics recorder capturing, at each recorded round,

  * the composite objective P(w_t) = F(w_t) + R(w_t),
  * the iterate's NNZ (L1 sparsity, the paper's Section 7.3 metric),
  * cumulative communication rounds (the CALL framework's currency —
    pSCOPE pays 2 all-reduces per outer round, eq. after Algorithm 1,
    vs per-step all-reduces for the dpSGD/dpSVRG family),
  * cumulative wall-clock seconds,

plus, on request, the partition-goodness estimate gamma(pi; eps) of
Definition 5 (via the batched `repro.partition.gamma_estimate`).
Training loops, the benchmark figures, and the dry-run grid all consume
the same Trace, so adding a solver (one `@register` block here) or a
partition scenario (one `register_scheme` block in
`repro.partition.schemes`) immediately shows up everywhere.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import pscope
from repro.core.baselines import (admm_history, cocoa_history, dbcd_history,
                                  dpsgd_history, dpsvrg_history,
                                  fista_history, owlqn_history, pgd_history,
                                  prox_svrg_history)
from repro.core.objectives import Objective
from repro.core.partition import Partition, gamma_estimate
from repro.core.prox import Regularizer

Array = jax.Array

# |w_i| above this counts as a nonzero (Section 7.3) — the single
# definition lives in pscope so the scanned drivers' device-side NNZ
# histories and Trace.record's host-side reduction can never diverge.
NNZ_TOL = pscope.NNZ_TOL


# ---------------------------------------------------------------------------
# Trace: the streaming metrics recorder
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Trace:
    """Streaming per-round metrics of one solver run.

    All lists are index-aligned; entry 0 is the initial iterate (zero
    communication, ~zero seconds).  `comm` and `seconds` are cumulative.

    `seconds` measures SOLVER work only: the cost of recording itself —
    the NNZ device reduction, the list bookkeeping, anything charged via
    `charge_overhead` — accumulates in an overhead counter that is
    subtracted from every subsequent timestamp, so cheap-step solvers
    are not billed for their own instrumentation (the table2/fig2a
    inflation bug).
    """

    solver: str
    objective: str
    partition: str
    p: int                     # number of workers
    d: int                     # dimensionality
    values: List[float] = dataclasses.field(default_factory=list)
    nnz: List[int] = dataclasses.field(default_factory=list)
    comm: List[float] = dataclasses.field(default_factory=list)
    seconds: List[float] = dataclasses.field(default_factory=list)
    gamma: Optional[float] = None     # Definition 5 estimate, if requested
    w_final: Optional[Array] = None
    heldout: Dict[str, float] = dataclasses.field(default_factory=dict)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # named cumulative counter series, index-aligned with `values`
    # (e.g. the scanned drivers' device-side catch_up / prox_skip —
    # see pscope.COUNTER_NAMES); empty unless the adapter feeds them
    # via `record_history(..., counters=...)`
    counters: Dict[str, List[float]] = dataclasses.field(
        default_factory=dict)
    _t0: Optional[float] = dataclasses.field(default=None, repr=False)
    _overhead: float = dataclasses.field(default=0.0, repr=False)

    # -- recording --------------------------------------------------------
    def start(self) -> "Trace":
        self._t0 = time.perf_counter()
        return self

    @property
    def overhead_seconds(self) -> float:
        """Cumulative recording overhead excluded from `seconds`."""
        return self._overhead

    def charge_overhead(self, seconds: float) -> None:
        """Exclude `seconds` of non-solver work (e.g. a caller's
        objective evaluation done purely for recording) from all
        subsequent wall-clock timestamps."""
        self._overhead += float(seconds)

    def record(self, w, value: float, comm_increment: float = 0.0, *,
               nnz: Optional[int] = None) -> None:
        """Append one round: iterate w (array or pytree — the DL train
        loop passes whole param trees), objective value, communication
        rounds spent since the previous record.  Pass `nnz` to skip the
        device reduction when the caller already holds it (the scanned
        drivers record NNZ on device); `w` may then be None."""
        t_in = time.perf_counter()
        if self._t0 is None:
            self._t0 = t_in
        self.values.append(float(value))
        if nnz is None:
            nnz = sum(int(jnp.sum(jnp.abs(leaf) > NNZ_TOL))
                      for leaf in jax.tree_util.tree_leaves(w))
        self.nnz.append(int(nnz))
        prev = self.comm[-1] if self.comm else 0.0
        self.comm.append(prev + float(comm_increment))
        self.seconds.append(t_in - self._t0 - self._overhead)
        # everything this call did after t_in is recording overhead
        self._overhead += time.perf_counter() - t_in

    def record_history(self, values, nnzs, comm_per_record: float,
                       total_seconds: float,
                       counters: Optional[Dict[str, Any]] = None) -> None:
        """Feed a device-recorded trajectory post-hoc (the zero-sync
        scanned drivers, `pscope.run_scanned`): index 0 is the initial
        iterate.  The compiled trajectory admits no per-round
        timestamps — one host sync total — so `total_seconds` (measured
        around the compiled call) is attributed linearly across rounds,
        exact for the uniform per-round cost of the SVRG family.

        Timing boundary: the scanned driver's in-program objective/NNZ
        evaluations remain inside `total_seconds`, exactly as the
        python-loop solvers' in-loop objective evaluations remain
        inside their `seconds` — the methodologies are symmetric; only
        the host-side recording mechanics (this loop, `record`'s NNZ
        reduction) are excluded via the overhead accumulator."""
        n = len(values)
        rounds = max(n - 1, 1)
        for i, (v, nz) in enumerate(zip(values, nnzs)):
            self.values.append(float(v))
            self.nnz.append(int(nz))
            prev = self.comm[-1] if self.comm else 0.0
            self.comm.append(prev + (comm_per_record if i else 0.0))
            self.seconds.append(total_seconds * i / rounds)
        if counters:
            # cumulative named series riding the same device transfer
            # (pscope.run_scanned(counters=True)); index-aligned with
            # the values just appended
            for name, series in counters.items():
                self.counters.setdefault(name, []).extend(
                    float(x) for x in series)

    def record_heldout(self, **metrics: float) -> None:
        """Attach held-out metrics (e.g. from `evaluate_heldout`).

        Like `record_history` this is a post-hoc feed: the evaluation
        happens after the compiled trajectory returned, so the scanned
        drivers stay zero-sync; callers charge the evaluation cost via
        `charge_overhead` so it never pollutes `seconds`."""
        self.heldout.update({k: float(v) for k, v in metrics.items()})

    def recorder(self, comm_per_record: float) -> Callable[[Array, float], None]:
        """An `on_record(w, value)` callback charging `comm_per_record`
        communication rounds to every record after the first."""

        def cb(w: Array, value: float) -> None:
            inc = comm_per_record if self.values else 0.0
            self.record(w, value, inc)

        return cb

    # -- derived metrics --------------------------------------------------
    @property
    def rounds(self) -> int:
        return max(len(self.values) - 1, 0)

    @property
    def final_value(self) -> float:
        return self.values[-1]

    def gap(self, p_star: float) -> float:
        """Final suboptimality P(w_T) - P*."""
        return self.final_value - p_star

    def suboptimality(self, p_star: float) -> List[float]:
        return [v - p_star for v in self.values]

    def time_to(self, p_star: float, eps: float = 1e-3) -> float:
        """First wall-clock second at which P(w) - P* <= eps (inf if never)."""
        for v, t in zip(self.values, self.seconds):
            if v - p_star <= eps:
                return t
        return float("inf")

    def rounds_to(self, p_star: float, eps: float = 1e-3) -> Optional[int]:
        for i, v in enumerate(self.values):
            if v - p_star <= eps:
                return i
        return None

    def comm_to(self, p_star: float, eps: float = 1e-3) -> float:
        """Communication rounds spent to reach eps-suboptimality."""
        for v, c in zip(self.values, self.comm):
            if v - p_star <= eps:
                return c
        return float("inf")

    def validate(self) -> "Trace":
        """Raise ValueError if the trace is malformed."""
        n = len(self.values)
        if n < 1:
            raise ValueError("empty trace: no rounds recorded")
        if not (len(self.nnz) == len(self.comm) == len(self.seconds) == n):
            raise ValueError(
                f"misaligned trace: values={n} nnz={len(self.nnz)} "
                f"comm={len(self.comm)} seconds={len(self.seconds)}")
        if not np.isfinite(self.values[0]):
            raise ValueError(f"non-finite initial objective {self.values[0]}")
        if any(b < a - 1e-9 for a, b in zip(self.comm, self.comm[1:])):
            raise ValueError("communication counter decreased")
        if any(b < a - 1e-6 for a, b in zip(self.seconds, self.seconds[1:])):
            raise ValueError("wall clock decreased")
        return self


# ---------------------------------------------------------------------------
# SolverConfig: the one knob-set every adapter understands
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Shared solver configuration.

    rounds        recorded rounds (outer epochs for the SVRG family,
                  iteration blocks of `record_every` for per-step methods)
    record_every  native iterations between records (per-step methods)
    eta           step size; None picks a 1/(2L) default from the data
    inner_epochs  local epochs per outer round (SVRG-family inner M)
    batch         minibatch size for the stochastic methods
    extras        solver-specific overrides, e.g. {"rho": 2.0} for ADMM;
                  unknown keys are ignored by other solvers
    """

    rounds: int = 20
    record_every: int = 1
    eta: Optional[float] = None
    inner_epochs: float = 2.0
    batch: int = 8
    seed: int = 0
    estimate_gamma: bool = False
    gamma_samples: int = 4
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def with_(self, **kw) -> "SolverConfig":
        return dataclasses.replace(self, **kw)


def _default_eta(obj: Objective, reg: Regularizer, part: Partition,
                 cfg: SolverConfig) -> float:
    """eta = 1/(2(L + lam1)) from the smoothness bound when unset
    (Corollary 1 scale; benchmarks override per figure).  Uses the
    partition's CSR-aware bound so sparse-backed data is never
    densified just to size a step."""
    if cfg.eta is not None:
        return cfg.eta
    L = part.smooth_lipschitz(obj) + reg.lam1
    return 1.0 / (2.0 * L)


def _w0(part: Partition, cfg: SolverConfig) -> Array:
    w0 = cfg.extras.get("w0")
    return jnp.zeros(part.d) if w0 is None else jnp.asarray(w0)


# ---------------------------------------------------------------------------
# SolverSpec registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """One solver behind the uniform run() interface.

    `run_fn(obj, reg, part, cfg, trace)` drives the native implementation,
    streams records into `trace`, and returns the final iterate.
    """

    name: str
    summary: str
    paper_ref: str             # which equation/algorithm it implements
    distributed: bool          # consumes worker-major (p, n_k, d) shards
    comm_model: str            # human-readable communication cost
    run_fn: Callable[[Objective, Regularizer, Partition, SolverConfig,
                      Trace], Array]


_REGISTRY: Dict[str, SolverSpec] = {}


def register(name: str, *, summary: str, paper_ref: str, distributed: bool,
             comm_model: str) -> Callable:
    """Decorator registering an adapter under `name`."""

    def deco(fn: Callable) -> Callable:
        if name in _REGISTRY:
            raise ValueError(f"solver {name!r} already registered")
        _REGISTRY[name] = SolverSpec(name=name, summary=summary,
                                     paper_ref=paper_ref,
                                     distributed=distributed,
                                     comm_model=comm_model, run_fn=fn)
        return fn

    return deco


def get(name: str) -> SolverSpec:
    if name not in _REGISTRY:
        raise KeyError(f"unknown solver {name!r}; "
                       f"available: {available()}")
    return _REGISTRY[name]


def available() -> Tuple[str, ...]:
    """Registered solver names, pSCOPE first, then insertion order."""
    return tuple(_REGISTRY)


def run(solver: str, obj: Objective, reg: Regularizer, part: Partition,
        config: Optional[SolverConfig] = None) -> Trace:
    """The uniform entry point: run `solver` on (obj, reg, part).

    Returns a validated `Trace`; `trace.w_final` holds the last iterate.
    The whole call is the `solve.<solver>` span (`repro.obs`).
    """
    spec = get(solver)
    cfg = config if config is not None else SolverConfig()
    with obs.span(f"solve.{spec.name}", rounds=cfg.rounds, p=part.p,
                  d=part.d):
        trace = Trace(solver=spec.name, objective=obj.name,
                      partition=part.name, p=part.p, d=part.d)
        trace.start()
        trace.w_final = spec.run_fn(obj, reg, part, cfg, trace)
        if cfg.estimate_gamma:
            trace.gamma = estimate_partition_gamma(
                obj, reg, part, num_samples=cfg.gamma_samples,
                seed=cfg.seed)
        return trace.validate()


def estimate_partition_gamma(obj: Objective, reg: Regularizer,
                             part: Partition, num_samples: int = 4,
                             eps: float = 1e-3, seed: int = 0,
                             fista_iters: int = 2000,
                             inner_iters: int = 200) -> float:
    """gamma(pi; eps) of Definition 5 for `part`, solving for w* with
    FISTA first; the p x num_samples grid of local solves runs as one
    batched XLA call (see docs/partition_theory.md)."""
    w_star, fh = fista_history(obj, reg, part.X, part.y, jnp.zeros(part.d),
                               iters=fista_iters, record_every=fista_iters)
    return gamma_estimate(obj, reg, part.Xp, part.yp, w_star, fh[-1],
                          eps=eps, num_samples=num_samples, seed=seed,
                          iters=inner_iters)


def evaluate_heldout(obj: Objective, reg: Regularizer, X_test, y_test,
                     w) -> Dict[str, float]:
    """Held-out metrics of an iterate: composite objective P(w) on the
    test rows, plus 0/1 accuracy when the labels are +-1.

    `X_test` may be dense (n, d) or a padded `CSRMatrix` (the split
    helper in `repro.datasets.split` preserves either); the sparse path
    evaluates through margins so the test set is never densified.
    """
    from repro.core.svrg import LINEAR_MODEL_H_LOSS
    from repro.data.sparse import CSRMatrix, matvec
    w = jnp.asarray(w)
    y = jnp.asarray(y_test)
    if isinstance(X_test, CSRMatrix):
        z = matvec(X_test, w)
        h = LINEAR_MODEL_H_LOSS.get(obj.name)
        if h is not None:
            loss = jnp.mean(h(z, y))
        else:      # unknown objective: densify (correct, not hot-path)
            from repro.data.sparse import csr_to_dense
            Xd = csr_to_dense(X_test)
            loss = obj.loss(w, Xd, y)
            z = Xd @ w
    else:
        X = jnp.asarray(X_test)
        loss = obj.loss(w, X, y)
        z = X @ w
    out = {"objective": float(loss + reg.value(w))}
    yn = np.asarray(y)
    if np.all(np.isin(yn, (-1.0, 1.0))):
        pred = jnp.where(z >= 0, 1.0, -1.0)
        out["accuracy"] = float(jnp.mean(pred == y))
    return out


# ---------------------------------------------------------------------------
# Adapters: pSCOPE + the nine Section-7.1 baselines
# ---------------------------------------------------------------------------

def _pscope_config(obj, reg, part, cfg, inner_path: str):
    inner = cfg.extras.get(
        "inner_steps", max(1, int(cfg.inner_epochs * part.n_k)))
    return pscope.PScopeConfig(
        eta=_default_eta(obj, reg, part, cfg), inner_steps=inner,
        inner_batch=cfg.extras.get("inner_batch", 1),
        outer_steps=cfg.rounds, seed=cfg.seed, inner_path=inner_path)


def _round_offsets(n_records: int, total_seconds: float) -> List[float]:
    """The linear per-round time attribution `record_history` uses —
    reused to timestamp counter events across the trajectory's run."""
    rounds = max(n_records - 1, 1)
    return [total_seconds * i / rounds for i in range(n_records)]


def _emit_counter_events(counters: Dict[str, Any], offsets: List[float],
                         t0_s: float) -> None:
    """Emit each cumulative series as obs counter samples, timestamped
    at the trajectory's start + the per-round attribution offsets."""
    for name, series in counters.items():
        for val, off in zip(series, offsets):
            obs.counter(name, float(val), ts_s=t0_s + off)


def _run_pscope_scanned(obj, reg, Xp, yp, w0, pcfg, trace, eval_data=None,
                        counters: bool = True):
    """Drive pSCOPE through the zero-sync scanned driver and feed the
    Trace from the device-side history — no per-round host sync.

    `eval_data` is an optional (X_test, y_test) pair (set via
    `SolverConfig.extras["eval"]`, e.g. from
    `datasets.train_test_split`): held-out metrics are evaluated
    post-hoc on the final iterate, outside the compiled trajectory, and
    their cost is charged as recording overhead.

    `counters=True` (the default; opt out via
    `SolverConfig.extras["counters"]`) carries the device-side
    telemetry counters through the scan — same single host transfer,
    values/NNZ bit-identical either way — and surfaces them as
    `trace.counters` plus per-round obs counter events timestamped
    across the trajectory's run; the host-side fan-out is charged as
    recording overhead."""
    t0 = time.perf_counter()
    if counters:
        w, values, nnzs, ctrs = pscope.run_scanned(
            obj, reg, Xp, yp, w0, pcfg, counters=True)
    else:
        w, values, nnzs = pscope.run_scanned(obj, reg, Xp, yp, w0, pcfg)
        ctrs = None
    total = time.perf_counter() - t0
    cdict = None
    if ctrs is not None:
        cdict = {name: ctrs[:, j]
                 for j, name in enumerate(pscope.COUNTER_NAMES)}
    trace.record_history(values, nnzs, comm_per_record=2.0,
                         total_seconds=total, counters=cdict)
    if cdict is not None:
        t_emit = time.perf_counter()
        _emit_counter_events(cdict, _round_offsets(len(values), total),
                             t0)
        trace.charge_overhead(time.perf_counter() - t_emit)
    if eval_data is not None:
        t_eval = time.perf_counter()
        trace.record_heldout(**evaluate_heldout(obj, reg, *eval_data, w))
        trace.charge_overhead(time.perf_counter() - t_eval)
    return w


@register("pscope",
          summary="proximal SCOPE under the CALL framework (this paper)",
          paper_ref="Algorithm 1; Theorems 1-2",
          distributed=True,
          comm_model="2 all-reduces per outer round")
def _run_pscope(obj, reg, part, cfg, trace):
    # extras={"inner_path": "lazy"} flips the same solver onto the sparse
    # engine ("auto" lets the cost model pick); "pscope_lazy" below is
    # the registry-level A/B entry.
    pcfg = _pscope_config(obj, reg, part, cfg,
                          cfg.extras.get("inner_path", "dense"))
    return _run_pscope_scanned(obj, reg, part.Xp, part.yp, _w0(part, cfg),
                               pcfg, trace, cfg.extras.get("eval"),
                               counters=cfg.extras.get("counters", True))


@register("pscope_lazy",
          summary="pSCOPE with the fused sparse lazy-prox inner engine",
          paper_ref="Algorithm 1 + Section 6 (Lemma 11 recovery)",
          distributed=True,
          comm_model="2 all-reduces per outer round")
def _run_pscope_lazy(obj, reg, part, cfg, trace):
    # part.csr_p is the Partition's cached worker-major CSR view: the
    # dense->CSR conversion happens at most once per Partition, not
    # once per solver run (regression-tested).
    pcfg = _pscope_config(obj, reg, part, cfg, "lazy")
    return _run_pscope_scanned(obj, reg, part.csr_p, part.yp,
                               _w0(part, cfg), pcfg, trace,
                               cfg.extras.get("eval"),
                               counters=cfg.extras.get("counters", True))


@register("pscope_mesh",
          summary="pSCOPE over a jax.distributed device mesh (real "
                  "cross-process CALL collectives; comm in bytes)",
          paper_ref="Algorithm 1; Section 5 CALL communication structure",
          distributed=True,
          comm_model="2 d-vector all-reduces per outer round "
                     "(O(d) bytes, independent of n)")
def _run_pscope_mesh(obj, reg, part, cfg, trace):
    """The multi-host layer behind the registry interface.

    Routes the partition's worker-major shards through
    `launch.mesh.run_mesh`: each worker's block lives on one mesh
    device (every process of a `jax.distributed` job places only the
    workers it owns), outer rounds are mesh psums, and `Trace.comm`
    records the analytic BYTES on the wire per round
    (`trace.meta["comm_units"] == "bytes"`) instead of round counts —
    one gradient all-reduce + one iterate average, O(d) and
    independent of n.  Needs one mesh device per worker
    (`jax.device_count() == part.p` across all processes); pass
    `extras={"mesh_spec": MeshSpec(...)}` for a custom layout.
    """
    from repro.launch import mesh as mesh_mod
    inner_path = cfg.extras.get("inner_path", "lazy")
    pcfg = _pscope_config(obj, reg, part, cfg, inner_path)
    data = part.Xp if inner_path == "dense" else part.csr_p
    spec = cfg.extras.get("mesh_spec")
    res = mesh_mod.run_mesh(obj, reg, data, part.yp, _w0(part, cfg), pcfg,
                            spec)
    trace.meta["comm_units"] = "bytes"
    trace.meta["mesh"] = {"num_processes": res.num_processes,
                          "local_worker_ids": list(res.worker_ids),
                          "devices": list(res.worker_devices)}
    # `run_mesh` puts the per-round wire bytes on the timeline as the
    # `comm_bytes` counter; Trace.comm holds the same analytic series
    trace.record_history(res.values, res.nnz,
                         comm_per_record=res.comm_bytes_per_round,
                         total_seconds=res.seconds)
    eval_data = cfg.extras.get("eval")
    if eval_data is not None:
        t_eval = time.perf_counter()
        trace.record_heldout(**evaluate_heldout(obj, reg, *eval_data, res.w))
        trace.charge_overhead(time.perf_counter() - t_eval)
    return jnp.asarray(res.w)


@register("pscope_elastic",
          summary="pSCOPE under an elastic host-failure schedule: "
                  "re-mesh survivors, adopt orphans, resume in place",
          paper_ref="Algorithm 1; data-partition invariance under "
                    "worker re-placement",
          distributed=True,
          comm_model="2 all-reduces per outer round + one KV barrier "
                     "per re-mesh")
def _run_pscope_elastic(obj, reg, part, cfg, trace):
    """Single-process rehearsal of the elastic recovery path.

    Simulates the failure schedule the multi-host layer
    (`launch.elastic.run_mesh_elastic`) handles live: the trajectory
    runs as `run_scanned` segments (RNG fast-forwarded via
    `start_round`); at each scheduled failure the ownership map is
    re-planned with `train.elastic.failure_plan` and the run resumes
    from the in-memory iterate.  Because the logical worker count p
    never changes — survivors merely adopt the orphaned shards — the
    trace is identical to `pscope_lazy` on the same problem: that
    placement transparency IS the correctness property, and the
    recovery events land in ``trace.meta["elastic"]``.

    extras:
      hosts       initial host count (default: p, one worker each)
      fail_at     round of the first failure (default: rounds // 2)
      fail_ranks  ranks to kill at fail_at (default: highest rank)
      rejoin_at   round the killed ranks rejoin (default: no rejoin);
                  ownership re-planned with `rebalance_plan` — the
                  scale-up inverse of `failure_plan`
    """
    from repro.train.elastic import (failure_plan, initial_ownership,
                                     rebalance_plan)

    hosts = int(cfg.extras.get("hosts", part.p))
    fail_at = int(cfg.extras.get("fail_at", max(1, cfg.rounds // 2)))
    fail_ranks = set(int(r) for r in cfg.extras.get(
        "fail_ranks", [hosts - 1]))
    rejoin_at = cfg.extras.get("rejoin_at")
    if not 0 < fail_at < cfg.rounds:
        raise ValueError(f"fail_at must fall inside the run "
                         f"(0 < {fail_at} < {cfg.rounds})")
    if rejoin_at is not None:
        rejoin_at = int(rejoin_at)
        if not fail_at < rejoin_at < cfg.rounds:
            raise ValueError(
                f"rejoin_at must land strictly between fail_at "
                f"({fail_at}) and rounds ({cfg.rounds}), got {rejoin_at}")

    pcfg = _pscope_config(obj, reg, part, cfg, "lazy")
    ownership = initial_ownership(part.p, hosts)
    t0 = time.perf_counter()
    seg1 = dataclasses.replace(pcfg, outer_steps=fail_at)
    w, v1, n1 = pscope.run_scanned(obj, reg, part.csr_p, part.yp,
                                   _w0(part, cfg), seg1)
    t_remesh = time.perf_counter()
    ownership = failure_plan(ownership, fail_ranks)
    remesh_s = time.perf_counter() - t_remesh
    events = [{"round": fail_at, "resume_round": fail_at,
               "rounds_to_recover": 0, "joiners": [],
               "dead": sorted(fail_ranks), "epoch": 1,
               "remesh_seconds": remesh_s,
               "survivors": sorted(ownership),
               "ownership": {int(r): list(ws)
                             for r, ws in ownership.items()}}]
    obs.instant("elastic.remesh", round=fail_at, epoch=1,
                dead=sorted(fail_ranks), joiners=[],
                survivors=sorted(ownership))

    segments = []
    if rejoin_at is not None:
        segments.append((fail_at, rejoin_at, None))
        segments.append((rejoin_at, cfg.rounds, sorted(fail_ranks)))
    else:
        segments.append((fail_at, cfg.rounds, None))

    values, nnzs = [v1], [n1]
    for start, end, joiners in segments:
        if joiners:
            t_remesh = time.perf_counter()
            ownership = rebalance_plan(ownership, joiners)
            events.append({
                "round": start, "resume_round": start,
                "rounds_to_recover": 0, "joiners": joiners,
                "dead": [], "epoch": len(events) + 1,
                "remesh_seconds": time.perf_counter() - t_remesh,
                "survivors": sorted(ownership),
                "ownership": {int(r): list(ws)
                              for r, ws in ownership.items()}})
            obs.instant("elastic.remesh", round=start, epoch=len(events),
                        dead=[], joiners=list(joiners),
                        survivors=sorted(ownership))
        seg = dataclasses.replace(pcfg, outer_steps=end - start)
        w, v, n = pscope.run_scanned(obj, reg, part.csr_p, part.yp, w,
                                     seg, start_round=start)
        values.append(v[1:])
        nnzs.append(n[1:])

    values = np.concatenate(values)
    nnzs = np.concatenate(nnzs)
    trace.meta["elastic"] = {"hosts": hosts, "events": events}
    trace.record_history(values, nnzs, comm_per_record=2.0,
                         total_seconds=time.perf_counter() - t0)
    return jnp.asarray(w)


@register("fista",
          summary="accelerated proximal gradient (Beck & Teboulle 2009)",
          paper_ref="Section 7.1 baseline; distributed gradient variant",
          distributed=False,
          comm_model="1 all-reduce per iteration")
def _run_fista(obj, reg, part, cfg, trace):
    w, _ = fista_history(obj, reg, part.X, part.y, _w0(part, cfg),
                         iters=cfg.rounds * cfg.record_every,
                         record_every=cfg.record_every,
                         on_record=trace.recorder(float(cfg.record_every)))
    return w


@register("pgd",
          summary="proximal gradient descent",
          paper_ref="eq. (2)",
          distributed=False,
          comm_model="1 all-reduce per iteration")
def _run_pgd(obj, reg, part, cfg, trace):
    w, _ = pgd_history(obj, reg, part.X, part.y, _w0(part, cfg),
                       iters=cfg.rounds * cfg.record_every,
                       record_every=cfg.record_every,
                       on_record=trace.recorder(float(cfg.record_every)))
    return w


@register("prox_svrg",
          summary="serial proximal SVRG (Xiao & Zhang 2014)",
          paper_ref="Corollary 2 (pSCOPE with p = 1)",
          distributed=False,
          comm_model="none (serial)")
def _run_prox_svrg(obj, reg, part, cfg, trace):
    inner = cfg.extras.get(
        "inner_steps", max(1, int(cfg.inner_epochs * part.n)))
    w, _ = prox_svrg_history(obj, reg, part.X, part.y, _w0(part, cfg),
                             eta=_default_eta(obj, reg, part, cfg),
                             inner_steps=inner, outer_steps=cfg.rounds,
                             inner_batch=cfg.extras.get("inner_batch", 1),
                             seed=cfg.seed, on_record=trace.recorder(0.0))
    return w


@register("dpsgd",
          summary="distributed minibatch proximal SGD",
          paper_ref="Section 7.1 baseline (Li et al. 2016-style)",
          distributed=True,
          comm_model="1 all-reduce per step")
def _run_dpsgd(obj, reg, part, cfg, trace):
    w, _ = dpsgd_history(obj, reg, part.Xp, part.yp, _w0(part, cfg),
                         eta0=_default_eta(obj, reg, part, cfg),
                         steps=cfg.rounds * cfg.record_every,
                         batch=cfg.batch, record_every=cfg.record_every,
                         seed=cfg.seed, decay=cfg.extras.get("decay", 0.0),
                         on_record=trace.recorder(float(cfg.record_every)))
    return w


@register("dpsvrg",
          summary="distributed minibatch proximal SVRG (AsyProx-SVRG core)",
          paper_ref="Section 7.1 baseline (Meng et al. 2017, synchronous)",
          distributed=True,
          comm_model="1 all-reduce per inner step (+1 per epoch)")
def _run_dpsvrg(obj, reg, part, cfg, trace):
    inner = cfg.extras.get(
        "inner_steps",
        max(1, int(cfg.inner_epochs * part.n_k / max(cfg.batch, 1))))
    w, _ = dpsvrg_history(obj, reg, part.Xp, part.yp, _w0(part, cfg),
                          eta=_default_eta(obj, reg, part, cfg),
                          inner_steps=inner, outer_steps=cfg.rounds,
                          batch=cfg.batch, seed=cfg.seed,
                          on_record=trace.recorder(float(inner + 1)))
    return w


@register("admm",
          summary="consensus ADMM with inexact local solves",
          paper_ref="Section 7.1 baseline (DFAL-family splitting)",
          distributed=True,
          comm_model="1 gather per outer iteration")
def _run_admm(obj, reg, part, cfg, trace):
    w, _ = admm_history(obj, reg, part.Xp, part.yp, _w0(part, cfg),
                        rho=cfg.extras.get("rho", 1.0),
                        outer_steps=cfg.rounds,
                        local_gd_steps=cfg.extras.get("local_gd_steps", 20),
                        on_record=trace.recorder(1.0))
    return w


@register("owlqn",
          summary="orthant-wise L-BFGS for L1 (mOWL-QN, Gong & Ye 2015)",
          paper_ref="Section 7.1 baseline; distributed gradient variant",
          distributed=False,
          comm_model="1 all-reduce per iteration (+ line-search evals)")
def _run_owlqn(obj, reg, part, cfg, trace):
    w, _ = owlqn_history(obj, reg, part.X, part.y, _w0(part, cfg),
                         iters=cfg.rounds * cfg.record_every,
                         mem=cfg.extras.get("mem", 10),
                         record_every=cfg.record_every,
                         on_record=trace.recorder(float(cfg.record_every)))
    return w


@register("dbcd",
          summary="distributed block coordinate descent (Mahajan et al.)",
          paper_ref="Section 7.1 baseline; Table 2 timing comparison",
          distributed=False,
          comm_model="1 prediction sync (O(n)) per round")
def _run_dbcd(obj, reg, part, cfg, trace):
    w, _ = dbcd_history(obj, reg, part.X, part.y, _w0(part, cfg),
                        p=part.p, outer_steps=cfg.rounds * cfg.record_every,
                        record_every=cfg.record_every,
                        on_record=trace.recorder(float(cfg.record_every)))
    return w


@register("cocoa",
          summary="proxCoCoA+-style local-subproblem method (Smith et al.)",
          paper_ref="Section 7.1 baseline; CoCoA L1 framework of PAPERS.md",
          distributed=False,
          comm_model="1 delta-w all-reduce per round")
def _run_cocoa(obj, reg, part, cfg, trace):
    w, _ = cocoa_history(obj, reg, part.X, part.y, _w0(part, cfg),
                         p=part.p, outer_steps=cfg.rounds * cfg.record_every,
                         local_steps=cfg.extras.get("local_steps", 10),
                         record_every=cfg.record_every,
                         on_record=trace.recorder(float(cfg.record_every)))
    return w
