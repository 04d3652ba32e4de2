"""Proximal SCOPE (pSCOPE) — Algorithm 1 of the paper.

Cooperative Autonomous Local Learning (CALL):
  outer step t:
    1. z  = grad F(w_t)                      (one DP all-reduce)
    2. each worker runs M inner prox-SVRG steps on its local shard,
       u <- prox_{R,eta}(u - eta * (grad f_i(u) - grad f_i(w_t) + z)),
       with NO communication
    3. w_{t+1} = (1/p) sum_k u_{k,M}         (second DP all-reduce)

Two execution modes:
  * `pscope_outer_step` — single-program simulation: the worker axis is
    a leading array dimension, inner loops vmapped.  Used for unit
    tests, benchmarks and partition studies on CPU.  Bitwise-defined
    semantics identical to the distributed mode.
  * `make_distributed_outer_step` — shard_map over a real mesh axis;
    the inner scan contains no DP collectives (this is the paper's
    communication structure, which tests audit in the compiled HLO).

Inner-loop engines, selected by `PScopeConfig.inner_path`:
  * "dense" — the microbatch VR gradient and the prox touch all d
    coordinates every step, the three elementwise stages fused into one
    VMEM pass by `kernels.ops.fused_prox_svrg` / `fused_prox_svrg_diff`.
  * "lazy"  — the fused sparse engine for high-dimensional CSR data
    (Section 6): per-step work scales with the microbatch's nonzero
    count, not d.  The whole epoch's catch-up bookkeeping (which
    coordinates each step touches and how stale they are) is hoisted
    out of the scan into a precomputed gather plan (`core.plan`), so
    each step is ONE gather + the Lemma-11 catch-up + the
    support-restricted VR step + ONE scatter
    (`kernels.ops.fused_lazy_epoch`; on TPU the entire epoch is a
    single Pallas kernel with the iterate resident in VMEM).  Requires
    a linear-model objective (svrg.LINEAR_MODEL_H_PRIME) and data as a
    `data.sparse.CSRMatrix`.
  * "auto" — a calibrated cost model (`plan.choose_inner_path`) picks
    dense vs lazy from (d, M, b, nnz) at run start.

All engines produce the same trajectory on the same sample sequence
(up to fp32 reassociation); tests/test_lazy_pscope.py and
tests/test_fused_inner.py enforce it (the PR-2 per-step scan survives
as `_lazy_inner_loop_ref`, the reference oracle).

Drivers: `run`/`run_distributed` execute the outer loop either as a
classic Python loop (one dispatch + host sync per round — required for
streaming `on_record` callbacks) or as a **zero-sync scanned driver**:
the whole T-round trajectory is one `lax.scan` inside one jit, the
objective/NNZ history accumulates in a device-side buffer, and the
host sees exactly one transfer at the end.  `run_scanned` /
`run_distributed_scanned` expose the device histories directly (the
`core.solvers.Trace` recorder is fed from them post-hoc).

p = 1 degenerates to proximal SVRG (Xiao & Zhang 2014), Corollary 2.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import plan as plan_mod
from repro.core import svrg
from repro.core.prox import Regularizer, prox_elastic_net
from repro.core.recovery import recovery_catch_up
from repro.core.objectives import Objective
from repro.data.sparse import CSRMatrix, EncodedCSR, dense_to_csr
from repro.kernels import ops

Array = jax.Array

NNZ_TOL = 1e-8   # |w_i| above this counts as a nonzero (Section 7.3)

# The CALL communication structure: one anchor-gradient psum + one
# iterate average per outer round, each moving a d-vector; the inner
# loops are collective-free.  `launch.mesh.comm_bytes_per_round` turns
# this into the analytic bytes-on-wire figure the mesh driver records.
COMM_ALLREDUCES_PER_ROUND = 2

# Device-side per-round counters carried through the scan when
# `run_scanned(..., counters=True)`: cumulative over rounds, one f32
# per name, surfaced post-hoc as `core.solvers.Trace.counters`.
#   catch_up    — Lemma-11 catch-up replays actually executed: the sum
#                 of the epoch plan's per-slot staleness counts q
#   prox_skip   — autonomous prox steps deferred to the end-of-epoch
#                 final catch-up (the plan's q_f residuals)
#   dup_fold    — the fused epoch kernel's duplicate-fold iterations:
#                 the sum of the steps' `plan.fold_bounds` (0 when no
#                 sampled row repeats a column)
COUNTER_NAMES = ("catch_up", "prox_skip", "dup_fold")

# Named scopes of an outer round's phases.  They land in the HLO
# `op_name` metadata of every op the phase emits, so a profiler trace
# attributes device time to a phase (docs/observability.md).  The epoch
# kernel keeps its own name and gets no scope.
SCOPE_ANCHOR_GRAD = "pscope.anchor_grad"   # phase 1 and its all-reduce
SCOPE_PLAN = "pscope.plan"                 # sampling, epoch plan, statics
# (inside SCOPE_PLAN, `plan.SCOPE_PLAN_WIDE` names the plan past int32 keys)
SCOPE_GATHER = "pscope.gather"             # the steps' operand gathers
SCOPE_AVERAGE = "pscope.average"           # phase 3 and its all-reduce
SCOPE_OBJECTIVE = "pscope.objective"       # recorded P(w) and NNZ


@dataclasses.dataclass(frozen=True)
class PScopeConfig:
    eta: float = 0.1            # inner learning rate
    inner_steps: int = 64       # M
    inner_batch: int = 1        # b (=1 reproduces Algorithm 1 exactly)
    outer_steps: int = 30       # T
    seed: int = 0
    # Straggler mitigation: if participation[k] == 0 for an outer round,
    # worker k's iterate is excluded from the average (weights renormalized).
    # None = all participate (the paper's setting).
    use_linear_model_fastpath: bool = True
    # Inner-loop engine: "dense" (full-vector updates, fused Pallas prox),
    # "lazy" (epoch-planned support-restricted updates + Lemma-11
    # catch-up; needs CSR data and a linear-model objective), or "auto"
    # (calibrated cost model picks per run).
    inner_path: str = "dense"


class PScopeState(NamedTuple):
    w: Array          # global iterate (d,)
    t: Array          # outer step counter
    key: Array
    # cumulative telemetry counters, (len(COUNTER_NAMES),) f32, or None
    # (the default: counter-free states are pytree-identical to the
    # pre-telemetry layout, so every existing caller is untouched).
    # Never feeds back into w/key — the iterate path is bit-identical
    # with counters on or off.
    ctr: Optional[Array] = None


def init_state(w0: Array, seed: int = 0) -> PScopeState:
    return PScopeState(w=w0, t=jnp.zeros((), jnp.int32),
                       key=jax.random.PRNGKey(seed))


@jax.jit
def _advance_key_jit(key: Array, t: Array) -> Array:
    return jax.lax.fori_loop(0, t, lambda i, k: jax.random.split(k)[0], key)


def advance_key(key: Array, rounds: int) -> Array:
    """The scan-carry key after `rounds` outer steps.

    Every outer step derives `key, k_idx = jax.random.split(key)` and
    carries the first half, so the key entering round t is split^t of
    the seed key.  This is what lets a run RESUME mid-trajectory (the
    elastic re-mesh path, `run_scanned(start_round=t)`): fast-forward
    the seed key t splits and round t draws the identical per-worker
    sample sequence the uninterrupted run would have drawn.
    """
    rounds = int(rounds)
    if rounds < 0:
        raise ValueError(f"cannot rewind a split chain (rounds={rounds})")
    if rounds == 0:
        return key
    return _advance_key_jit(key, jnp.asarray(rounds, jnp.int32))


# ---------------------------------------------------------------------------
# Dense inner loop (fused elementwise path)
# ---------------------------------------------------------------------------

def _inner_loop(loss_fn: Callable, reg: Regularizer, eta: float,
                u0: Array, w_anchor: Array, z: Array,
                Xk: Array, yk: Array, idx: Array,
                h_prime: Optional[Callable] = None) -> Array:
    """M inner prox-SVRG steps on one worker's shard. idx: (M, b).

    The elementwise tail of every step — combine the VR gradient,
    take the eta-step, apply the elastic-net prox — runs as a single
    fused Pallas VMEM pass instead of 3 unfused O(d) ops.
    """

    def step(u, ix):
        with jax.named_scope(SCOPE_GATHER):
            Xb = jnp.take(Xk, ix, axis=0)
            yb = jnp.take(yk, ix, axis=0)
        if h_prime is not None:
            dv = svrg.linear_model_vr_diff(h_prime, u, w_anchor, Xb, yb)
            u = ops.fused_prox_svrg_diff(u, dv, z, eta=eta, lam1=reg.lam1,
                                         lam2=reg.lam2)
        else:
            g_u, g_w = svrg.vr_gradient_pair(loss_fn, u, w_anchor, Xb, yb)
            u = ops.fused_prox_svrg(u, g_u, g_w, z, eta=eta, lam1=reg.lam1,
                                    lam2=reg.lam2)
        return u, None

    u, _ = jax.lax.scan(step, u0, idx)
    return u


# ---------------------------------------------------------------------------
# Fused lazy sparse inner loop (epoch gather plan + fused step)
# ---------------------------------------------------------------------------

def _lazy_inner_loop(h_prime: Callable, reg: Regularizer, eta: float,
                     u0: Array, w_anchor: Array, z: Array,
                     vals_k: Array, cols_k: Array, yk: Array,
                     idx: Array,
                     statics: Optional[plan_mod.ShardStatics] = None,
                     with_stats: bool = False):
    """M fused inner steps touching only each microbatch's columns.

    `with_stats=True` additionally returns a (3,) f32 of this epoch's
    plan-derived work counters — (sum of catch-up replays q, sum of
    final-catch-up residuals q_f, duplicate-fold iterations) — read
    straight off the already-built `EpochPlan`, so the iterate math is
    untouched (see COUNTER_NAMES).

    All catch-up bookkeeping — which columns each step touches, how
    many autonomous prox steps each must replay (Lemma 11), which slots
    are duplicates — depends only on the sampled index sequence, so it
    is hoisted out of the scan into one vectorized plan build
    (`core.plan.build_epoch_plan`).  The anchor-side operands (z and
    w_anchor gathers, the anchor VR coefficients) are constant across
    the epoch and pre-gathered in single (M, ...) passes.  What remains
    per step is exactly one iterate gather, the catch-up + VR step +
    elastic-net prox math, and one duplicate-safe scatter
    (`kernels.ops.fused_lazy_epoch`; the PR-2 engine paid 4 gathers +
    3 scatters + an int32 bookkeeping scatter per step).

    `statics` carries the data-only shard precomputes (duplicate sums,
    membership table) built once per run by the drivers; if None they
    are rebuilt here (correct, but repays the precompute every epoch).

    The catch-up replays the STANDARD elastic-net prox iteration
        u <- S(u - eta z, eta lam2) / (1 + eta lam1)
    which equals the Lemma-11 linearized iteration at the effective
    step size eta_eff = eta / (1 + eta·lam1)  (S(ax, at) = a S(x, t));
    for pure L1 the two coincide.  This keeps the lazy engine bit-
    compatible with the dense path's prox convention.
    """
    d = u0.shape[0]
    with jax.named_scope(SCOPE_PLAN):
        if statics is None:
            n_k, k = cols_k.shape
            statics = plan_mod.shard_statics(
                vals_k, cols_k,
                with_member=plan_mod.default_with_member(
                    n_k, k, inner_batch=idx.shape[1]))
        eplan = plan_mod.build_epoch_plan(cols_k, idx, d, statics)
    with jax.named_scope(SCOPE_GATHER):
        gathers = plan_mod.epoch_gathers(h_prime, w_anchor, z, vals_k, yk,
                                         idx, eplan.cflat, statics)
    u = ops.fused_lazy_epoch(u0, z, eplan, gathers, h_prime=h_prime,
                             eta=eta, lam1=reg.lam1, lam2=reg.lam2,
                             inner_batch=idx.shape[1])
    if not with_stats:
        return u
    return u, _epoch_plan_stats(eplan, idx.shape[1])


def _lazy_inner_loop_enc(h_prime: Callable, reg: Regularizer, eta: float,
                         u0: Array, w_anchor: Array, z: Array,
                         vals16_k: Array, colb_k: Array, dcols_k: Array,
                         nnz_k: Array, yk: Array, idx: Array,
                         statics: Optional[plan_mod.ShardStatics] = None,
                         with_stats: bool = False):
    """`_lazy_inner_loop` over an ENCODED shard (datasets codec leaves).

    The decode is fused into the epoch, not materialized up front:
    columns are reconstructed from (first col, deltas, row_nnz) by a
    masked cumsum feeding the plan build directly, and the value gather
    moves uint16 bf16 bits — half the bytes of f32 — which the epoch
    kernels bitcast to f32 at use (`EpochGathers.vb` dtype dispatch).
    On bf16-representable data the trajectory is bitwise identical to
    the raw-store path: the bits -> f32 bitcast is exact, and the plan
    depends only on the (exactly reconstructed) integer columns.
    """
    d = u0.shape[0]
    enc = EncodedCSR(vals16=vals16_k, colb=colb_k, dcols=dcols_k,
                     row_nnz=nnz_k, d=d)
    with jax.named_scope(SCOPE_PLAN):
        cols_k = enc.decode_cols()
        if statics is None:
            n_k, k = cols_k.shape
            statics = plan_mod.shard_statics(
                enc.decode_vals(), cols_k,
                with_member=plan_mod.default_with_member(
                    n_k, k, inner_batch=idx.shape[1]))
        eplan = plan_mod.build_epoch_plan(cols_k, idx, d, statics)
    with jax.named_scope(SCOPE_GATHER):
        gathers = plan_mod.epoch_gathers(h_prime, w_anchor, z, vals16_k, yk,
                                         idx, eplan.cflat, statics)
    u = ops.fused_lazy_epoch(u0, z, eplan, gathers, h_prime=h_prime,
                             eta=eta, lam1=reg.lam1, lam2=reg.lam2,
                             inner_batch=idx.shape[1])
    if not with_stats:
        return u
    return u, _epoch_plan_stats(eplan, idx.shape[1])


def _epoch_plan_stats(eplan, inner_batch: int) -> Array:
    """(catch_up, prox_skip, dup_fold) for one epoch, read off the
    gather plan."""
    with jax.named_scope(SCOPE_PLAN):
        fold = plan_mod.fold_bounds(eplan.rep, inner_batch)
        return jnp.stack([jnp.sum(eplan.q.astype(jnp.float32)),
                          jnp.sum(eplan.qf.astype(jnp.float32)),
                          jnp.sum(fold.astype(jnp.float32))])


def _lazy_inner_loop_ref(h_prime: Callable, reg: Regularizer, eta: float,
                         u0: Array, w_anchor: Array, z: Array,
                         vals_k: Array, cols_k: Array, yk: Array,
                         idx: Array) -> Array:
    """The PR-2 per-step lazy scan — kept as the reference oracle.

    Bookkeeping: `last[j]` = the inner step coordinate j is current at,
    carried through the scan; each step gathers/catches up/updates its
    microbatch's columns and stamps them.  Produces the identical
    trajectory to `_lazy_inner_loop` (tests/test_fused_inner.py) and
    anchors the `inner_loop/lazy/*` rows of BENCH_inner_loop.json.
    """
    lam1, lam2 = reg.lam1, reg.lam2
    eta_eff = eta / (1.0 + eta * lam1)
    M = idx.shape[0]

    def step(carry, mi):
        u, last = carry
        m, ix = mi
        vb = jnp.take(vals_k, ix, axis=0)        # (b, k)
        cb = jnp.take(cols_k, ix, axis=0)        # (b, k)
        yb = jnp.take(yk, ix, axis=0)
        cflat = cb.reshape(-1)
        z_t = jnp.take(z, cflat, axis=0)

        q = m - jnp.take(last, cflat, axis=0)
        u_t = recovery_catch_up(jnp.take(u, cflat, axis=0), z_t, q,
                                eta_eff, lam1, lam2)

        w_active = jnp.take(w_anchor, cflat, axis=0).reshape(vb.shape)
        ge = svrg.sparse_vr_gradient_entries(h_prime, u_t.reshape(vb.shape),
                                             w_active, vb, yb)

        u = u.at[cflat].set(u_t - eta * z_t)
        u = u.at[cflat].add(-eta * ge.reshape(-1))
        u = u.at[cflat].set(prox_elastic_net(jnp.take(u, cflat, axis=0),
                                             eta, lam1, lam2))
        last = last.at[cflat].set(m + 1)
        return (u, last), None

    steps = (jnp.arange(M, dtype=jnp.int32), idx)
    (u, last), _ = jax.lax.scan(step, (u0, jnp.zeros_like(u0, jnp.int32)),
                                steps)
    return ops.lazy_prox(u, z, M - last, eta=eta_eff, lam1=lam1, lam2=lam2)


def _pick_h_prime(obj: Objective, cfg: PScopeConfig):
    if not cfg.use_linear_model_fastpath:
        return None
    return svrg.LINEAR_MODEL_H_PRIME.get(obj.name)


def _require_lazy_support(obj: Objective, cfg: PScopeConfig):
    h_prime = svrg.LINEAR_MODEL_H_PRIME.get(obj.name)
    if h_prime is None:
        raise ValueError(
            f"inner_path='lazy' needs a linear-model objective with a "
            f"registered h' (svrg.LINEAR_MODEL_H_PRIME); got {obj.name!r}")
    return h_prime


def _as_csr_shards(Xp, yp):
    """Accept worker-major CSR/encoded directly, or convert dense
    (p, n_k, d)."""
    if isinstance(Xp, (CSRMatrix, EncodedCSR)):
        return Xp, yp
    p, n_k, d = Xp.shape
    flat = dense_to_csr(jnp.reshape(Xp, (p * n_k, d)))
    shaped = CSRMatrix(vals=flat.vals.reshape(p, n_k, -1),
                       cols=flat.cols.reshape(p, n_k, -1),
                       row_nnz=flat.row_nnz.reshape(p, n_k), d=d)
    return shaped, yp


def _resolve_inner_path(obj: Objective, cfg: PScopeConfig,
                        X) -> PScopeConfig:
    """Materialize inner_path="auto" via the calibrated cost model.

    `X` is whatever data layout the caller holds — worker-major dense,
    worker-major CSR, flat dense or flat CSR; only its shape/nnz feed
    the model.
    """
    if cfg.inner_path != "auto":
        return cfg
    if isinstance(X, (CSRMatrix, EncodedCSR)):
        # CSR/encoded input can only feed the lazy engine — there is no
        # dense view to fall back to, so the cost model has no choice to
        # make (an unsupported objective still gets the clear
        # _require_lazy_support error downstream)
        return dataclasses.replace(cfg, inner_path="lazy")
    lazy_ok = svrg.LINEAR_MODEL_H_PRIME.get(obj.name) is not None
    d = X.shape[-1]
    # one O(n*d) pass at setup; the padded CSR slice width is what
    # the lazy engine would actually gather per row
    k = int(np.max(np.sum(np.asarray(X) != 0, axis=-1), initial=1))
    path = plan_mod.choose_inner_path(d, cfg.inner_steps, cfg.inner_batch,
                                      k, lazy_supported=lazy_ok)
    return dataclasses.replace(cfg, inner_path=path)


def _sim_statics(csr_p, cfg: PScopeConfig) -> plan_mod.ShardStatics:
    """Per-worker shard statics for simulation mode, built once per run.

    Encoded shards decode once here — the statics (duplicate sums,
    representatives) are f32/int32 precomputes either way, and the
    decode is exact, so statics from an encoded store equal the raw
    store's bitwise.
    """
    if isinstance(csr_p, EncodedCSR):
        vals, cols = csr_p.decode_vals(), csr_p.decode_cols()
    else:
        vals, cols = csr_p.vals, csr_p.cols
    p, n_k, k = vals.shape
    with_member = plan_mod.default_with_member(n_k, k, workers=p,
                                               inner_batch=cfg.inner_batch)
    return jax.vmap(functools.partial(plan_mod.shard_statics,
                                      with_member=with_member))(vals, cols)


# ---------------------------------------------------------------------------
# Simulation-mode outer steps (worker axis = leading array dim, vmapped)
# ---------------------------------------------------------------------------

def _outer_step_core(obj: Objective, reg: Regularizer, cfg: PScopeConfig,
                     state: PScopeState, Xp: Array, yp: Array,
                     participation: Optional[Array]) -> PScopeState:
    """One dense outer iteration (unjitted core; scan-able)."""
    p, n_k, _ = Xp.shape
    w_t = state.w

    # --- phase 1: full gradient (the first "all-reduce") ------------------
    with jax.named_scope(SCOPE_ANCHOR_GRAD):
        local_grads = jax.vmap(
            lambda X, y: jax.grad(obj.loss_fn)(w_t, X, y))(Xp, yp)
        z = jnp.mean(local_grads, axis=0)

    # --- phase 2: autonomous local learning (no communication) ------------
    with jax.named_scope(SCOPE_PLAN):
        key, idx = _sample_round(state.key, p, n_k, cfg)
    h_prime = _pick_h_prime(obj, cfg)
    inner = functools.partial(_inner_loop, obj.loss_fn, reg, cfg.eta,
                              h_prime=h_prime)
    u_final = jax.vmap(lambda Xk, yk, ixk: inner(w_t, w_t, z, Xk, yk, ixk))(
        Xp, yp, idx)

    # --- phase 3: cooperative averaging (the second "all-reduce") ---------
    with jax.named_scope(SCOPE_AVERAGE):
        w_next = _average(u_final, participation)
    # the dense engine has no epoch plan: its counters stay at zero
    return PScopeState(w=w_next, t=state.t + 1, key=key, ctr=state.ctr)


def _sample_round(key: Array, p: int, n_k: int, cfg: PScopeConfig):
    """(carried key, (p, M, b) microbatch indices) of one outer round:
    worker k draws from split(k_idx, p)[k]."""
    key, k_idx = jax.random.split(key)
    idx = jax.vmap(
        lambda k: svrg.sample_microbatches(k, n_k, cfg.inner_steps,
                                           cfg.inner_batch)
    )(jax.random.split(k_idx, p))
    return key, idx


def _outer_step_lazy_core(obj: Objective, reg: Regularizer,
                          cfg: PScopeConfig, state: PScopeState,
                          csr_p: CSRMatrix, yp: Array,
                          participation: Optional[Array],
                          statics: Optional[plan_mod.ShardStatics]
                          ) -> PScopeState:
    """One fused-lazy outer iteration (unjitted core; scan-able).

    `csr_p` is worker-major: a `CSRMatrix`, or an `EncodedCSR` from a
    codec shard store — the encoded form is consumed directly (phase 1
    decodes inside the jit where XLA fuses the bitcast/cumsum into the
    scatter-add; phase 2 gathers bf16 bits, see `_lazy_inner_loop_enc`).
    """
    h_prime = _require_lazy_support(obj, cfg)
    encoded = isinstance(csr_p, EncodedCSR)
    p, n_k = yp.shape
    d = state.w.shape[0]
    w_t = state.w

    # --- phase 1: anchor gradient via sparse scatter-add ------------------
    with jax.named_scope(SCOPE_ANCHOR_GRAD):
        if encoded:
            vals_p, cols_p = csr_p.decode_vals(), csr_p.decode_cols()
        else:
            vals_p, cols_p = csr_p.vals, csr_p.cols
        local_grads = jax.vmap(
            lambda v, c, y: svrg.sparse_linear_model_full_gradient(
                h_prime, w_t, v, c, y, d))(vals_p, cols_p, yp)
        z = jnp.mean(local_grads, axis=0)

    # --- phase 2: fused lazy autonomous local learning --------------------
    with jax.named_scope(SCOPE_PLAN):
        key, idx = _sample_round(state.key, p, n_k, cfg)
    want_stats = state.ctr is not None
    if encoded:
        inner = functools.partial(_lazy_inner_loop_enc, h_prime, reg,
                                  cfg.eta, with_stats=want_stats)
        if statics is None:
            out = jax.vmap(
                lambda v16, cb, dc, nz, yk, ixk: inner(
                    w_t, w_t, z, v16, cb, dc, nz, yk, ixk))(
                    csr_p.vals16, csr_p.colb, csr_p.dcols, csr_p.row_nnz,
                    yp, idx)
        else:
            out = jax.vmap(
                lambda v16, cb, dc, nz, yk, ixk, st: inner(
                    w_t, w_t, z, v16, cb, dc, nz, yk, ixk, statics=st))(
                    csr_p.vals16, csr_p.colb, csr_p.dcols, csr_p.row_nnz,
                    yp, idx, statics)
    else:
        inner = functools.partial(_lazy_inner_loop, h_prime, reg, cfg.eta,
                                  with_stats=want_stats)
        if statics is None:
            out = jax.vmap(
                lambda v, c, yk, ixk: inner(w_t, w_t, z, v, c, yk, ixk))(
                    csr_p.vals, csr_p.cols, yp, idx)
        else:
            out = jax.vmap(
                lambda v, c, yk, ixk, st: inner(w_t, w_t, z, v, c, yk, ixk,
                                                statics=st))(
                    csr_p.vals, csr_p.cols, yp, idx, statics)

    # --- phase 3: cooperative averaging -----------------------------------
    ctr = state.ctr
    if want_stats:
        u_final, stats_w = out          # stats_w: (p, 3) per-worker sums
        with jax.named_scope(SCOPE_PLAN):
            ctr = ctr + jnp.sum(stats_w, axis=0)
    else:
        u_final = out
    with jax.named_scope(SCOPE_AVERAGE):
        w_next = _average(u_final, participation)
    return PScopeState(w=w_next, t=state.t + 1, key=key, ctr=ctr)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def pscope_outer_step(obj: Objective, reg: Regularizer, cfg: PScopeConfig,
                      state: PScopeState, Xp: Array, yp: Array,
                      participation: Optional[Array] = None) -> PScopeState:
    """One outer iteration. Xp: (p, n_k, d), yp: (p, n_k).

    Simulation mode: workers along axis 0, inner loops vmapped.
    """
    return _outer_step_core(obj, reg, cfg, state, Xp, yp, participation)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def pscope_outer_step_lazy(obj: Objective, reg: Regularizer,
                           cfg: PScopeConfig, state: PScopeState,
                           csr_p: CSRMatrix, yp: Array,
                           participation: Optional[Array] = None,
                           statics: Optional[plan_mod.ShardStatics] = None
                           ) -> PScopeState:
    """Sparse outer iteration: csr_p holds worker-major (p, n_k, k) CSR.

    Same three CALL phases as `pscope_outer_step`, but every phase is
    support-restricted: the anchor gradient is one O(nnz) scatter-add
    per worker, and the inner loops run the epoch-planned fused engine.
    Pass `statics` (from `plan.shard_statics`, vmapped) to amortize the
    data-only precomputes across rounds — `run` does.
    """
    return _outer_step_lazy_core(obj, reg, cfg, state, csr_p, yp,
                                 participation, statics)


def _average(u_final: Array, participation: Optional[Array]) -> Array:
    if participation is None:
        return jnp.mean(u_final, axis=0)
    wts = participation.astype(u_final.dtype)
    return jnp.sum(u_final * wts[:, None], axis=0) / jnp.maximum(
        jnp.sum(wts), 1.0)


def _objective_value_device(obj: Objective, reg: Regularizer, Xp, yp):
    """w -> P(w) over the full dataset as a pure device function."""
    if isinstance(Xp, (CSRMatrix, EncodedCSR)):
        h_loss = svrg.LINEAR_MODEL_H_LOSS[obj.name]
        if isinstance(Xp, EncodedCSR):
            # decode lazily inside the jit'd evaluation — only recorded
            # rounds pay it, and XLA fuses the bitcast into the margins
            k = Xp.vals16.shape[-1]
            enc, yflat = Xp, yp.reshape(-1)
            return lambda w: svrg.sparse_linear_model_loss(
                h_loss, w, enc.decode_vals().reshape(-1, k),
                enc.decode_cols().reshape(-1, k), yflat) + reg.value(w)
        k = Xp.vals.shape[-1]
        vals = Xp.vals.reshape(-1, k)
        cols = Xp.cols.reshape(-1, k)
        yflat = yp.reshape(-1)
        return lambda w: svrg.sparse_linear_model_loss(
            h_loss, w, vals, cols, yflat) + reg.value(w)
    Xflat = Xp.reshape(-1, Xp.shape[-1])
    yflat = yp.reshape(-1)
    return lambda w: obj.loss(w, Xflat, yflat) + reg.value(w)


def _objective_value_fn(obj: Objective, reg: Regularizer, Xp, yp,
                        cfg: PScopeConfig):
    """jit'd w -> P(w), matching the data layout."""
    return jax.jit(_objective_value_device(obj, reg, Xp, yp))


def _resolve_driver(driver: str, on_record) -> str:
    """Validate and materialize the run/run_distributed driver choice."""
    if driver not in ("auto", "scan", "python"):
        raise ValueError(f"unknown driver {driver!r}")
    if driver == "scan" and on_record is not None:
        raise ValueError("driver='scan' records on device; on_record "
                         "streaming needs driver='python' (or feed a "
                         "Trace post-hoc via the *_scanned drivers)")
    if driver == "auto":
        return "python" if on_record is not None else "scan"
    return driver


def _stack_participation(schedule: Optional[Callable[[int], Array]],
                         T: int, p: int) -> Optional[Array]:
    """Host-evaluate a participation schedule into a (T, p) scan input."""
    if schedule is None:
        return None
    rows = []
    for t in range(T):
        part = schedule(t)
        rows.append(jnp.ones((p,)) if part is None else jnp.asarray(part))
    return jnp.stack(rows)


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------

def _prepare_sim(obj: Objective, reg: Regularizer, Xp, yp,
                 cfg: PScopeConfig):
    """Resolve auto path / CSR conversion / statics for simulation mode."""
    cfg = _resolve_inner_path(obj, cfg, Xp)
    statics = None
    if cfg.inner_path == "lazy":
        _require_lazy_support(obj, cfg)
        Xp, yp = _as_csr_shards(Xp, yp)
        statics = _sim_statics(Xp, cfg)
    elif isinstance(Xp, (CSRMatrix, EncodedCSR)):
        raise ValueError("dense inner_path cannot consume CSRMatrix/"
                         "EncodedCSR data; set PScopeConfig(inner_path='lazy')")
    return cfg, Xp, yp, statics


def _scan_with_recording(step_fn, record, state, parts, T: int,
                         record_every: int):
    """Scan T outer rounds, evaluating `record` only on recorded rounds.

    record_every == 1 records inline; otherwise the rounds are chunked
    (record_every per chunk, one record per chunk, trailing remainder
    rounds advanced unrecorded) so the full-dataset objective and the
    NNZ reduction are never computed for rounds the caller will drop —
    matching the Python driver's evaluation count exactly.
    """
    def inner(st, part_t):
        return step_fn(st, part_t), None

    if record_every == 1:
        def body(st, part_t):
            st2 = step_fn(st, part_t)
            return st2, record(st2)
        return jax.lax.scan(body, state, parts, length=T)

    full, rem = divmod(T, record_every)
    parts_main = parts_rem = None
    if parts is not None:
        split = full * record_every
        parts_main = parts[:split].reshape(full, record_every,
                                           *parts.shape[1:])
        parts_rem = parts[split:]

    def chunk(st, part_chunk):
        st, _ = jax.lax.scan(inner, st, part_chunk, length=record_every)
        return st, record(st)

    state, recs = jax.lax.scan(chunk, state, parts_main, length=full)
    state, _ = jax.lax.scan(inner, state, parts_rem, length=rem)
    return state, recs


# bounded: each entry pins a compiled whole-trajectory executable; a
# hyperparameter sweep must not accumulate them unboundedly
@functools.lru_cache(maxsize=32)
def _sim_trajectory_fn(obj: Objective, reg: Regularizer, cfg: PScopeConfig,
                       record_every: int = 1, with_counters: bool = False):
    """Compiled T-round simulation trajectory, cached per (obj, reg, cfg,
    record_every, with_counters)."""
    lazy = cfg.inner_path == "lazy"

    def trajectory(w0, key0, Xp, yp, parts, statics):
        obj_val = _objective_value_device(obj, reg, Xp, yp)
        ctr0 = (jnp.zeros((len(COUNTER_NAMES),), jnp.float32)
                if with_counters else None)
        state = PScopeState(w=w0, t=jnp.zeros((), jnp.int32), key=key0,
                            ctr=ctr0)

        def record(st):
            with jax.named_scope(SCOPE_OBJECTIVE):
                base = (obj_val(st.w), jnp.sum(jnp.abs(st.w) > NNZ_TOL))
            return base + (st.ctr,) if with_counters else base

        def step_fn(st, part_t):
            if lazy:
                return _outer_step_lazy_core(obj, reg, cfg, st, Xp, yp,
                                             part_t, statics)
            return _outer_step_core(obj, reg, cfg, st, Xp, yp, part_t)

        if with_counters:
            v0, nnz0, c0 = record(state)
            state, (vals, nnzs, ctrs) = _scan_with_recording(
                step_fn, record, state, parts, cfg.outer_steps, record_every)
            return (state.w, jnp.concatenate([v0[None], vals]),
                    jnp.concatenate([nnz0[None], nnzs]),
                    jnp.concatenate([c0[None], ctrs]))
        v0, nnz0 = record(state)
        state, (vals, nnzs) = _scan_with_recording(
            step_fn, record, state, parts, cfg.outer_steps, record_every)
        return (state.w, jnp.concatenate([v0[None], vals]),
                jnp.concatenate([nnz0[None], nnzs]))

    # the iterate buffer is donated into the scan carry (run_scanned
    # hands over a fresh copy, so callers keep their w0)
    return jax.jit(trajectory, donate_argnums=(0,))


def run_scanned(obj: Objective, reg: Regularizer, Xp, yp: Array, w0: Array,
                cfg: PScopeConfig,
                participation_schedule: Optional[Callable] = None,
                record_every: int = 1, start_round: int = 0,
                counters: bool = False):
    """The zero-sync simulation driver: T outer rounds in ONE compiled
    program.

    The outer loop is a `lax.scan`; every `record_every`-th round's
    objective P(w_t) and iterate NNZ are recorded into device-side
    history buffers via the layout-matched loss (sparse CSR loss on the
    lazy path) — unrecorded rounds skip the evaluation entirely — and
    the host synchronizes exactly once, on the final transfer.  The
    state buffers are donated to the scan, so the iterate is updated in
    place round over round.

    `start_round=t` resumes mid-trajectory: the RNG key is fast-
    forwarded t splits (see `advance_key`) so rounds t..t+T-1 draw the
    sample sequences the uninterrupted run would have — pass the round-t
    iterate as `w0` and the segment reproduces the tail of the full run
    exactly (the elastic resume path and its tests rely on this).

    Returns (w_T, values, nnz) — numpy arrays of T // record_every + 1
    entries, index 0 being the initial (round start_round) iterate.

    `counters=True` additionally carries the (len(COUNTER_NAMES),)
    telemetry counters through the scan and returns them as a fourth
    (records, len(COUNTER_NAMES)) cumulative array — same single host
    transfer, same values/NNZ bits (the counters never touch the iterate
    path; the added cost is two scalar plan reductions per round).

    The host side of a solve is three spans (`repro.obs`):
    `solve.prepare` (the CSR view, the shard statics, the inputs),
    `solve.dispatch` (the call of the compiled trajectory) and
    `solve.fetch` (the host transfers, which wait for the device).
    """
    with obs.span("solve.prepare"):
        cfg, Xp, yp, statics = _prepare_sim(obj, reg, Xp, yp, cfg)
        p = yp.shape[0]
        parts = _stack_participation(participation_schedule,
                                     cfg.outer_steps, p)
        compiled = _sim_trajectory_fn(obj, reg, cfg, record_every,
                                      bool(counters))
        w0d = jnp.array(w0, dtype=jnp.float32, copy=True)
        key0 = advance_key(jax.random.PRNGKey(cfg.seed), start_round)
    with obs.span("solve.dispatch"):
        out = compiled(w0d, key0, Xp, yp, parts, statics)
    # the first transfer waits for the device to finish the trajectory
    with obs.span("solve.fetch"):
        return tuple(np.asarray(x) for x in out)


def run(obj: Objective, reg: Regularizer, Xp, yp: Array, w0: Array,
        cfg: PScopeConfig, record_every: int = 1,
        participation_schedule: Optional[Callable[[int], Array]] = None,
        on_record: Optional[Callable[[Array, float], None]] = None,
        driver: str = "auto"):
    """Full pSCOPE driver. Returns (w_T, history of P(w_t)).

    `Xp` is worker-major data: a dense (p, n_k, d) array, or a
    `CSRMatrix` with (p, n_k, k) row-slices.  With
    cfg.inner_path == "lazy" dense input is auto-converted to CSR so
    callers can A/B the engines by flipping the config alone;
    "auto" lets the calibrated cost model pick.

    `driver` selects the outer-loop execution:
      * "scan"   — the zero-sync compiled trajectory (`run_scanned`):
        one dispatch, one host transfer, history recorded on device.
        Incompatible with `on_record` (which needs per-round streaming).
      * "python" — the classic loop: one dispatch + objective sync per
        round; `on_record(w, value)` fires at every history append.
      * "auto"   — "scan" unless an `on_record` callback is given.
    """
    driver = _resolve_driver(driver, on_record)
    if driver == "scan":
        w, values, _ = run_scanned(obj, reg, Xp, yp, w0, cfg,
                                   participation_schedule, record_every)
        # match the python driver's return type (a device array)
        return jnp.asarray(w), [float(v) for v in values]

    cfg, Xp, yp, statics = _prepare_sim(obj, reg, Xp, yp, cfg)
    if cfg.inner_path == "lazy":
        step_fn = functools.partial(pscope_outer_step_lazy, statics=statics)
    else:
        step_fn = pscope_outer_step

    state = init_state(w0, cfg.seed)
    obj_val = _objective_value_fn(obj, reg, Xp, yp, cfg)

    def emit(w, history):
        v = float(obj_val(w))
        history.append(v)
        if on_record is not None:
            on_record(w, v)

    history: list = []
    emit(state.w, history)
    for t in range(cfg.outer_steps):
        part = (participation_schedule(t)
                if participation_schedule is not None else None)
        state = step_fn(obj, reg, cfg, state, Xp, yp, part)
        if (t + 1) % record_every == 0:
            emit(state.w, history)
    return state.w, history


# ---------------------------------------------------------------------------
# Distributed execution: shard_map over a real mesh axis.
# ---------------------------------------------------------------------------

def _distributed_statics(cfg: PScopeConfig, mesh, axis: str,
                         csr: CSRMatrix, p: int):
    """Build per-shard statics once, sharded over the mesh axis."""
    n_k = csr.vals.shape[0] // p
    k = csr.vals.shape[-1]
    with_member = plan_mod.default_with_member(n_k, k, workers=p,
                                               inner_batch=cfg.inner_batch)
    build = functools.partial(plan_mod.shard_statics,
                              with_member=with_member)
    out_specs = plan_mod.ShardStatics(
        xdup=P(axis), rep_row=P(axis),
        member=P(axis) if with_member else None)
    sharded = jax.shard_map(build, mesh=mesh,
                            in_specs=(P(axis), P(axis)),
                            out_specs=out_specs, check_vma=False)
    return jax.jit(sharded)(csr.vals, csr.cols)


def make_distributed_outer_step(obj: Objective, reg: Regularizer,
                                cfg: PScopeConfig, mesh,
                                axis: str = "data"):
    """Returns a jit'd outer step where the worker axis is a mesh axis.

    Dense layout: X (p * n_k, d) sharded over `axis` on dim 0; w
    replicated.  With cfg.inner_path == "lazy" the step instead takes a
    flat `CSRMatrix` (n, k) whose rows are sharded over `axis` (plus
    optional sharded `plan.ShardStatics`), and the inner scan runs the
    fused epoch-planned engine.  Either way the shard_map body performs
    exactly two collectives (pmean of the anchor gradient, pmean of the
    final iterates); the inner scan is collective-free — this is the
    CALL communication structure.
    """
    core = make_distributed_outer_step_core(obj, reg, cfg, mesh, axis)
    return jax.jit(core)


def make_distributed_outer_step_core(obj: Objective, reg: Regularizer,
                                     cfg: PScopeConfig, mesh,
                                     axis: str = "data"):
    """Unjitted distributed outer step (composable into the scanned
    driver; `make_distributed_outer_step` is its jitted wrapper)."""
    lazy = cfg.inner_path == "lazy"
    h_prime = (_require_lazy_support(obj, cfg) if lazy
               else _pick_h_prime(obj, cfg))
    p = mesh.shape[axis]

    def local_idx(key, n_k):
        # The per-worker key is split(key, p)[worker] — the SAME
        # derivation simulation mode uses — so worker k draws the
        # identical sample sequence in both modes and a mesh trajectory
        # matches run_scanned's within fp32 reassociation (the
        # multi-host equivalence tests pin this; fold_in(key, widx)
        # would decorrelate the modes).
        with jax.named_scope(SCOPE_PLAN):
            widx = jax.lax.axis_index(axis)
            k_local = jnp.take(jax.random.split(key, p), widx, axis=0)
            return svrg.sample_microbatches(k_local, n_k, cfg.inner_steps,
                                            cfg.inner_batch)

    def body(w_t, key, Xk_or_vals, yk, cols_k=None, statics=None):
        # phase 1: one all-reduce for the anchor (full) gradient
        with jax.named_scope(SCOPE_ANCHOR_GRAD):
            if lazy:
                z_local = svrg.sparse_linear_model_full_gradient(
                    h_prime, w_t, Xk_or_vals, cols_k, yk, w_t.shape[0])
            else:
                z_local = jax.grad(obj.loss_fn)(w_t, Xk_or_vals, yk)
            z = jax.lax.pmean(z_local, axis)
        # phase 2: local inner loop, no DP collectives
        idx = local_idx(key, Xk_or_vals.shape[0])
        if lazy:
            u = _lazy_inner_loop(h_prime, reg, cfg.eta, w_t, w_t, z,
                                 Xk_or_vals, cols_k, yk, idx,
                                 statics=statics)
        else:
            u = _inner_loop(obj.loss_fn, reg, cfg.eta, w_t, w_t, z,
                            Xk_or_vals, yk, idx, h_prime=h_prime)
        # phase 3: one all-reduce to average iterates
        with jax.named_scope(SCOPE_AVERAGE):
            return jax.lax.pmean(u, axis)

    def body_enc(w_t, key, vals16, y, colb, dcols, nnz):
        # encoded-shard variant: the registered device operands are the
        # codec leaves (uint16 bf16 bits, delta columns) — about half
        # the raw CSR bytes — and the decode is fused into each phase
        # (cumsum+bitcast into the anchor scatter-add, bit-gather into
        # the epoch kernels) instead of materializing a decoded copy.
        d = w_t.shape[0]
        enc = EncodedCSR(vals16=vals16, colb=colb, dcols=dcols,
                         row_nnz=nnz, d=d)
        with jax.named_scope(SCOPE_ANCHOR_GRAD):
            z_local = svrg.sparse_linear_model_full_gradient(
                h_prime, w_t, enc.decode_vals(), enc.decode_cols(), y, d)
            z = jax.lax.pmean(z_local, axis)
        idx = local_idx(key, y.shape[0])
        u = _lazy_inner_loop_enc(h_prime, reg, cfg.eta, w_t, w_t, z,
                                 vals16, colb, dcols, nnz, y, idx)
        with jax.named_scope(SCOPE_AVERAGE):
            return jax.lax.pmean(u, axis)

    def make_shard_body(with_statics: bool, encoded: bool = False):
        n_data = 5 if encoded else (3 if lazy else 2)
        extra = ((P(axis),) if with_statics else ())
        in_specs = (P(), P()) + (P(axis),) * n_data + extra
        fn = body_enc if encoded else body
        if with_statics:
            fn = lambda w, key, vals, y, cols, st: body(w, key, vals, y,
                                                        cols, statics=st)
        return jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
            # the inner scan carry starts replicated (u0 = w_t) and becomes
            # device-varying through per-shard sampling; disable the VMA
            # consistency check rather than pcast-ing every carry leaf
            check_vma=False,
        )

    if lazy:
        def outer_step(state: PScopeState, csr, y: Array,
                       statics=None) -> PScopeState:
            with jax.named_scope(SCOPE_PLAN):
                key, sub = jax.random.split(state.key)
            if isinstance(csr, EncodedCSR):
                # statics are rebuilt inside the epoch on this path (a
                # data-only precompute; identical plans either way)
                w_next = make_shard_body(False, encoded=True)(
                    state.w, sub, csr.vals16, y, csr.colb, csr.dcols,
                    csr.row_nnz)
            elif statics is None:
                w_next = make_shard_body(False)(state.w, sub, csr.vals, y,
                                                csr.cols)
            else:
                w_next = make_shard_body(True)(state.w, sub, csr.vals, y,
                                               csr.cols, statics)
            return PScopeState(w=w_next, t=state.t + 1, key=key)
    else:
        def outer_step(state: PScopeState, X: Array, y: Array,
                       statics=None) -> PScopeState:
            with jax.named_scope(SCOPE_PLAN):
                key, sub = jax.random.split(state.key)
            w_next = make_shard_body(False)(state.w, sub, X, y)
            return PScopeState(w=w_next, t=state.t + 1, key=key)

    return outer_step


def _prepare_distributed(obj: Objective, reg: Regularizer, X, y,
                         cfg: PScopeConfig, mesh, axis: str):
    cfg = _resolve_inner_path(obj, cfg, X)
    if isinstance(X, EncodedCSR):
        # encoded shards skip the sharded statics precompute (they are
        # rebuilt from the decoded shard inside each epoch — identical
        # plans) so the registered operands stay compressed
        if cfg.inner_path != "lazy":
            raise ValueError("EncodedCSR data requires inner_path "
                             f"'lazy'/'auto', got {cfg.inner_path!r}")
        return cfg, X, None
    if cfg.inner_path == "lazy" and not isinstance(X, CSRMatrix):
        X = dense_to_csr(X)
    statics = None
    if cfg.inner_path == "lazy":
        p = mesh.shape[axis]
        with obs.span("mesh.statics"):
            statics = _distributed_statics(cfg, mesh, axis, X, p)
    return cfg, X, statics


# bounded: each entry pins a compiled whole-trajectory executable (and a
# Mesh); a hyperparameter sweep must not accumulate them unboundedly
@functools.lru_cache(maxsize=32)
def _distributed_trajectory_fn(obj: Objective, reg: Regularizer,
                               cfg: PScopeConfig, mesh, axis: str,
                               record_every: int = 1):
    """Compiled distributed trajectory, cached per (obj, reg, cfg, mesh)."""
    step_core = make_distributed_outer_step_core(obj, reg, cfg, mesh, axis)

    def trajectory(w0, key0, X, y, statics):
        state = PScopeState(w=w0, t=jnp.zeros((), jnp.int32), key=key0)
        obj_val = _objective_value_device(obj, reg, X, y)

        def record(st):
            with jax.named_scope(SCOPE_OBJECTIVE):
                return obj_val(st.w), jnp.sum(jnp.abs(st.w) > NNZ_TOL)

        def step_fn(st, _):
            return step_core(st, X, y, statics)

        v0, nnz0 = record(state)
        state, (vals, nnzs) = _scan_with_recording(
            step_fn, record, state, None, cfg.outer_steps, record_every)
        return (state.w, jnp.concatenate([v0[None], vals]),
                jnp.concatenate([nnz0[None], nnzs]))

    return jax.jit(trajectory, donate_argnums=(0,))


def run_distributed_scanned(obj: Objective, reg: Regularizer, X, y: Array,
                            w0: Array, cfg: PScopeConfig, mesh,
                            axis: str = "data", record_every: int = 1,
                            start_round: int = 0):
    """Zero-sync distributed driver: the T-round shard_map trajectory as
    one compiled scan with device-side history (cf. `run_scanned`).

    `start_round` fast-forwards the RNG split chain exactly as in
    `run_scanned` — a resumed segment reproduces the uninterrupted
    trajectory's tail from the same iterate.

    Returns (w_T, values, nnz) as numpy arrays of T // record_every + 1
    entries.  Its host spans are `mesh.prepare` (the sharded statics, in
    its `mesh.statics`, and the inputs), `mesh.dispatch` and
    `mesh.fetch`, as in `run_scanned`.
    """
    with obs.span("mesh.prepare"):
        cfg, X, statics = _prepare_distributed(obj, reg, X, y, cfg, mesh,
                                               axis)
        compiled = _distributed_trajectory_fn(obj, reg, cfg, mesh, axis,
                                              record_every)
        w0d = jnp.array(w0, dtype=jnp.float32, copy=True)
        key0 = advance_key(jax.random.PRNGKey(cfg.seed), start_round)
    with obs.span("mesh.dispatch"):
        out = compiled(w0d, key0, X, y, statics)
    with obs.span("mesh.fetch"):
        return tuple(np.asarray(x) for x in out)


# ---------------------------------------------------------------------------
# Stacked-workers distributed execution: uneven workers-per-device.
#
# After an elastic re-mesh the surviving s devices own UNEVEN worker
# sets (a survivor that adopted an orphan holds 2 shards, its peers 1)
# — something `NamedSharding` row-sharding cannot express.  The stacked
# layout can: each device holds a zero-padded (W_max, n_k, ...) stack
# of its owned workers' shards plus an int32 slot→global-worker-id row
# (-1 marks a pad slot).  The LOGICAL worker count p never changes:
#   * pad slots carry all-zero vals, so their anchor-gradient scatter
#     contributions vanish identically;
#   * each real slot draws ITS ORIGINAL WORKER's sample sequence
#     (key = split(round_key, p_total)[worker_id] — the same derivation
#     simulation and even-mesh modes use);
#   * phase 3 masks pad slots out of the iterate sum and divides by
#     p_total, not by the slot count.
# Net effect: the trajectory is a function of the p-worker partition
# only, not of which device hosts which shard — placement transparency.
# A post-re-mesh segment therefore matches `run_scanned(start_round=t)`
# over the same p shards within fp32 reassociation, which is exactly
# what the elastic acceptance tests pin.
# ---------------------------------------------------------------------------

def make_stacked_outer_step_core(obj: Objective, reg: Regularizer,
                                 cfg: PScopeConfig, mesh,
                                 axis: str = "workers", *, p_total: int):
    """Unjitted outer step over stacked per-device worker slots.

    Operands (all sharded over `axis` on dim 0; s = mesh size):
      vals  (s, W_max, n_k, k)  float32, zero-padded pad slots
      cols  (s, W_max, n_k, k)  int32
      y     (s, W_max, n_k)     float32 (pad slots: any finite label)
      slots (s, W_max)          int32 global worker ids, -1 = pad
    Lazy engine only (the elastic path is CSR/store-backed).
    """
    h_prime = _require_lazy_support(obj, cfg)

    def body(w_t, key, vals, cols, y, slots, statics=None):
        vals, cols, y, slots = vals[0], cols[0], y[0], slots[0]
        n_k = y.shape[-1]
        d = w_t.shape[0]
        valid = (slots >= 0)

        # phase 1: per-slot anchor gradients; one all-reduce.  Each
        # slot's full gradient is its shard mean, so the global anchor
        # is sum-over-real-slots / p_total (pad slots are identically
        # zero — vals==0 kills every scattered term — the mask is
        # belt-and-braces).
        g = jax.vmap(lambda v, c, yk: svrg.sparse_linear_model_full_gradient(
            h_prime, w_t, v, c, yk, d))(vals, cols, y)
        g_sum = jnp.sum(g * valid[:, None].astype(g.dtype), axis=0)
        z = jax.lax.psum(g_sum, axis) / p_total

        # phase 2: collective-free inner loops, one per slot.  The slot
        # keys index the per-WORKER split, so worker k's sequence is
        # identical wherever its shard currently lives (pad slots run a
        # throwaway loop on zero data; phase 3 masks them out).
        keys = jax.random.split(key, p_total)
        k_slot = jnp.take(keys, jnp.clip(slots, 0, p_total - 1), axis=0)
        idx = jax.vmap(
            lambda kk: svrg.sample_microbatches(kk, n_k, cfg.inner_steps,
                                                cfg.inner_batch))(k_slot)
        inner = functools.partial(_lazy_inner_loop, h_prime, reg, cfg.eta)
        if statics is None:
            u = jax.vmap(lambda v, c, yk, ixk: inner(w_t, w_t, z, v, c,
                                                     yk, ixk))(
                vals, cols, y, idx)
        else:
            u = jax.vmap(lambda v, c, yk, ixk, st: inner(
                w_t, w_t, z, v, c, yk, ixk, statics=st))(
                vals, cols, y, idx, statics)

        # phase 3: masked iterate average over the p_total real workers
        u_sum = jnp.sum(u * valid[:, None].astype(u.dtype), axis=0)
        return jax.lax.psum(u_sum, axis) / p_total

    def make_shard_body(with_statics: bool):
        extra = ((P(axis),) if with_statics else ())
        in_specs = (P(), P()) + (P(axis),) * 4 + extra
        fn = body
        if with_statics:
            def fn(w, key, vals, cols, y, slots, st):
                st = jax.tree_util.tree_map(lambda x: x[0], st)
                return body(w, key, vals, cols, y, slots, statics=st)
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=P(), check_vma=False)

    def outer_step(state: PScopeState, vals, cols, y, slots,
                   statics=None) -> PScopeState:
        key, sub = jax.random.split(state.key)
        if statics is None:
            w_next = make_shard_body(False)(state.w, sub, vals, cols, y,
                                            slots)
        else:
            w_next = make_shard_body(True)(state.w, sub, vals, cols, y,
                                           slots, statics)
        return PScopeState(w=w_next, t=state.t + 1, key=key)

    return outer_step


def _stacked_statics(cfg: PScopeConfig, mesh, axis: str, vals_g, cols_g,
                     p_total: int):
    """Per-slot shard statics, sharded in the stacked (s, W_max) layout."""
    _, W, n_k, k = vals_g.shape
    with_member = plan_mod.default_with_member(n_k, k, workers=p_total,
                                               inner_batch=cfg.inner_batch)
    build = functools.partial(plan_mod.shard_statics,
                              with_member=with_member)

    def build_block(v, c):
        st = jax.vmap(build)(v[0], c[0])
        return jax.tree_util.tree_map(lambda x: x[None], st)

    out_specs = plan_mod.ShardStatics(
        xdup=P(axis), rep_row=P(axis),
        member=P(axis) if with_member else None)
    sharded = jax.shard_map(build_block, mesh=mesh,
                            in_specs=(P(axis), P(axis)),
                            out_specs=out_specs, check_vma=False)
    return jax.jit(sharded)(vals_g, cols_g)


def _stacked_objective_value(obj: Objective, reg: Regularizer, mesh,
                             axis: str, p_total: int, n_k: int):
    """(w, vals, cols, y, slots) -> P(w) with pad rows masked out.

    `sparse_linear_model_loss` takes a mean over ALL rows, which would
    let pad slots (margin 0, loss h(0, y) != 0) pollute the objective;
    here the per-row losses are summed over REAL slots only and divided
    by the true row count p_total * n_k.
    """
    h_loss = svrg.LINEAR_MODEL_H_LOSS[obj.name]

    def local_loss_sum(w, vals, cols, y, slots):
        vals, cols, y, slots = vals[0], cols[0], y[0], slots[0]
        margins = jnp.sum(vals * jnp.take(w, cols, axis=0), axis=-1)
        rows = h_loss(margins, y)                          # (W, n_k)
        valid = (slots >= 0).astype(rows.dtype)
        return jax.lax.psum(jnp.sum(rows * valid[:, None]), axis)

    sharded = jax.shard_map(local_loss_sum, mesh=mesh,
                            in_specs=(P(),) + (P(axis),) * 4,
                            out_specs=P(), check_vma=False)

    def value(w, vals, cols, y, slots):
        return sharded(w, vals, cols, y, slots) / (p_total * n_k) \
            + reg.value(w)

    return value


# bounded: each entry pins a compiled whole-trajectory executable (and a
# Mesh); the elastic chunk driver re-enters with identical keys
@functools.lru_cache(maxsize=32)
def _stacked_trajectory_fn(obj: Objective, reg: Regularizer,
                           cfg: PScopeConfig, mesh, axis: str,
                           p_total: int, n_k: int, record_every: int = 1):
    """Compiled stacked trajectory, cached per (obj, reg, cfg, mesh)."""
    step_core = make_stacked_outer_step_core(obj, reg, cfg, mesh, axis,
                                             p_total=p_total)
    obj_val = _stacked_objective_value(obj, reg, mesh, axis, p_total, n_k)

    def trajectory(w0, key0, vals, cols, y, slots, statics):
        state = PScopeState(w=w0, t=jnp.zeros((), jnp.int32), key=key0)

        def record(st):
            return (obj_val(st.w, vals, cols, y, slots),
                    jnp.sum(jnp.abs(st.w) > NNZ_TOL))

        def step_fn(st, _):
            return step_core(st, vals, cols, y, slots, statics)

        v0, nnz0 = record(state)
        state, (vs, nnzs) = _scan_with_recording(
            step_fn, record, state, None, cfg.outer_steps, record_every)
        return (state.w, jnp.concatenate([v0[None], vs]),
                jnp.concatenate([nnz0[None], nnzs]))

    return jax.jit(trajectory, donate_argnums=(0,))


def run_stacked_scanned(obj: Objective, reg: Regularizer, vals_g, cols_g,
                        y_g, slots_g, w0: Array, cfg: PScopeConfig, mesh,
                        axis: str = "workers", record_every: int = 1,
                        start_round: int = 0, *, p_total: int):
    """Zero-sync scanned driver over the stacked uneven-ownership layout.

    Same contract as `run_distributed_scanned` (returns (w, values,
    nnz); index 0 = the round-`start_round` iterate) but the data
    operands are the stacked per-device arrays described in
    `make_stacked_outer_step_core` — built by
    `launch.mesh.stacked_worker_arrays` from an ownership map.
    `p_total` is the ORIGINAL logical worker count; it must equal the
    number of distinct non-negative ids in `slots_g`.
    """
    if cfg.inner_path not in ("lazy", "auto"):
        raise ValueError("the stacked driver is CSR-only; need "
                         f"inner_path 'lazy'/'auto', got {cfg.inner_path!r}")
    cfg = dataclasses.replace(cfg, inner_path="lazy")
    _require_lazy_support(obj, cfg)
    n_k = int(y_g.shape[-1])
    statics = _stacked_statics(cfg, mesh, axis, vals_g, cols_g, p_total)
    compiled = _stacked_trajectory_fn(obj, reg, cfg, mesh, axis, p_total,
                                      n_k, record_every)
    w0d = jnp.array(w0, dtype=jnp.float32, copy=True)
    key0 = advance_key(jax.random.PRNGKey(cfg.seed), start_round)
    w, values, nnzs = compiled(w0d, key0, vals_g, cols_g, y_g, slots_g,
                               statics)
    return np.asarray(w), np.asarray(values), np.asarray(nnzs)


def run_distributed(obj: Objective, reg: Regularizer, X, y: Array,
                    w0: Array, cfg: PScopeConfig, mesh, axis: str = "data",
                    record_every: int = 1,
                    on_record: Optional[Callable[[Array, float], None]] = None,
                    driver: str = "auto"):
    """Distributed driver; `X` is dense (n, d) or a flat CSRMatrix (n, k).

    `driver` works as in `run`: "scan" compiles the whole trajectory
    (one host sync), "python" streams per round for `on_record`.
    """
    driver = _resolve_driver(driver, on_record)
    if driver == "scan":
        w, values, _ = run_distributed_scanned(obj, reg, X, y, w0, cfg,
                                               mesh, axis, record_every)
        return jnp.asarray(w), [float(v) for v in values]

    cfg, X, statics = _prepare_distributed(obj, reg, X, y, cfg, mesh, axis)
    step = jax.jit(make_distributed_outer_step_core(obj, reg, cfg, mesh,
                                                    axis))
    state = init_state(w0, cfg.seed)
    obj_val = jax.jit(_objective_value_device(obj, reg, X, y))

    def emit(w, history):
        v = float(obj_val(w))
        history.append(v)
        if on_record is not None:
            on_record(w, v)

    history: list = []
    emit(state.w, history)
    for t in range(cfg.outer_steps):
        state = step(state, X, y, statics)
        if (t + 1) % record_every == 0:
            emit(state.w, history)
    return state.w, history
