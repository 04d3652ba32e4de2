"""Epoch gather plans for the fused lazy inner engine.

The lazy inner loop (core/pscope) needs, at every inner step m, the
catch-up staleness of each touched coordinate:

    q[m, s] = m - last[cflat[m, s]]

where ``last[j]`` is 1 + the latest step < m that touched column j.
The PR-2 engine maintained ``last`` as a (d,) carry inside the scan —
one gather and one scatter per step that exist purely for bookkeeping.
But q depends only on the sampled index sequence ``idx`` and the CSR
column structure, never on the data values or the iterate: the whole
(M, S) staleness table can be hoisted out of the scan into one
vectorized pass per epoch.  This module builds that plan.

Two plan builders, selected by shard shape:

* **row-membership** (b = 1, small shards): precompute once per shard
  the boolean table ``member[r, s, r'] = cols[r, s] in row r'``.  Per
  epoch, the latest prior touch of slot (m, s) is then a max over the
  rows containing that column of "when was r' last sampled" — a tiny
  (M, n_k) cummax plus one fused (M, k, n_k) masked reduction.  No
  sort anywhere.
* **sort-based** (the general path, any b): pack (col, step) into one
  int32 key, add one probe key per column that sorts after its last
  touch, and sort the keys with their positions.  The neighbour just
  before a group head in sorted order is the latest earlier touch of
  the same column, so a shifted compare finds every ``last``, a
  running max copies it to the group's duplicates, and a sort by
  position puts the plan back in step order.  No binary search.
  Where d * (M + 1) or (N + d) * S no longer fits one int32 (news20's
  1,355,191 features at M = 2,499), the wide variant sorts on the
  column alone and hands each head's plan to its duplicates by a
  gather (scope ``pscope.plan_wide``).

All produce identical plans (tests/test_fused_inner.py enforces it
against a literal Python replay).

`ShardStatics` holds the data-only precomputes — duplicate-column
sums, within-row duplicate representatives, the membership table —
which are computed **once per run** (not per epoch) and threaded
through the outer loop by ``pscope.run``.

`choose_inner_path` is the calibrated cost model behind
``PScopeConfig(inner_path="auto")``; constants come from the measured
BENCH_inner_loop.json sweep (see docs/kernels.md).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# Above this many elements the member[r, s, r'] table is not built and
# the sort-based plan is used instead (the table is O(n_k^2 * k)).
MEMBER_TABLE_LIMIT = 48_000_000

# The named scope of the wide sort plan, inside the round's `pscope.plan`
# (docs/observability.md).
SCOPE_PLAN_WIDE = "pscope.plan_wide"


# ---------------------------------------------------------------------------
# per-shard, data-only statics (computed once per run)
# ---------------------------------------------------------------------------

class ShardStatics(NamedTuple):
    """Precomputes that depend only on the shard's CSR structure.

    xdup     (n_k, k) float32  duplicate-summed row values:
             xdup[r, s] = sum of vals[r, s'] over s' with
             cols[r, s'] == cols[r, s].  Lets the b = 1 scan apply the
             full per-column gradient with a plain elementwise multiply
             instead of a scatter-add / re-gather pair.
    rep_row  (n_k, k) int32    first slot in row r holding the same
             column as slot s (the duplicate representative the fused
             kernel's segment-sum keys on).
    member   (n_k, k, n_k) bool or None
             member[r, s, r'] = cols[r, s] in row r'.  Only built for
             b = 1 shards under MEMBER_TABLE_LIMIT.
    """

    xdup: Array
    rep_row: Array
    member: Optional[Array]


def member_table_ok(n_k: int, k: int, workers: int = 1,
                    limit: int = MEMBER_TABLE_LIMIT) -> bool:
    return workers * n_k * k * n_k <= limit


def default_with_member(n_k: int, k: int, workers: int = 1,
                        inner_batch: int = 1) -> bool:
    """Production policy for building the membership table.

    The two plan builders are exact equals; which is faster is a
    backend question.  On CPU the packed-key single-operand sort beats
    the (M, S, n_k) masked reduction at every measured grid cell, so
    the table is only worth its memory on TPU, where sorts lower poorly
    but the masked reduce is a native VPU pattern.
    """
    return (inner_batch == 1 and jax.default_backend() == "tpu"
            and member_table_ok(n_k, k, workers))


def shard_statics(vals_k: Array, cols_k: Array,
                  with_member: bool = True) -> ShardStatics:
    """Build the data-only statics for one (n_k, k) CSR shard."""
    n_k, k = cols_k.shape

    def per_row(v, c):
        sc = jnp.sort(c)
        pos = jnp.searchsorted(sc, c, side="left").astype(jnp.int32)
        xd = jnp.take(jnp.zeros_like(v).at[pos].add(v), pos)
        # representative = the smallest slot index of each duplicate
        # group; pos is a stable group id within the row
        slots = jnp.arange(k, dtype=jnp.int32)
        rep = jnp.take(jnp.full((k,), k, jnp.int32).at[pos].min(slots), pos)
        return xd, rep

    xdup, rep_row = jax.vmap(per_row)(vals_k, cols_k)

    member = None
    if with_member:
        sorted_cols = jnp.sort(cols_k, axis=-1)                  # (n_k, k)

        def member_row(c_query):                                 # (k,)
            def against(srow):
                p = jnp.minimum(
                    jnp.searchsorted(srow, c_query, side="left"), k - 1)
                return jnp.take(srow, p) == c_query
            return jax.vmap(against)(sorted_cols).T              # (k, n_k)

        member = jax.vmap(member_row)(cols_k)                    # (n_k,k,n_k)
    return ShardStatics(xdup=xdup, rep_row=rep_row, member=member)


# ---------------------------------------------------------------------------
# the epoch plan
# ---------------------------------------------------------------------------

class EpochPlan(NamedTuple):
    """Everything the fused inner scan needs that is data-independent.

    cflat  (M, S) int32   flat active columns per step (S = b * k)
    q      (M, S) int32   catch-up staleness m - last[cflat[m, s]]
    rep    (M, S) int32   within-step duplicate representative slot
    qf     (d,)   int32   final catch-up counts M - last (one per coord)
    """

    cflat: Array
    q: Array
    rep: Array
    qf: Array


def padded_slot_width(k: int) -> int:
    """The fused epoch kernel's slots per sample: k rounded up to a
    multiple of 128 lanes."""
    return -(-k // 128) * 128


def fold_bounds(rep: Array, inner_batch: int) -> Array:
    """(M,) int32 trip counts of the fused epoch kernel's duplicate fold.

    Each step's count is one past its last non-representative slot
    (``rep[s] != s``), in the kernel's padded slot space where slot i
    of sample j sits at ``j * padded_slot_width(k) + i``; 0 for a step
    whose columns are distinct.  Every slot at or past it represents
    itself, so the fold adds nothing there.
    """
    M, S = rep.shape
    k = S // inner_batch
    slot = jnp.arange(S, dtype=jnp.int32)
    padded = slot // k * padded_slot_width(k) + slot % k
    return jnp.max(jnp.where(rep != slot, padded + 1, 0),
                   axis=1).astype(jnp.int32)


def build_epoch_plan(cols_k: Array, idx: Array, d: int,
                     statics: Optional[ShardStatics] = None) -> EpochPlan:
    """Hoist the whole epoch's catch-up bookkeeping out of the scan.

    ``idx`` is the (M, b) sampled row sequence.  Dispatches to the
    row-membership builder when ``statics`` carries a member table and
    b == 1, else to the sort-based builder: the packed one where its
    keys fit one int32, the wide one past that.
    """
    M, b = idx.shape
    if b == 1 and statics is not None and statics.member is not None:
        return _plan_from_membership(cols_k, idx, d, statics)
    if packed_plan_fits(d, M, b * cols_k.shape[-1]):
        return _plan_from_sort(cols_k, idx, d)
    with jax.named_scope(SCOPE_PLAN_WIDE):
        return _plan_from_sort_wide(cols_k, idx, d)


def packed_plan_fits(d: int, M: int, S: int) -> bool:
    """Whether `_plan_from_sort`'s packed values fit int32: the key and
    the carried column and ``last`` (d * (M + 1)), and the carried
    sorted index and slot ((M * S + d) * S)."""
    return max(d * (M + 1), (M * S + d) * S) < (1 << 31)


def _last_sampled(idx_flat: Array, n_k: int) -> tuple[Array, Array]:
    """ls_excl[m, r'] = 1 + latest step < m with idx == r' (0 if none);
    last_row[r'] = the same over the whole epoch."""
    M = idx_flat.shape[0]
    steps = jnp.arange(M, dtype=jnp.int32)
    onehot = jnp.where(idx_flat[:, None] == jnp.arange(n_k)[None, :],
                       steps[:, None] + 1, 0)
    ls_incl = jax.lax.cummax(onehot, axis=0)
    ls_excl = jnp.concatenate(
        [jnp.zeros((1, n_k), ls_incl.dtype), ls_incl[:-1]], axis=0)
    return ls_excl, ls_incl[-1]


def _plan_from_membership(cols_k: Array, idx: Array, d: int,
                          statics: ShardStatics) -> EpochPlan:
    """b = 1 fast path: no sort, mostly static lookups."""
    M = idx.shape[0]
    n_k, k = cols_k.shape
    r = idx.reshape(-1)                                          # (M,)
    ls_excl, last_row = _last_sampled(r, n_k)
    mem = jnp.take(statics.member, r, axis=0)                    # (M, k, n_k)
    last = jnp.max(jnp.where(mem, ls_excl[:, None, :], 0), axis=-1)
    q = jnp.arange(M, dtype=jnp.int32)[:, None] - last           # (M, k)
    cflat = jnp.take(cols_k, r, axis=0)                          # (M, k)
    rep = jnp.take(statics.rep_row, r, axis=0)                   # (M, k)
    last_final = jnp.zeros((d,), jnp.int32).at[cols_k.reshape(-1)].max(
        jnp.broadcast_to(last_row[:, None], (n_k, k)).reshape(-1))
    return EpochPlan(cflat=cflat, q=q, rep=rep, qf=M - last_final)


def _plan_from_sort(cols_k: Array, idx: Array, d: int) -> EpochPlan:
    """General path, any b: a sort by key, neighbour compares in sorted
    order, and a sort back by position.

    The N = M * S touches are keyed col * (M + 1) + step, and one probe
    per column j is keyed j * (M + 1) + M, so that it sorts just after
    column j's last touch.  The keys are sorted together with each
    entry's flat position.  A group head (the first entry of a key) is
    preceded in sorted order by the latest earlier touch of its column,
    if any: that gives ``last``, and q = step - last, which for a
    probe (step M) is the column's final staleness qf.  A running max
    carries each head's ``last`` and slot to the group's duplicates,
    and a second sort, keyed on the position, puts every entry back in
    place (on a TPU it is faster than the scatter by position).  The
    packed values must fit int32 (`packed_plan_fits`): the key and the
    carried column and ``last`` (d * (M + 1)), and the carried sorted
    index and slot ((N + d) * S).  rcv1 (d = 47,236, M <= 5,060) fits;
    news20 (d = 1,355,191, M = 2,499: d * (M + 1) = 3.4e9) does not,
    and `build_epoch_plan` takes `_plan_from_sort_wide` there.
    """
    M, b = idx.shape
    k = cols_k.shape[-1]
    S = b * k
    N = M * S
    if not packed_plan_fits(d, M, S):
        raise ValueError(
            f"packed plan values overflow int32 for d={d}, M={M}, S={S}; "
            f"past this bound build_epoch_plan takes _plan_from_sort_wide")
    cflat = jnp.take(cols_k, idx, axis=0).reshape(M, S)
    flat = jnp.arange(N + d, dtype=jnp.int32)   # step * S + slot, then N + j
    col = jnp.concatenate([cflat.reshape(-1), jnp.arange(d, dtype=jnp.int32)])
    step = jnp.where(flat < N, flat // S, M)
    # stable: a (col, step) group keeps its slots in order, so its head
    # holds the smallest slot, the duplicate representative
    skey, spos = jax.lax.sort((col * (M + 1) + step, flat), num_keys=1,
                              is_stable=True)
    scol, sstep = skey // (M + 1), skey % (M + 1)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), skey[:-1]])
    head = skey != prev
    # heads' (col, last) and (sorted index, slot) both rise along the
    # sorted order, so a running max hands each head's to its group
    last_at_head = jnp.where(prev // (M + 1) == scol, prev % (M + 1) + 1, 0)
    last = jax.lax.cummax(jnp.where(head, scol * (M + 1) + last_at_head,
                                    0)) - scol * (M + 1)
    rep = jax.lax.cummax(jnp.where(head, flat * S + spos % S, 0)) % S
    _, packed = jax.lax.sort((spos, (sstep - last) * S + rep), num_keys=1)
    return EpochPlan(cflat=cflat, q=(packed[:N] // S).reshape(M, S),
                     rep=(packed[:N] % S).reshape(M, S), qf=packed[N:] // S)


def _plan_from_sort_wide(cols_k: Array, idx: Array, d: int) -> EpochPlan:
    """`_plan_from_sort` for a d and M whose packed key overflows int32.

    The entries are sorted on the column alone.  The sort is stable and
    the flat position is step * S + slot for a touch and N + j for
    column j's probe, so each column's entries keep step order, then
    slot order, with the probe last: the order the packed key gives.  A
    group head is an entry whose (column, step) differs from its
    neighbour's before it, and that neighbour gives ``last`` as in the
    packed path.  Each head's staleness and slot are packed into one
    value, and a running max of the heads' sorted index, with one
    gather, hands them to the group's duplicates.  Only N + d and
    (M + 1) * S have to fit int32.
    """
    M, b = idx.shape
    k = cols_k.shape[-1]
    S = b * k
    N = M * S
    if max(N + d, (M + 1) * S) >= (1 << 31):
        raise ValueError(
            f"plan positions overflow int32 for d={d}, M={M}, S={S}")
    cflat = jnp.take(cols_k, idx, axis=0).reshape(M, S)
    flat = jnp.arange(N + d, dtype=jnp.int32)   # step * S + slot, then N + j
    col = jnp.concatenate([cflat.reshape(-1), jnp.arange(d, dtype=jnp.int32)])
    scol, spos = jax.lax.sort((col, flat), num_keys=1, is_stable=True)
    sstep = jnp.where(spos < N, spos // S, M)
    pcol = jnp.concatenate([jnp.full((1,), -1, jnp.int32), scol[:-1]])
    pstep = jnp.concatenate([jnp.full((1,), -1, jnp.int32), sstep[:-1]])
    head = (scol != pcol) | (sstep != pstep)
    last = jnp.where(pcol == scol, pstep + 1, 0)        # right at heads
    at_head = (sstep - last) * S + spos % S
    group = jax.lax.cummax(jnp.where(head, flat, 0))    # each one's head
    _, packed = jax.lax.sort((spos, jnp.take(at_head, group)), num_keys=1)
    return EpochPlan(cflat=cflat, q=(packed[:N] // S).reshape(M, S),
                     rep=(packed[:N] % S).reshape(M, S), qf=packed[N:] // S)


# ---------------------------------------------------------------------------
# per-epoch gathers (anchor- and z-dependent, hoisted out of the scan)
# ---------------------------------------------------------------------------

class EpochGathers(NamedTuple):
    """Step-indexed operands pre-gathered once per epoch.

    The anchor w_t and the full gradient z are constant across an inner
    epoch, so every step's gathers of them can be batched into single
    (M, ...) operations instead of M scan-step gathers:

    vb  (M, b, k)        microbatch values — float32, OR uint16 bf16
                         bit patterns when the shard is stored encoded
                         (datasets codec): the gather then moves half
                         the bytes and the epoch kernels bitcast the
                         bits to f32 at use (kernels/ops dispatches on
                         this dtype)
    yb  (M, b)           labels
    zg  (M, S)           z at the active columns
    sw  (M, b)           h'(x_i . w_anchor, y_i) — the anchor half of
                         the VR coefficient, constant per epoch
    xd  (M, k) or None   duplicate-summed values (b = 1 only): lets the
                         scan apply the per-column gradient without a
                         scatter-add / re-gather pair
    """

    vb: Array
    yb: Array
    zg: Array
    sw: Array
    xd: Optional[Array]


def epoch_gathers(h_prime, w_anchor: Array, z: Array, vals_k: Array,
                  yk: Array, idx: Array, cflat: Array,
                  statics: Optional[ShardStatics] = None) -> EpochGathers:
    """`vals_k` is (n_k, k) float32, or uint16 bf16 bits from an
    encoded shard — in the latter case `vb` STAYS in bits (the decode
    is fused into the consuming kernel) and only the anchor-coefficient
    reduction here reads a transient f32 view."""
    from repro.data.sparse import bf16_bits_to_f32
    M, b = idx.shape
    k = vals_k.shape[-1]
    vb = jnp.take(vals_k, idx, axis=0)                           # (M, b, k)
    vbf = bf16_bits_to_f32(vb) if vb.dtype == jnp.uint16 else vb
    yb = jnp.take(yk, idx, axis=0)                               # (M, b)
    zg = jnp.take(z, cflat, axis=0)                              # (M, S)
    wg = jnp.take(w_anchor, cflat, axis=0).reshape(M, b, k)
    sw = h_prime(jnp.sum(vbf * wg, axis=-1), yb)                 # (M, b)
    xd = None
    if b == 1 and statics is not None:
        xd = jnp.take(statics.xdup, idx.reshape(-1), axis=0)     # (M, k)
    return EpochGathers(vb=vb, yb=yb, zg=zg, sw=sw, xd=xd)


# ---------------------------------------------------------------------------
# inner_path="auto": the calibrated cost model
# ---------------------------------------------------------------------------

# Per-epoch cost models in MICROSECONDS, fit to the measured
# BENCH_inner_loop.json sweep on the reference container CPU
# (docs/kernels.md tabulates model vs measurement).  Absolute numbers
# are machine-specific; what the model must get right — and does, on
# every measured cell with >= 1.3x margin — is the SIGN of
# (lazy - dense), which is driven by two effects the terms encode:
#
# * dense pays (b + 5) O(d) vector passes per step, whose per-element
#   cost STEPS UP as the working set falls out of each cache tier
#   (_DENSE_TIER_US: ~0.55 ns/elem in-L2 to ~4 ns/elem in-DRAM);
# * the fused lazy engine pays per touched slot (plan build + scan
#   step math), two O(d) tails (final catch-up, plan delivery), and a
#   fixed per-step dispatch floor — and its small working set stays
#   cache-resident at every d in the sweep.
_LAZY_SLOT_US = 0.15      # per touched slot per epoch (plan + scan)
_LAZY_DIM_US = 0.04       # per coordinate (final catch-up + qf delivery)
_LAZY_STEP_US = 15.0      # per inner step (scan dispatch floor)


def _dense_tier_us_per_elem(d: int) -> float:
    """Measured per-element cost of one dense O(d) pass by cache tier."""
    if d <= (1 << 14):
        return 0.55e-3
    if d <= (1 << 16):
        return 1.6e-3
    return 4.0e-3


def dense_epoch_cost(d: int, inner_steps: int, inner_batch: int) -> float:
    """Modeled microseconds for one dense inner epoch."""
    elems = float(inner_steps) * (inner_batch + 5) * d
    return elems * _dense_tier_us_per_elem(d)


def lazy_epoch_cost(d: int, inner_steps: int, inner_batch: int,
                    nnz_per_row: int) -> float:
    """Modeled microseconds for one fused lazy inner epoch."""
    slots = float(inner_steps) * inner_batch * nnz_per_row
    return (_LAZY_SLOT_US * slots + _LAZY_DIM_US * d
            + _LAZY_STEP_US * inner_steps)


def choose_inner_path(d: int, inner_steps: int, inner_batch: int,
                      nnz_per_row: int, lazy_supported: bool = True) -> str:
    """Pick "dense" or "lazy" from the calibrated per-epoch cost model.

    ``nnz_per_row`` is the padded CSR slice width (max nnz per row) the
    lazy engine would actually gather.  Objectives without a
    linear-model h' cannot run lazy regardless of the model.
    """
    if not lazy_supported:
        return "dense"
    dense = dense_epoch_cost(d, inner_steps, inner_batch)
    lazy = lazy_epoch_cost(d, inner_steps, inner_batch, nnz_per_row)
    return "lazy" if lazy < dense else "dense"
