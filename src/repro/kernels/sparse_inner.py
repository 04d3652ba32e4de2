"""Fused lazy inner epoch as a single Pallas TPU kernel.

The whole inner epoch is one ``pallas_call`` with ``grid=(M,)``:

* the iterate u lives in the kernel's output block in VMEM for the
  whole epoch (the block index map is constant, so the M grid steps
  revisit the same VMEM-resident tiles — the standard accumulator
  pattern); it is written back to HBM once;
* each grid step streams in only its own row of the epoch plan
  (precomputed active columns, staleness counts, duplicate
  representatives — core/plan.py) and microbatch operands;
* the step body does gather -> Lemma-11 catch-up -> support-restricted
  VR gradient -> eta-step -> elastic-net prox -> duplicate-safe
  scatter;
* the last grid step additionally applies the O(d) final catch-up
  in-place, so no separate kernel launch is needed for it.

Memory layout.  u/z/qf are (T, 8, 128) tiles: coordinate c sits in
tile c >> 10 at flat position c & 1023, with at least one spare tail
slot.  Plan rows are padded to a 128-multiple slot count per sample
with a dummy column pointing at that spare slot (value 0, staleness 0),
whose update is the identity.  The per-step vector operands arrive as
(b, kp) blocks (samples x padded slots); the active columns and
duplicate representatives arrive in SMEM, one scalar per slot, beside
the step's fold bound.

Mosaic lowers no vector gather or scatter, so the step moves the
touched coordinates slot by slot, each by placing it and not by
picking it out.  A scalar column index c from SMEM selects an (8, 128)
tile by its leading index (c >> 10).  The gather masks the tile to the
one position c & 1023, sums its 8 sublanes into one row that holds the
value at lane c & 127, and rotates that row (`pltpu.roll`) so the
value lands on its slot's lane.  The scatter rotates the slot's lane
of the step's new values to lane c & 127, broadcasts it over the
sublanes and writes it with a masked store of that one position: no
tile load and no branch.  Duplicate columns of a step are
folded into their representative slot in slot order (the reference's
scatter-add order), and only representatives are written back: their
columns are distinct, so the single-position stores commute.

VMEM.  The pipeline double-buffers every block, and the four
iterate-sized ones (u0, z, qf and the output) take 32 B a coordinate:
1.4 MiB at rcv1's 47,236 features, under Mosaic's default scoped limit
of 16 MiB on a v5e, and 41.4 MiB at news20's 1,355,191.  Past the
default the kernel asks for the VMEM its blocks need, up to the v5e's
128 MiB, which holds d up to `MAX_FEATURES` (about 4.06 M).

The slot loops run only over slots that can change the iterate: the
gather and the scatter over the b * k real slots (a padded slot's
gather reads 0 and its write-back stores 0, which the zero-initialised
gather block and the untouched spare coordinate already hold), and the
fold up to the step's bound, one past its last duplicate slot
(`core.plan.fold_bounds`; every later slot represents itself).  The
gather and the scatter run their slots in groups of `_GROUP` written
out in one loop body, the remainder after the last whole group
unrolled, so the independent slots of a group can overlap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.prox import prox_elastic_net
from repro.kernels.lazy_prox import _catch_up_block

_LANES = 128
_TILE_BITS = 10                     # an (8, 128) tile holds 1024 coordinates
_TILE_MASK = (1 << _TILE_BITS) - 1
# slots a gather / scatter loop trip: the fastest of 1 to 64 on a v5e
_GROUP = 64
# Mosaic's default scoped-VMEM limit on a v5e, and the chip's VMEM
_VMEM_DEFAULT = 16 << 20
_VMEM_CAP = 128 << 20
# room for the step's own blocks and Mosaic's internal scratch
_VMEM_SLACK = 4 << 20
# bytes of VMEM an (8, 128) tile of the iterate takes: u0, z, qf and the
# output, each double-buffered
_VMEM_PER_TILE = 4 * 2 * 8 * 128 * 4
# the largest d whose tiles (with the spare tail slot) fit the cap
MAX_FEATURES = (_VMEM_CAP - _VMEM_SLACK) // _VMEM_PER_TILE * 1024 - 1


def _vmem_limit(n_tiles: int):
    """The scoped-VMEM limit the kernel asks for: None (Mosaic's
    default) where its blocks fit that, else what they need."""
    need = n_tiles * _VMEM_PER_TILE + _VMEM_SLACK
    if need <= _VMEM_DEFAULT:
        return None
    if need > _VMEM_CAP:
        raise ValueError(
            f"the epoch kernel's iterate of {n_tiles} tiles needs {need} B "
            f"of VMEM, more than a v5e's {_VMEM_CAP}: d above "
            f"{MAX_FEATURES} does not fit")
    return need


def _pick(x, mask):
    """The element of `x` under the one-hot `mask`, as a (1, 1) value
    (0 where the mask is empty)."""
    return jnp.sum(jnp.sum(jnp.where(mask, x, 0.0), axis=1, keepdims=True),
                   axis=0, keepdims=True)


def _epoch_kernel(cf_ref, rep_ref, nf_ref, u0_ref, z_ref, qf_ref, q_ref,
                  vb_ref, yb_ref, zg_ref, sw_ref, o_ref, *, h_prime, eta,
                  eta_eff, lam1, lam2, n_cols, n_steps):
    i = pl.program_id(0)
    n_tiles = o_ref.shape[0]
    b, kp = q_ref.shape

    def over_tiles(body):
        def step(t, carry):
            body(t)
            return carry
        jax.lax.fori_loop(0, n_tiles, step, 0)

    def over_slots(n, body, carry):
        # body(i, carry) for i in [0, n), n static: a loop over whole
        # groups of _GROUP slots, then the remainder written out
        def group(t, carry):
            for u in range(_GROUP):
                carry = body(t * _GROUP + u, carry)
            return carry
        carry = jax.lax.fori_loop(0, n // _GROUP, group, carry)
        for i in range(n - n % _GROUP, n):
            carry = body(i, carry)
        return carry

    def lane_blocks():
        # (first slot, real slots i < n_cols) of each 128-lane block of a
        # sample's kp slots
        return [(lo, max(0, min(n_cols - lo, _LANES)))
                for lo in range(0, kp, _LANES)]

    @pl.when(i == 0)
    def _init():
        def copy(t):
            o_ref[t] = u0_ref[t]
        over_tiles(copy)

    slot = (jax.lax.broadcasted_iota(jnp.int32, (b, kp), 0) * kp
            + jax.lax.broadcasted_iota(jnp.int32, (b, kp), 1))
    sample = jax.lax.broadcasted_iota(jnp.int32, (b, kp), 0)
    pos = (jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0) * _LANES
           + jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 1))

    # 1. gather the touched coordinates: slot i of sample j reads column
    #    c into lane i of row j
    def gather_sample(j, g):
        rows = []
        for lo, n in lane_blocks():
            def gather(i, acc, base=j * kp + lo):
                c = cf_ref[0, base + i]
                x = jnp.where(pos == (c & _TILE_MASK),
                              o_ref[c >> _TILE_BITS], 0.0)
                at_c = jnp.sum(x, axis=0, keepdims=True)   # lane c & 127
                return acc + pltpu.roll(at_c, (i - c) & (_LANES - 1), 1)
            rows.append(over_slots(n, gather,
                                   jnp.zeros((1, _LANES), jnp.float32)))
        row = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
        return jnp.where(sample == j, row, g)

    u_g = jax.lax.fori_loop(0, b, gather_sample,
                            jnp.zeros((b, kp), jnp.float32))

    # 2. Lemma-11 catch-up, then the support-restricted VR gradient
    #    entries (anchor half precomputed)
    zgm = zg_ref[...]
    u_t = _catch_up_block(u_g, zgm, q_ref[...], eta_eff, lam1, lam2, 1 << 30)
    vbm = vb_ref[...]
    if vbm.dtype == jnp.uint16:
        # encoded-shard path: raw bf16 bit patterns (half the HBM bytes
        # of f32); the bf16 -> f32 widening is exact
        vbm = jax.lax.bitcast_convert_type(vbm, jnp.bfloat16)
    vbm = vbm.astype(jnp.float32)
    du = jnp.sum(vbm * u_t, axis=1, keepdims=True)                 # (b, 1)
    coef = (h_prime(du, yb_ref[...]) - sw_ref[...]) / b
    ge = coef * vbm

    # duplicate-safe accumulation: fold every duplicate slot's entry
    # into its representative (rep[s] <= s), in slot order, up to the
    # step's last duplicate slot
    def fold(s, ge):
        r = rep_ref[0, s]
        src = jnp.where(r == s, -1, s)
        return jnp.where(slot == r, ge + _pick(ge, slot == src), ge)

    ge = jax.lax.fori_loop(0, nf_ref[0, 0], fold, ge)

    # 3. eta-step + elastic-net prox, written back by representatives
    new = prox_elastic_net(u_t - eta * (zgm + ge), eta, lam1, lam2)

    def scatter_sample(j, carry):
        row = jnp.sum(jnp.where(sample == j, new, 0.0), axis=0,
                      keepdims=True)                              # (1, kp)
        for lo, n in lane_blocks():
            def scatter(i, carry, base=j * kp + lo,
                        block=row[:, lo:lo + _LANES]):
                s = base + i
                c = cf_ref[0, s]
                at_c = pltpu.roll(block, (c - i) & (_LANES - 1), 1)
                # only a representative writes (a non-representative's
                # mask is empty: no position is -1)
                p = jnp.where(rep_ref[0, s] == s, c & _TILE_MASK, -1)
                pltpu.store(o_ref.at[c >> _TILE_BITS],
                            jnp.broadcast_to(at_c, (8, _LANES)),
                            mask=pos == p)
                return carry
            over_slots(n, scatter, 0)
        return carry

    jax.lax.fori_loop(0, b, scatter_sample, 0)

    @pl.when(i == n_steps - 1)
    def _final_catch_up():
        def catch(t):
            o_ref[t] = _catch_up_block(o_ref[t], z_ref[t], qf_ref[t],
                                       eta_eff, lam1, lam2, 1 << 30)
        over_tiles(catch)


@functools.partial(jax.jit, static_argnames=("h_prime", "eta", "eta_eff",
                                             "lam1", "lam2", "n_cols",
                                             "interpret"))
def fused_lazy_epoch_pallas(u0_t: jax.Array, z_t: jax.Array, qf_t: jax.Array,
                            cflat: jax.Array, q: jax.Array, rep: jax.Array,
                            fold_n: jax.Array, vb: jax.Array, yb: jax.Array,
                            zg: jax.Array, sw: jax.Array, *, h_prime,
                            eta: float, eta_eff: float, lam1: float,
                            lam2: float, n_cols: int,
                            interpret: bool = True) -> jax.Array:
    """u0_t/z_t: (T, 8, 128) f32; qf_t: (T, 8, 128) i32.

    Plan operands per step m, with kp a 128-multiple holding each
    sample's `n_cols` real slots first: q/zg (M, b, kp), yb/sw
    (M, b, 1), cflat/rep (M, 1, b * kp) int32 (rep in padded slot
    space), fold_n (M, 1, 1) int32 (`core.plan.fold_bounds`).  `vb` is
    (M, b, kp) f32, or uint16 bf16 bit patterns from an encoded shard —
    decoded in VMEM, so the per-step value traffic from HBM halves.
    """
    T, sub, lanes = u0_t.shape
    M, b, kp = q.shape
    assert (sub, lanes) == (8, _LANES) and kp % _LANES == 0, (u0_t.shape, kp)
    assert 0 < n_cols <= kp, (n_cols, kp)
    assert cflat.shape == rep.shape == (M, 1, b * kp), (cflat.shape,
                                                        rep.shape)
    assert fold_n.shape == (M, 1, 1), fold_n.shape
    assert vb.dtype in (jnp.float32, jnp.uint16), vb.dtype
    full = pl.BlockSpec((T, 8, _LANES), lambda i: (0, 0, 0))
    row_s = pl.BlockSpec((None, b, kp), lambda i: (i, 0, 0))
    row_b = pl.BlockSpec((None, b, 1), lambda i: (i, 0, 0))
    slots = pl.BlockSpec((None, 1, b * kp), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    step_scalar = pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM)
    kernel = functools.partial(_epoch_kernel, h_prime=h_prime, eta=eta,
                               eta_eff=eta_eff, lam1=lam1, lam2=lam2,
                               n_cols=n_cols, n_steps=M)
    limit = _vmem_limit(T)
    return pl.pallas_call(
        kernel,
        grid=(M,),
        in_specs=[slots, slots, step_scalar, full, full, full, row_s, row_s,
                  row_b, row_s, row_b],
        out_specs=full,
        out_shape=jax.ShapeDtypeStruct(u0_t.shape, u0_t.dtype),
        interpret=interpret,
        compiler_params=(None if limit is None else
                         pltpu.CompilerParams(vmem_limit_bytes=limit)),
        name="fused_lazy_epoch",
    )(cflat, rep, fold_n, u0_t, z_t, qf_t, q, vb, yb, zg, sw)
