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
duplicate representatives arrive in SMEM, one scalar per slot.

Mosaic lowers no vector gather or scatter, so the step moves the
touched coordinates slot by slot: a scalar column index from SMEM
selects an (8, 128) tile by its leading index, and a lane mask picks
or replaces the one coordinate in it.  Duplicate columns of a step are
folded into their representative slot in slot order (the reference's
scatter-add order), and only representatives are written back.
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


def _pick(x, mask):
    """The element of `x` under the one-hot `mask`, as a (1, 1) value
    (0 where the mask is empty)."""
    return jnp.sum(jnp.sum(jnp.where(mask, x, 0.0), axis=1, keepdims=True),
                   axis=0, keepdims=True)


def _epoch_kernel(cf_ref, rep_ref, u0_ref, z_ref, qf_ref, q_ref, vb_ref,
                  yb_ref, zg_ref, sw_ref, o_ref, *, h_prime, eta, eta_eff,
                  lam1, lam2, n_steps):
    i = pl.program_id(0)
    n_tiles = o_ref.shape[0]
    b, kp = q_ref.shape
    n_slots = b * kp

    def over_tiles(body):
        def step(t, carry):
            body(t)
            return carry
        jax.lax.fori_loop(0, n_tiles, step, 0)

    @pl.when(i == 0)
    def _init():
        def copy(t):
            o_ref[t] = u0_ref[t]
        over_tiles(copy)

    slot = (jax.lax.broadcasted_iota(jnp.int32, (b, kp), 0) * kp
            + jax.lax.broadcasted_iota(jnp.int32, (b, kp), 1))
    pos = (jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0) * _LANES
           + jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 1))

    # 1. gather the touched coordinates, one slot at a time
    def gather(s, g):
        c = cf_ref[0, s]
        val = _pick(o_ref[c >> _TILE_BITS], pos == (c & _TILE_MASK))
        return jnp.where(slot == s, val, g)

    u_g = jax.lax.fori_loop(0, n_slots, gather,
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
    # into its representative (rep[s] <= s), in slot order
    def fold(s, ge):
        r = rep_ref[0, s]
        src = jnp.where(r == s, -1, s)
        return jnp.where(slot == r, ge + _pick(ge, slot == src), ge)

    ge = jax.lax.fori_loop(0, n_slots, fold, ge)

    # 3. eta-step + elastic-net prox, written back by representatives
    new = prox_elastic_net(u_t - eta * (zgm + ge), eta, lam1, lam2)

    def scatter(s, carry):
        @pl.when(rep_ref[0, s] == s)
        def _write():
            c = cf_ref[0, s]
            t = c >> _TILE_BITS
            o_ref[t] = jnp.where(pos == (c & _TILE_MASK),
                                 _pick(new, slot == s), o_ref[t])
        return carry

    jax.lax.fori_loop(0, n_slots, scatter, 0)

    @pl.when(i == n_steps - 1)
    def _final_catch_up():
        def catch(t):
            o_ref[t] = _catch_up_block(o_ref[t], z_ref[t], qf_ref[t],
                                       eta_eff, lam1, lam2, 1 << 30)
        over_tiles(catch)


@functools.partial(jax.jit, static_argnames=("h_prime", "eta", "eta_eff",
                                             "lam1", "lam2", "interpret"))
def fused_lazy_epoch_pallas(u0_t: jax.Array, z_t: jax.Array, qf_t: jax.Array,
                            cflat: jax.Array, q: jax.Array, rep: jax.Array,
                            vb: jax.Array, yb: jax.Array, zg: jax.Array,
                            sw: jax.Array, *, h_prime, eta: float,
                            eta_eff: float, lam1: float, lam2: float,
                            interpret: bool = True) -> jax.Array:
    """u0_t/z_t: (T, 8, 128) f32; qf_t: (T, 8, 128) i32.

    Plan operands per step m, with kp a 128-multiple: q/zg (M, b, kp),
    yb/sw (M, b, 1), cflat/rep (M, 1, b * kp) int32 (rep in padded
    slot space).  `vb` is (M, b, kp) f32, or uint16 bf16 bit patterns
    from an encoded shard — decoded in VMEM, so the per-step value
    traffic from HBM halves.
    """
    T, sub, lanes = u0_t.shape
    M, b, kp = q.shape
    assert (sub, lanes) == (8, _LANES) and kp % _LANES == 0, (u0_t.shape, kp)
    assert cflat.shape == rep.shape == (M, 1, b * kp), (cflat.shape,
                                                        rep.shape)
    assert vb.dtype in (jnp.float32, jnp.uint16), vb.dtype
    full = pl.BlockSpec((T, 8, _LANES), lambda i: (0, 0, 0))
    row_s = pl.BlockSpec((None, b, kp), lambda i: (i, 0, 0))
    row_b = pl.BlockSpec((None, b, 1), lambda i: (i, 0, 0))
    slots = pl.BlockSpec((None, 1, b * kp), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    kernel = functools.partial(_epoch_kernel, h_prime=h_prime, eta=eta,
                               eta_eff=eta_eff, lam1=lam1, lam2=lam2,
                               n_steps=M)
    return pl.pallas_call(
        kernel,
        grid=(M,),
        in_specs=[slots, slots, full, full, full, row_s, row_s, row_b,
                  row_s, row_b],
        out_specs=full,
        out_shape=jax.ShapeDtypeStruct(u0_t.shape, u0_t.dtype),
        interpret=interpret,
        name="fused_lazy_epoch",
    )(cflat, rep, u0_t, z_t, qf_t, q, vb, yb, zg, sw)
