"""Fused prox-SVRG inner update as a Pallas TPU kernel.

    u <- prox_elastic_net(u - eta * (g_u - g_w + z), eta)

Unfused this is 3 HBM-bound elementwise ops (subtract-combine, axpy,
prox) = 7 reads + 3 writes of the parameter vector; fused it is 4 reads
+ 1 write in a single VMEM pass — a 2x cut of the memory-roofline term
of the inner loop, which is memory-bound (arithmetic intensity < 1
FLOP/byte).

Two variants share the tiling:
  * 4-operand (u, g_u, g_w, z) for the autodiff path, where the two
    batch gradients arrive as separate arrays;
  * 3-operand "diff" (u, dv, z) for the linear-model fastpath, which
    already forms dv = grad f_B(u) - grad f_B(w) with a single
    X_B^T matvec (see svrg.linear_model_vr_diff) — one fewer (d,)
    HBM read per inner step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK_ROWS = 256
_LANES = 128


def _fused_kernel(u_ref, gu_ref, gw_ref, z_ref, o_ref, *, eta, lam1, lam2):
    u = u_ref[...]
    v = gu_ref[...] - gw_ref[...] + z_ref[...]
    t = u - eta * v
    # elastic-net prox: soft-threshold then shrink
    st = jnp.sign(t) * jnp.maximum(jnp.abs(t) - eta * lam2, 0.0)
    o_ref[...] = st / (1.0 + eta * lam1)


@functools.partial(jax.jit,
                   static_argnames=("eta", "lam1", "lam2", "interpret"))
def fused_prox_svrg_pallas(u: jax.Array, g_u: jax.Array, g_w: jax.Array,
                           z: jax.Array, *, eta: float, lam1: float,
                           lam2: float, interpret: bool = True) -> jax.Array:
    rows, lanes = u.shape
    assert lanes == _LANES and rows % 8 == 0, (rows, lanes)
    block_rows = min(_BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block_rows),)
    bspec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    kernel = functools.partial(_fused_kernel, eta=eta, lam1=lam1, lam2=lam2)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[bspec] * 4,
        out_specs=bspec,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        interpret=interpret,
        name="fused_prox_svrg",
    )(u, g_u, g_w, z)


def _fused_diff_kernel(u_ref, dv_ref, z_ref, o_ref, *, eta, lam1, lam2):
    t = u_ref[...] - eta * (dv_ref[...] + z_ref[...])
    st = jnp.sign(t) * jnp.maximum(jnp.abs(t) - eta * lam2, 0.0)
    o_ref[...] = st / (1.0 + eta * lam1)


@functools.partial(jax.jit,
                   static_argnames=("eta", "lam1", "lam2", "interpret"))
def fused_prox_svrg_diff_pallas(u: jax.Array, dv: jax.Array, z: jax.Array,
                                *, eta: float, lam1: float, lam2: float,
                                interpret: bool = True) -> jax.Array:
    rows, lanes = u.shape
    assert lanes == _LANES and rows % 8 == 0, (rows, lanes)
    block_rows = min(_BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block_rows),)
    bspec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    kernel = functools.partial(_fused_diff_kernel, eta=eta, lam1=lam1,
                               lam2=lam2)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[bspec] * 3,
        out_specs=bspec,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        interpret=interpret,
        name="fused_prox_svrg_diff",
    )(u, dv, z)
