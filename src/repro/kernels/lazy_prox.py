"""Pallas TPU kernel for the paper's recovery rule (Lemma 11).

Catch a parameter block up by q skipped autonomous prox steps
    u <- S_{lam2*eta}((1 - lam1*eta) u - eta z)
in closed form.  Elementwise on the VPU; (8,128)-aligned VMEM blocks.

The math is shared with core/recovery.py (`recovery_catch_up`), which
doubles as the ref oracle — the kernel body runs the identical
branch-free phase decomposition on a VMEM tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import recovery as _rec

# sublane x lane tile; multiple rows per program amortizes grid overhead
_BLOCK_ROWS = 256
_LANES = 128


def _lazy_prox_kernel(u_ref, z_ref, q_ref, o_ref, *, eta, lam1, lam2, q_max):
    u = u_ref[...]
    z = z_ref[...]
    q = q_ref[...]
    o_ref[...] = _catch_up_block(u, z, q, eta, lam1, lam2, q_max)


def expm1_poly(x):
    """expm1 from ops the TPU kernel lowering accepts (it has no expm1).

    Below |x| = 0.5 a degree-9 Taylor polynomial (truncation error
    < 1.1e-8 |x|), above it exp(x) - 1, whose cancellation error is
    at most one ulp of 1 over |x| >= 0.5.  Either way the relative
    error stays within a few f32 ulps, the accuracy `_beta`'s
    geometric form needs when lam1*eta is tiny.
    """
    poly = jnp.zeros_like(x)
    for n in range(9, 0, -1):
        poly = (poly + 1.0) * x / n
    return jnp.where(jnp.abs(x) < 0.5, poly, jnp.exp(x) - 1.0)


def _catch_up_block(u, z, q, eta, lam1, lam2, q_max):
    """Branch-free Lemma-11 catch-up on one VMEM tile: the phase
    structure of core.recovery.recovery_catch_up, with `expm1_poly` in
    the affine phases so Pallas traces only ops Mosaic lowers."""
    def affine(u0, s, r, z_):
        return _rec._affine_phase(u0, s, r, z_, eta, lam1, lam2,
                                  expm1=expm1_poly)

    s0 = jnp.sign(u)
    q0 = _rec._q0_branch_steps(u, jnp.where(s0 == 0, 1.0, s0), z, eta, lam1,
                               lam2, q_max, affine=affine)
    q0 = jnp.where(s0 == 0, 0, q0)
    return _rec._finish_catch_up(u, z, q, eta, lam1, lam2, q0, affine)


@functools.partial(jax.jit,
                   static_argnames=("eta", "lam1", "lam2", "interpret"))
def lazy_prox_pallas(u: jax.Array, z: jax.Array, q: jax.Array, *, eta: float,
                     lam1: float, lam2: float,
                     interpret: bool = True) -> jax.Array:
    """u, z: (rows, 128) float32; q: (rows, 128) int32. rows % 8 == 0."""
    rows, lanes = u.shape
    assert lanes == _LANES and rows % 8 == 0, (rows, lanes)
    block_rows = min(_BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block_rows),)
    bspec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    kernel = functools.partial(_lazy_prox_kernel, eta=eta, lam1=lam1,
                               lam2=lam2, q_max=1 << 30)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[bspec, bspec, bspec],
        out_specs=bspec,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        interpret=interpret,
        name="lazy_prox",
    )(u, z, q)
