"""Public jit'd wrappers for the Pallas kernels.

Handles shape canonicalization (padding to (rows, 128) tiles), dtype
promotion, and the interpret-mode switch `_interpret()`, keyed on the
backend: off the TPU kernels run in Pallas interpret mode; on a TPU they
compile to Mosaic and never run interpreted.  `USE_PALLAS=0` env var
falls back to the jnp reference (used to A/B the kernels inside the
full system).
"""
from __future__ import annotations

import os
import functools

import jax
import jax.numpy as jnp

from repro.core import plan as _plan
from repro.kernels import ref as _ref
from repro.kernels.lazy_prox import lazy_prox_pallas
from repro.kernels.fused_prox_svrg import (fused_prox_svrg_pallas,
                                           fused_prox_svrg_diff_pallas)
from repro.kernels.sparse_inner import fused_lazy_epoch_pallas
from repro.kernels.flash_attention import flash_attention_pallas

_LANES = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_pallas() -> bool:
    return os.environ.get("USE_PALLAS", "1") != "0"


def _force_epoch_kernel() -> bool:
    """REPRO_SPARSE_INNER_KERNEL=1 forces the whole-epoch Pallas kernel
    even off-TPU (interpret mode) — used by tests and kernel A/Bs."""
    return os.environ.get("REPRO_SPARSE_INNER_KERNEL", "0") == "1"


def _to_tiles(x: jax.Array):
    """Flatten to (rows, 128) with zero padding; returns (tiles, d)."""
    flat = x.reshape(-1)
    d = flat.shape[0]
    rows = max(8, -(-d // _LANES))
    rows = -(-rows // 8) * 8
    pad = rows * _LANES - d
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, _LANES), d


def _from_tiles(tiles: jax.Array, d: int, shape):
    return tiles.reshape(-1)[:d].reshape(shape)


def lazy_prox(u: jax.Array, z: jax.Array, q: jax.Array, *, eta: float,
              lam1: float, lam2: float) -> jax.Array:
    """Catch-up of q skipped prox steps (Lemma 11); any shape, q int."""
    if not _use_pallas():
        return _ref.lazy_prox_ref(u, z, q, eta=eta, lam1=lam1, lam2=lam2)
    ut, d = _to_tiles(u.astype(jnp.float32))
    zt, _ = _to_tiles(jnp.broadcast_to(z, u.shape).astype(jnp.float32))
    qt, _ = _to_tiles(jnp.broadcast_to(q, u.shape).astype(jnp.int32))
    out = lazy_prox_pallas(ut, zt, qt, eta=eta, lam1=lam1, lam2=lam2,
                           interpret=_interpret())
    return _from_tiles(out, d, u.shape).astype(u.dtype)


def fused_prox_svrg(u: jax.Array, g_u: jax.Array, g_w: jax.Array,
                    z: jax.Array, *, eta: float, lam1: float,
                    lam2: float) -> jax.Array:
    """Fused VR-gradient + elastic-net prox step; any shape."""
    if not _use_pallas():
        return _ref.fused_prox_svrg_ref(u, g_u, g_w, z, eta=eta, lam1=lam1,
                                        lam2=lam2)
    ut, d = _to_tiles(u.astype(jnp.float32))
    gut, _ = _to_tiles(g_u.astype(jnp.float32))
    gwt, _ = _to_tiles(g_w.astype(jnp.float32))
    zt, _ = _to_tiles(z.astype(jnp.float32))
    out = fused_prox_svrg_pallas(ut, gut, gwt, zt, eta=eta, lam1=lam1,
                                 lam2=lam2, interpret=_interpret())
    return _from_tiles(out, d, u.shape).astype(u.dtype)


def fused_prox_svrg_diff(u: jax.Array, dv: jax.Array, z: jax.Array, *,
                         eta: float, lam1: float, lam2: float) -> jax.Array:
    """3-operand fused update: prox_en(u - eta*(dv + z)); any shape.

    dv is the precombined VR gradient difference grad f_B(u) - grad
    f_B(w) (linear-model fastpath) — one fewer (d,) HBM read than the
    4-operand variant.
    """
    if not _use_pallas():
        return _ref.fused_prox_svrg_diff_ref(u, dv, z, eta=eta, lam1=lam1,
                                             lam2=lam2)
    ut, d = _to_tiles(u.astype(jnp.float32))
    dvt, _ = _to_tiles(dv.astype(jnp.float32))
    zt, _ = _to_tiles(z.astype(jnp.float32))
    out = fused_prox_svrg_diff_pallas(ut, dvt, zt, eta=eta, lam1=lam1,
                                      lam2=lam2, interpret=_interpret())
    return _from_tiles(out, d, u.shape).astype(u.dtype)


def _tiles_with_spare(x: jax.Array, d: int, dtype) -> jax.Array:
    """(T, 8, 128) tiles holding x's first d entries with >= 1 spare tail
    slot — the dummy coordinate padded plan rows point at."""
    tiles = -(-(d + 1) // (8 * _LANES))
    flat = x.reshape(-1).astype(dtype)
    pad = tiles * 8 * _LANES - d
    return jnp.concatenate([flat, jnp.zeros((pad,), dtype)]).reshape(
        tiles, 8, _LANES)


def fused_lazy_epoch(u0: jax.Array, z: jax.Array, plan, gathers, *, h_prime,
                     eta: float, lam1: float, lam2: float,
                     inner_batch: int) -> jax.Array:
    """One fused lazy inner epoch: M plan-driven steps + final catch-up.

    `plan` is a core.plan.EpochPlan, `gathers` a core.plan.EpochGathers.
    Dispatch policy: the whole-epoch Pallas kernel runs when Pallas is
    enabled AND (the backend is a real TPU, or REPRO_SPARSE_INNER_KERNEL
    forces it) — in interpret mode the M-step grid costs more than the
    identical jnp scan, so off-TPU the reference formulation IS the
    production path (same convention as the per-step catch-up in
    docs/kernels.md).
    """
    if not (_use_pallas() and (not _interpret() or _force_epoch_kernel())):
        return _ref.fused_lazy_epoch_ref(u0, z, plan, gathers,
                                         h_prime=h_prime, eta=eta,
                                         lam1=lam1, lam2=lam2,
                                         inner_batch=inner_batch)
    eta_eff = eta / (1.0 + eta * lam1)
    d = u0.shape[0]
    M, S = plan.cflat.shape
    b = inner_batch
    k = S // b
    kp = _plan.padded_slot_width(k)
    padw = kp - k

    def pad_slots(a, fill, dtype):
        a3 = a.reshape(M, b, k).astype(dtype)
        return jnp.pad(a3, ((0, 0), (0, 0), (0, padw)),
                       constant_values=fill)

    # dummy column d = the guaranteed spare tile slot (value 0, z 0,
    # staleness 0: its update is the identity on a zero coordinate)
    cflat_p = pad_slots(plan.cflat, d, jnp.int32).reshape(M, 1, -1)
    q_p = pad_slots(plan.q, 0, jnp.int32)
    # remap duplicate representatives from slot space S to padded slot
    # space b * kp; padding slots represent themselves
    rep3 = plan.rep.reshape(M, b, k)
    rep_padded = jnp.pad(rep3 // k * kp + rep3 % k,
                         ((0, 0), (0, 0), (0, padw)))
    slot_iota = (jax.lax.broadcasted_iota(jnp.int32, (M, b, kp), 2)
                 + jax.lax.broadcasted_iota(jnp.int32, (M, b, kp), 1) * kp)
    pad_mask = jax.lax.broadcasted_iota(jnp.int32, (M, b, kp), 2) >= k
    rep_p = jnp.where(pad_mask, slot_iota, rep_padded).reshape(M, 1, -1)
    # the fold runs only up to each step's last duplicate slot
    fold_n = _plan.fold_bounds(plan.rep, b).reshape(M, 1, 1)
    # encoded shards deliver vb as uint16 bf16 bits (plan.EpochGathers);
    # pad in the native dtype and let the kernel decode in VMEM —
    # padding bits 0x0000 decode to exactly 0.0f, same as f32 padding
    vals_dtype = (jnp.uint16 if gathers.vb.dtype == jnp.uint16
                  else jnp.float32)
    vb_p = pad_slots(gathers.vb.reshape(M, S), 0, vals_dtype)
    zg_p = pad_slots(gathers.zg, 0.0, jnp.float32)
    out = fused_lazy_epoch_pallas(
        _tiles_with_spare(u0, d, jnp.float32),
        _tiles_with_spare(z, d, jnp.float32),
        _tiles_with_spare(plan.qf, d, jnp.int32), cflat_p, q_p, rep_p,
        fold_n, vb_p, gathers.yb.reshape(M, b, 1).astype(jnp.float32), zg_p,
        gathers.sw.reshape(M, b, 1).astype(jnp.float32), h_prime=h_prime,
        eta=eta, eta_eff=eta_eff, lam1=lam1, lam2=lam2, n_cols=k,
        interpret=_interpret())
    return out.reshape(-1)[:d].astype(u0.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Blocked attention; q (B,H,S,D), kv (B,KVH,S,D)."""
    if not _use_pallas():
        return _ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())
