"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.recovery import recovery_catch_up as _catch_up
from repro.core.recovery import (catch_up_tables,
                                 recovery_catch_up_capped as _catch_up_capped)
from repro.core.prox import prox_elastic_net


def lazy_prox_ref(u, z, q, *, eta, lam1, lam2):
    """Oracle for kernels/lazy_prox: Lemma-11 catch-up (any shape)."""
    return _catch_up(u, z, q, eta, lam1, lam2)


def lazy_prox_sequential_ref(u, z, q, *, eta, lam1, lam2, max_steps):
    """Literal step-by-step oracle (slow; ground truth for both)."""
    from repro.core.recovery import sequential_catch_up
    return sequential_catch_up(u, z, q, eta, lam1, lam2, max_steps)


def fused_lazy_epoch_ref(u0, z, plan, gathers, *, h_prime, eta, lam1, lam2,
                         inner_batch):
    """Oracle for kernels/sparse_inner: one fused lazy inner epoch.

    Runs the plan-driven scan: per step, ONE gather of the iterate at
    the step's active columns, the Lemma-11 catch-up with the
    precomputed staleness counts, the support-restricted VR step +
    elastic-net prox, and ONE duplicate-safe scatter back — then the
    single O(d) final catch-up.  This is also the production CPU path
    (see kernels/ops.fused_lazy_epoch); the Pallas kernel runs the
    identical math with the iterate resident in VMEM.

    The catch-up replays the standard-prox iteration at the effective
    step size eta_eff = eta / (1 + eta*lam1) (see docs/kernels.md,
    "prox-convention bridge").

    Encoded shards (`gathers.vb` as uint16 bf16 bits, see
    plan.EpochGathers) are decoded here at operand-pack time: the
    bits -> f32 bitcast is exact and XLA fuses it into the pack
    concatenation, so the scan body is unchanged and bitwise identical
    to the f32-input path on bf16-representable data.
    """
    from repro.data.sparse import bf16_bits_to_f32
    vb_all = gathers.vb
    if vb_all.dtype == jnp.uint16:
        vb_all = bf16_bits_to_f32(vb_all)
    eta_eff = eta / (1.0 + eta * lam1)
    b = inner_batch
    M, S = plan.cflat.shape
    k = S // b
    # in-epoch staleness is bounded by M, so every catch-up (per-step
    # AND the final O(d) pass) runs the capped tabulated form — bitwise
    # identical to the unbounded one, but the affine-phase
    # transcendentals become gathers from these (M + 2,) tables, built
    # once here so the scan body cannot re-materialize them per step
    tables = catch_up_tables(eta_eff, lam1, M)

    def catch(u_g, z_g, q_g):
        return _catch_up_capped(u_g, z_g, q_g, eta_eff, lam1, lam2,
                                q_cap=M, tables=tables)

    # the step-indexed operands are packed into ONE f32 array so the
    # scan slices a single buffer per step instead of 7 — on CPU the
    # per-step dynamic-slice dispatch is a measurable slice of the whole
    # epoch.  Index payloads (cflat < d, rep < S, q <= M) round-trip
    # exactly through f32 below 2^24; beyond that, fall back to a
    # separate int32 buffer.
    exact_f32 = plan.qf.shape[0] < (1 << 24) and M < (1 << 24)

    def pack(int_cols, flt_cols):
        if exact_f32:
            cols = [c.astype(jnp.float32) for c in int_cols] + list(flt_cols)
            return jnp.concatenate(cols, axis=1), None
        return (jnp.concatenate(flt_cols, axis=1),
                jnp.concatenate(int_cols, axis=1))

    def unpack_ints(x, widths):
        buf, ints = x
        out, off = [], 0
        src = buf if ints is None else ints
        for wd in widths:
            col = src[off:off + wd]
            out.append(col.astype(jnp.int32) if ints is None else col)
            off += wd
        flt_off = off if ints is None else 0
        return out, buf, flt_off

    if gathers.xd is not None and b == 1:
        # b = 1 fast path: duplicate groups resolved via the statically
        # dup-summed values, no scatter-add in the scan
        packed = pack([plan.cflat, plan.q],
                      [vb_all.reshape(M, k), gathers.xd, gathers.zg,
                       gathers.sw.reshape(M, 1), gathers.yb.reshape(M, 1)])

        def step(u, x):
            (cf, qm), fv, o = unpack_ints(x, (k, k))
            vbm, xdm = fv[o:o + k], fv[o + k:o + 2 * k]
            zgm = fv[o + 2 * k:o + 3 * k]
            swm, ybm = fv[o + 3 * k], fv[o + 3 * k + 1]
            u_t = catch(jnp.take(u, cf, axis=0), zgm, qm)
            coef = h_prime(jnp.sum(vbm * u_t), ybm) - swm
            u_new = prox_elastic_net(u_t - eta * (zgm + coef * xdm),
                                     eta, lam1, lam2)
            return u.at[cf].set(u_new), None
    else:
        # general path: per-slot gradient entries accumulated across
        # duplicates by a segment-sum keyed on the plan's representative
        packed = pack([plan.cflat, plan.q, plan.rep],
                      [vb_all.reshape(M, S), gathers.zg,
                       gathers.sw.reshape(M, b), gathers.yb.reshape(M, b)])

        def step(u, x):
            (cf, qm, rp), fv, o = unpack_ints(x, (S, S, S))
            vbm, zgm = fv[o:o + S].reshape(b, k), fv[o + S:o + 2 * S]
            swm = fv[o + 2 * S:o + 2 * S + b]
            ybm = fv[o + 2 * S + b:o + 2 * S + 2 * b]
            u_t = catch(jnp.take(u, cf, axis=0), zgm, qm)
            du = jnp.sum(vbm * u_t.reshape(b, k), axis=-1)
            coef = (h_prime(du, ybm) - swm) / b
            ge = (coef[:, None] * vbm).reshape(S)
            ge_tot = jnp.take(jnp.zeros((S,), u.dtype).at[rp].add(ge), rp)
            u_new = prox_elastic_net(u_t - eta * (zgm + ge_tot),
                                     eta, lam1, lam2)
            return u.at[cf].set(u_new), None

    u, _ = jax.lax.scan(step, u0, packed)
    return catch(u, z, plan.qf)


def fused_prox_svrg_ref(u, g_u, g_w, z, *, eta, lam1, lam2):
    """Oracle for kernels/fused_prox_svrg."""
    v = g_u - g_w + z
    return prox_elastic_net(u - eta * v, eta, lam1, lam2)


def fused_prox_svrg_diff_ref(u, dv, z, *, eta, lam1, lam2):
    """Oracle for the 3-operand diff variant (dv = g_u - g_w precombined)."""
    return prox_elastic_net(u - eta * (dv + z), eta, lam1, lam2)

