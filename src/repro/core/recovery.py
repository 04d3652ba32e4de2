"""Recovery strategy for high-dimensional sparse data (Section 6, Lemma 11).

When coordinate j of the data gradient is zero for q consecutive inner
steps (x_s^(j) == 0 for the sampled instances), the prox-SVRG update of
that coordinate reduces to the autonomous scalar iteration

    u <- S_{lam2*eta}( (1 - lam1*eta) * u - eta * z_j )          (*)

(S = soft threshold).  The paper's Lemma 11 gives closed forms to jump
q steps at once.  The CPU formulation is a per-coordinate case analysis
(5 sign cases); here it is restructured **branch-free** so it vectorizes
on the TPU VPU (and is implemented as a Pallas kernel in
kernels/lazy_prox.py):

  * phase A — the iterate keeps its initial sign s0; the dynamics is
    affine: u_m = rho^m u_0 - eta*(z + s0*lam2)*beta_m, with
    rho = 1 - lam1*eta, beta_m = (1-rho^m)/(1-rho).  The number of steps
    q0 for which the sign survives has a closed form (log/linear).
  * one exact prox step lands either in the absorbing 0 state or jumps
    across to the opposite branch;
  * phase B — at most one more sign regime (the opposite branch is
    invariant), again affine.

The trajectory of (*) changes sign at most once, so 2 exact steps + 2
affine phases reproduce any number of iterations exactly.  Equivalence
with the literal sequential iteration is enforced by hypothesis tests
(tests/test_recovery.py) over all five z-sign cases of Lemma 11.

All functions accept per-coordinate step counts q (int array), enabling
the block-lazy Algorithm 2 execution in `lazy_inner_loop`.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.prox import soft_threshold

Array = jax.Array


def _rho_pow(r, lam1_eta):
    """rho^r with rho = 1 - lam1_eta, stable for tiny lam1_eta."""
    r = jnp.asarray(r, jnp.float32)
    return jnp.exp(r * jnp.log1p(-lam1_eta))


def _beta(r, lam1_eta, expm1=jnp.expm1):
    """beta_r = sum_{i=1..r} rho^{i-1} = (1 - rho^r)/lam1_eta; = r at 0.

    Uses expm1/log1p to avoid the (1-rho^r)/(1-rho) cancellation that
    loses ~3 digits in float32 when lam1*eta ~ 1e-6.  `expm1` is
    swappable because the TPU kernel lowering has none (see
    kernels/lazy_prox.expm1_poly); this oracle keeps the exact one.
    """
    r = jnp.asarray(r, jnp.float32)
    geom = -expm1(r * jnp.log1p(-lam1_eta)) / jnp.maximum(lam1_eta, 1e-38)
    # below ~1e-30 the f32 log1p underflows and the geometric form is
    # 0/0: treat as the lam1 = 0 linear regime (they agree to <1e-28)
    return jnp.where(lam1_eta > 1e-30, geom, r)


def _affine_phase(u0, s, r, z, eta, lam1, lam2, expm1=jnp.expm1):
    """u after r steps of (*) assuming the sign stays s the whole time."""
    lam1_eta = lam1 * eta
    c = z + s * lam2
    return (_rho_pow(r, lam1_eta) * u0
            - eta * c * _beta(r, lam1_eta, expm1))


def _exact_step(u, z, eta, lam1, lam2):
    """One literal iteration of (*)."""
    rho = 1.0 - lam1 * eta
    return soft_threshold(rho * u - eta * z, lam2 * eta)


def _q0_branch_steps(u0, s, z, eta, lam1, lam2, q_max, affine=None):
    """Largest m such that the affine phase keeps sign s for steps 1..m.

    Closed form with a +-1 float-robustness correction. Where the branch
    never exits (s*(z + s*lam2) <= 0), returns q_max.  `affine`
    overrides the phase evaluator (the capped variant passes its
    tabulated one — same floats, shared numerics).
    """
    lam1_eta = lam1 * eta
    c_hat = s * (z + s * lam2)            # > 0 iff branch eventually exits
    su0 = s * u0
    big = jnp.asarray(q_max, jnp.float32)

    safe_c = jnp.maximum(c_hat, 1e-30)
    # rho < 1: q0 = floor( ln(1 + su0*lam1_eta/(eta*c)) / -ln(rho) )
    log_form = jnp.log1p(su0 * lam1_eta / (eta * safe_c)) / jnp.maximum(
        -jnp.log1p(-lam1_eta), 1e-38)
    # rho == 1: alpha_q = q  =>  q0 = floor(su0 / (eta*c))
    lin_form = su0 / (eta * safe_c)
    q0f = jnp.where(lam1_eta > 1e-30, log_form, lin_form)
    # clip in float before the int cast: lin_form reaches ~1e30 for a
    # tiny c_hat, and an out-of-range float -> int32 cast is undefined
    q0 = jnp.floor(jnp.clip(jnp.where(c_hat > 0, q0f, big), 0.0, big))
    q0 = q0.astype(jnp.int32)

    if affine is None:
        def affine(u0_, s_, r, z_):
            return _affine_phase(u0_, s_, r, z_, eta, lam1, lam2)

    # float-robustness: ensure sign survives at q0 and dies at q0+1
    def sign_at(m):
        return s * affine(u0, s, m, z)

    for _ in range(2):
        q0 = jnp.where(sign_at(q0) < 0, jnp.maximum(q0 - 1, 0), q0)
        q0 = jnp.where(
            (q0 < q_max) & (sign_at(q0 + 1) > 0) & (c_hat > 0), q0 + 1, q0)
    q0 = jnp.where(c_hat > 0, q0, q_max)
    return q0


def _finish_catch_up(u: Array, z: Array, q: Array, eta: float, lam1: float,
                     lam2: float, q0: Array, affine) -> Array:
    """The shared phase structure of the Lemma-11 catch-up.

    Given the (s0-masked) phase-A length bound `q0` and an evaluator
    `affine(u0, s, r, z)` for r affine steps under constant sign s
    (closed-form exp or the capped table — both compute the identical
    floats), runs: phase A for a = min(q, q0) steps, the landing step
    (exits the branch / leaves 0), the absorbing-zero case, the second
    landing, and phase B on the opposite branch.
    """
    s0 = jnp.sign(u)
    a = jnp.minimum(q, q0)
    u_a = jnp.where(s0 == 0, u, affine(u, s0, a, z))
    done = q <= a

    # ---- landing step (exits the branch / leaves 0) -----------------------
    u_b = _exact_step(u_a, z, eta, lam1, lam2)
    u_res = jnp.where(done, u_a, u_b)
    done_b = done | (q <= a + 1)

    # ---- absorbing zero ----------------------------------------------------
    absorbed = (u_b == 0.0) & (jnp.abs(z) <= lam2)
    done_zero = done_b | absorbed

    # ---- second landing (leaves 0 when |z| > lam2) -------------------------
    u_c = _exact_step(u_b, z, eta, lam1, lam2)
    # If u_b != 0 it jumped straight onto the opposite branch; phase B then
    # starts at u_b with r = q - a - 1 steps. If u_b == 0 and not absorbed,
    # phase B starts at u_c with r = q - a - 2 steps.
    jumped = u_b != 0.0
    s1 = jnp.where(jumped, jnp.sign(u_b), jnp.sign(u_c))
    start = jnp.where(jumped, u_b, u_c)
    r = jnp.maximum(jnp.where(jumped, q - a - 1, q - a - 2), 0)
    u_phase_b = affine(start, s1, r, z)

    out = jnp.where(done_zero, jnp.where(done_b, u_res, 0.0), u_phase_b)
    # q == 0 must be the identity
    return jnp.where(q == 0, u, out)


def recovery_catch_up(u: Array, z: Array, q: Array, eta: float,
                      lam1: float, lam2: float, q_max: int = 1 << 30) -> Array:
    """Jump q steps of iteration (*) at once; q may vary per coordinate.

    Exactly equivalent to applying `_exact_step` q times (Lemma 11).
    """
    q = jnp.asarray(q, jnp.int32)
    s0 = jnp.sign(u)
    q0 = _q0_branch_steps(u, jnp.where(s0 == 0, 1.0, s0), z, eta, lam1, lam2,
                          q_max)
    q0 = jnp.where(s0 == 0, 0, q0)

    def affine(u0, s, r, z_):
        return _affine_phase(u0, s, r, z_, eta, lam1, lam2)

    return _finish_catch_up(u, z, q, eta, lam1, lam2, q0, affine)


def catch_up_tables(eta: float, lam1: float, q_cap: int):
    """(rho^r, beta_r) for r in [0, q_cap + 1] — the loop-invariant
    tables of `recovery_catch_up_capped`.  Build once outside a scan
    and pass back in so XLA cannot re-materialize them per step."""
    lam1_eta = lam1 * eta
    r_tab = jnp.arange(q_cap + 2, dtype=jnp.float32)
    return _rho_pow(r_tab, lam1_eta), _beta(r_tab, lam1_eta)


def recovery_catch_up_capped(u: Array, z: Array, q: Array, eta: float,
                             lam1: float, lam2: float, q_cap: int,
                             tables=None) -> Array:
    """`recovery_catch_up` specialized to a static bound q <= q_cap.

    Inside an inner epoch of M steps every staleness count is <= M, so
    the affine-phase factors rho^r and beta_r only ever need r in
    [0, q_cap + 1].  This variant tabulates both sequences once —
    computed by the *identical* `_rho_pow`/`_beta` formulas, so the
    result is bitwise equal to the uncapped version — and turns the
    ~12 per-coordinate transcendental passes (exp/expm1 in six affine
    evaluations plus the q0 closed form) into gathers from a
    (q_cap + 2)-entry table plus ONE log1p per coordinate.  On CPU this
    is ~3x faster where it matters most: the O(d) final catch-up that
    runs inside the same XLA computation as the inner scan.

    Exactness (tests/test_fused_inner.py): equal to `recovery_catch_up`
    and to the literal `sequential_catch_up` for all q <= q_cap.
    """
    rho_tab, beta_tab = (catch_up_tables(eta, lam1, q_cap)
                         if tables is None else tables)

    def affine(u0, s, r, z_):
        r = jnp.clip(r, 0, q_cap + 1)
        return (jnp.take(rho_tab, r) * u0
                - eta * (z_ + s * lam2) * jnp.take(beta_tab, r))

    q = jnp.asarray(q, jnp.int32)
    s0 = jnp.sign(u)
    # q0 capped at q_cap is exact: only a = min(q, q0) is consumed, and
    # q <= q_cap; the closed form + robustness loop are shared with the
    # uncapped path, evaluated through the tabulated affine
    q0 = _q0_branch_steps(u, jnp.where(s0 == 0, 1.0, s0), z, eta, lam1,
                          lam2, q_cap, affine=affine)
    q0 = jnp.where(s0 == 0, 0, q0)

    return _finish_catch_up(u, z, q, eta, lam1, lam2, q0, affine)


def sequential_catch_up(u: Array, z: Array, q: Array, eta: float,
                        lam1: float, lam2: float, max_steps: int) -> Array:
    """Literal reference: apply (*) step-by-step, masked per coordinate.

    O(max_steps * d); only used as the correctness oracle.
    """
    q = jnp.asarray(q, jnp.int32)

    def body(m, u_cur):
        u_next = _exact_step(u_cur, z, eta, lam1, lam2)
        return jnp.where(m < q, u_next, u_cur)

    return jax.lax.fori_loop(0, max_steps, body, u)


# ---------------------------------------------------------------------------
# Algorithm 2: lazy inner loop for linear models on block-sparse data.
# ---------------------------------------------------------------------------

def lazy_inner_loop(h_prime: Callable, reg_lam1: float, reg_lam2: float,
                    eta: float, u0: Array, w_anchor: Array, z: Array,
                    X_blocks: Array, y: Array, block_ids: Array,
                    idx: Array, block_size: int,
                    catch_up_fn: Optional[Callable] = None) -> Array:
    """M inner steps touching only the active feature blocks per sample.

    Data layout (produced by data/synthetic.make_block_sparse):
      X_blocks:  (n, nb_active, block_size)  values of the active blocks
      block_ids: (n, nb_active) int32        which feature block each is
      y:         (n,)
    The model dimension d = num_blocks * block_size.  Feature blocks not
    named in block_ids are exactly zero for that instance — for those
    coordinates the update degenerates to iteration (*), so we defer
    them and catch up lazily with `recovery_catch_up` (TPU-block
    adaptation of the paper's per-coordinate rule; exact, not an
    approximation).

    Returns u after M steps — bitwise the same trajectory as the dense
    inner loop restricted to linear models.
    """
    if catch_up_fn is None:
        catch_up_fn = functools.partial(recovery_catch_up, eta=eta,
                                        lam1=reg_lam1, lam2=reg_lam2)
    d = u0.shape[0]
    nb = d // block_size
    M = idx.shape[0]

    w_anchor_blocks = w_anchor.reshape(nb, block_size)

    def step(carry, ix):
        u, last = carry            # u: (d,), last: (nb,) int32 step stamps
        m = ix[0]
        s = ix[1]
        bids = block_ids[s]        # (nb_active,)
        xb = X_blocks[s]           # (nb_active, block_size)

        # 1. catch the active blocks up to step m
        u2d = u.reshape(nb, block_size)
        q_blocks = (m - last)[bids]                       # (nb_active,)
        u_active = catch_up_fn(u2d[bids], z.reshape(nb, block_size)[bids],
                               q_blocks[:, None])
        u2d = u2d.at[bids].set(u_active)

        # 2. the actual prox-SVRG step on the active coordinates, written
        #    in the paper's Algorithm-2 convention
        #      u <- S_{lam2 eta}((1 - lam1 eta) u - eta v),
        #    i.e. the L2 term is linearized into the multiplier (this is
        #    the convention Lemma 11's recovery formulas assume).
        dot_u = jnp.sum(u2d[bids] * xb)
        dot_w = jnp.sum(w_anchor_blocks[bids] * xb)
        coef = h_prime(dot_u, y[s]) - h_prime(dot_w, y[s])
        v_active = coef * xb + z.reshape(nb, block_size)[bids]
        u_step = soft_threshold(
            (1.0 - reg_lam1 * eta) * u2d[bids] - eta * v_active,
            reg_lam2 * eta)
        u2d = u2d.at[bids].set(u_step)
        last = last.at[bids].set(m + 1)
        return (u2d.reshape(-1), last), None

    steps = jnp.stack([jnp.arange(M, dtype=jnp.int32), idx], axis=1)
    (u, last), _ = jax.lax.scan(step, (u0, jnp.zeros((nb,), jnp.int32)), steps)

    # final global catch-up to step M
    u2d = u.reshape(nb, block_size)
    qf = (M - last)[:, None]
    u2d = catch_up_fn(u2d, z.reshape(nb, block_size), qf)
    return u2d.reshape(-1)


def dense_inner_loop_linear(h_prime: Callable, reg_lam1: float,
                            reg_lam2: float, eta: float, u0: Array,
                            w_anchor: Array, z: Array, X: Array, y: Array,
                            idx: Array) -> Array:
    """Dense oracle matching `lazy_inner_loop` (same prox convention)."""

    def step(u, s):
        xs = X[s]
        coef = h_prime(xs @ u, y[s]) - h_prime(xs @ w_anchor, y[s])
        v = coef * xs + z
        return soft_threshold((1.0 - reg_lam1 * eta) * u - eta * v,
                              reg_lam2 * eta), None

    u, _ = jax.lax.scan(step, u0, idx)
    return u
