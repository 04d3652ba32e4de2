"""The plain reference: L1 / elastic-net logistic regression on CSR rows,
solved by accelerated proximal gradient, and the comparison that decides
`correct`.

It imports nothing of the program and takes nothing the program made.
The solver is plain `jax.numpy` in the dtype it is given -- float32 for
the reference, bfloat16 for the control -- and works on the padded CSR
rows without densifying them.  Objective values that are compared are
taken in float64 on the host, so the comparison's own rounding stays
far below the gaps it reads.

Problem:  P(w) = mean_i log(1 + exp(-y_i x_i.w)) + lam1/2 |w|^2 + lam2 |w|_1.
"""
from __future__ import annotations

import numpy as np

NOT_FINITE = 1e300


def objective64(vals, cols, y, w, lam1: float, lam2: float) -> float:
    """P(w) in float64 on the host."""
    w = np.asarray(w, np.float64)
    margin = np.sum(np.asarray(vals, np.float64) * w[np.asarray(cols)],
                    axis=1)
    loss = np.mean(np.logaddexp(0.0, -np.asarray(y, np.float64) * margin))
    return float(loss + 0.5 * lam1 * np.dot(w, w)
                 + lam2 * np.sum(np.abs(w)))


def solve(vals, cols, y, d: int, lam1: float, lam2: float, *, dtype,
          iters: int, power_iters: int = 40):
    """Accelerated proximal gradient (FISTA) from w = 0 in `dtype`, with
    the momentum restarted whenever it points uphill (O'Donoghue and
    Candes 2015), so that it contracts at the strongly convex rate.

    The step is 1 / (L + lam1), with L = lambda_max(X^T X / n) / 4 taken
    by power iteration (times 1.05 for safety).

    Returns (w (d,) as float32 numpy, history of the solver's own P(w)
    in `dtype`, one value per iteration and the start, and the step).
    """
    import jax
    import jax.numpy as jnp

    n = vals.shape[0]
    with jax.default_matmul_precision("highest"):
        X = jnp.asarray(vals, dtype)
        C = jnp.asarray(cols)
        Y = jnp.asarray(y, dtype)
        flat_cols = C.reshape(-1)

        def margins(w):
            return jnp.sum(X * w[C], axis=1)

        def rmatvec(s):
            return jnp.zeros((d,), dtype).at[flat_cols].add(
                (X * s[:, None]).reshape(-1)) / n

        def value(w):
            loss = jnp.mean(jnp.logaddexp(jnp.zeros((), dtype),
                                          -Y * margins(w)))
            return (loss + 0.5 * lam1 * jnp.sum(w * w)
                    + lam2 * jnp.sum(jnp.abs(w)))

        @jax.jit
        def run(key):
            def power(_, v):
                u = rmatvec(margins(v))
                return u / jnp.linalg.norm(u.astype(jnp.float32)).astype(
                    dtype)
            v0 = jax.random.normal(key, (d,), jnp.float32).astype(dtype)
            v0 = v0 / jnp.linalg.norm(v0.astype(jnp.float32)).astype(dtype)
            v = jax.lax.fori_loop(0, power_iters, power, v0)
            lmax = jnp.dot(v.astype(jnp.float32),
                           rmatvec(margins(v)).astype(jnp.float32))
            eta = (1.0 / (1.05 * lmax / 4.0 + lam1)).astype(dtype)

            def step(carry, _):
                w, z, t = carry
                s = -Y * jax.nn.sigmoid(-Y * margins(z))
                u = z - eta * (rmatvec(s) + lam1 * z)
                w_new = (jnp.sign(u) * jnp.maximum(jnp.abs(u) - eta * lam2,
                                                   0)).astype(dtype)
                uphill = jnp.vdot((z - w_new).astype(jnp.float32),
                                  (w_new - w).astype(jnp.float32)) > 0
                t = jnp.where(uphill, 1.0, t)
                t_new = (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0
                z = (w_new + ((t - 1.0) / t_new) * (w_new - w)).astype(dtype)
                return (w_new, z, t_new), value(w_new)

            w0 = jnp.zeros((d,), dtype)
            (w, _, _), hist = jax.lax.scan(
                step, (w0, w0, jnp.float32(1.0)), None, length=iters)
            return w, jnp.concatenate([value(w0)[None], hist]), eta

        w, hist, eta = run(jax.random.PRNGKey(0))
        return (np.asarray(w.astype(jnp.float32)),
                np.asarray(hist.astype(jnp.float32), np.float64),
                float(eta))


def polish(vals, cols, y, w, lam1: float, lam2: float, eta: float,
           steps: int):
    """`steps` more proximal gradient steps from w, in float64 on the
    host, so that P* carries no float32 rounding of the solve."""
    X = np.asarray(vals, np.float64)
    C = np.asarray(cols)
    Y = np.asarray(y, np.float64)
    w = np.asarray(w, np.float64)
    for _ in range(steps):
        s = -Y / (1.0 + np.exp(Y * np.sum(X * w[C], axis=1)))
        g = np.bincount(C.ravel(), weights=(X * s[:, None]).ravel(),
                        minlength=w.shape[0]) / len(Y)
        u = w - eta * g
        w = (np.sign(u) * np.maximum(np.abs(u) - eta * lam2, 0.0)
             / (1.0 + eta * lam1))
    return w


def rel_gaps(values, p_star: float) -> np.ndarray:
    """(P(w_t) - P*) / P* for each recorded round."""
    return (np.asarray(values, np.float64) - p_star) / p_star


def rounds_to_gap(values, p_star: float, eps: float):
    """Rounds until the relative gap first reaches `eps`, interpolated
    in log(gap) between the two recorded rounds that bracket it (linear
    convergence makes log(gap) nearly linear in rounds).  None where the
    history never reaches `eps`, or is not finite."""
    g = rel_gaps(values, p_star)
    if not np.all(np.isfinite(g)):
        return None
    hit = np.flatnonzero(g <= eps)
    if hit.size == 0:
        return None
    t = int(hit[0])
    if t == 0:
        return 0.0
    lo, hi = np.log(g[t - 1]), np.log(max(g[t], 1e-300))
    return float(t - 1 + (lo - np.log(eps)) / (lo - hi))


def compare(solves, rows, p_star: float, lam1: float, lam2: float) -> dict:
    """The numbers that decide `correct`, each the worst over `solves`.

    solves  list of (values, w_final): the solve's recorded history of
            P(w_t) and its final iterate
    rows    (vals, cols, y) of the rows the solve was given

    final_gap  (P(w_T) - P*) / P*, P(w_T) taken in float64 here
    value_err  |recorded P(w) - P(w) taken here| / P*, at the start
               (w = 0) and at the final iterate: the history the
               rounds-to-gap metric reads has to be the objective of
               the iterates the solve made
    """
    vals, cols, y = rows
    final_gap = value_err = -np.inf
    for values, w in solves:
        w = np.asarray(w)
        if not (np.all(np.isfinite(values)) and np.all(np.isfinite(w))):
            # a non-finite solve fails every limit; JSON has no infinity
            return {"final_gap": NOT_FINITE, "value_err": NOT_FINITE}
        p_final = objective64(vals, cols, y, w, lam1, lam2)
        p_zero = objective64(vals, cols, y, np.zeros(w.shape[0]), lam1,
                             lam2)
        final_gap = max(final_gap, (p_final - p_star) / p_star)
        value_err = max(value_err,
                        abs(float(values[-1]) - p_final) / p_star,
                        abs(float(values[0]) - p_zero) / p_star)
    return {"final_gap": float(final_gap), "value_err": float(value_err)}
