"""FISTA (Beck & Teboulle 2009) for composite minimization.

Paper ref: Section 7.1 baseline "FISTA"; the distributed variant
computes the gradient distributively (one all-reduce per iteration),
which is mathematically identical to this serial iteration.  Also used
as the inner solver for the local-objective minimizations of eq. (6) in
core/partition.py.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.prox import Regularizer

Array = jax.Array


def fista(smooth_loss: Callable[[Array], Array], reg: Regularizer,
          w0: Array, L: float, iters: int = 200) -> Array:
    """argmin smooth_loss(w) + reg(w); L = smoothness constant."""
    eta = 1.0 / L
    grad = jax.grad(smooth_loss)

    def body(_, carry):
        w, v, t = carry
        g = grad(v)
        w_next = reg.prox(v - eta * g, eta)
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        v_next = w_next + ((t - 1.0) / t_next) * (w_next - w)
        return (w_next, v_next, t_next)

    w, _, _ = jax.lax.fori_loop(0, iters, body,
                                (w0, w0, jnp.asarray(1.0, w0.dtype)))
    return w


def fista_history(obj, reg: Regularizer, X: Array, y: Array, w0: Array,
                  iters: int = 100, record_every: int = 1,
                  on_record=None) -> Tuple[Array, List[float]]:
    """FISTA with objective history (one entry per iteration block).

    `on_record(w, value)` fires at every history append (streaming hook
    for the `core.solvers.Trace` recorder).
    """
    L = obj.lipschitz(X) + reg.lam1

    def smooth_loss(w, X, y):
        return obj.loss(w, X, y) + 0.5 * reg.lam1 * jnp.sum(w * w)

    reg_l1 = Regularizer(0.0, reg.lam2)   # L2 handled smoothly above
    eta = 1.0 / L
    # X and y are arguments, not closed-over constants: a jitted closure
    # embeds the whole design matrix in the program as a literal
    grad = jax.jit(jax.grad(smooth_loss))
    obj_val = jax.jit(lambda w, X, y: obj.loss(w, X, y) + reg.value(w))

    hist: List[float] = []

    def emit(w):
        v = float(obj_val(w, X, y))
        hist.append(v)
        if on_record is not None:
            on_record(w, v)

    w, v, t = w0, w0, 1.0
    emit(w)
    for i in range(iters):
        g = grad(v, X, y)
        w_next = reg_l1.prox(v - eta * g, eta)
        t_next = 0.5 * (1.0 + (1.0 + 4.0 * t * t) ** 0.5)
        v = w_next + ((t - 1.0) / t_next) * (w_next - w)
        w, t = w_next, t_next
        if (i + 1) % record_every == 0:
            emit(w)
    return w, hist
