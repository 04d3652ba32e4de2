"""Proximal gradient descent — eq. (2) of the paper.

Paper ref: the prox-GD iteration w <- prox_{eta R}(w - eta grad F(w))
that pSCOPE's Theorem 2 is benchmarked against; the distributed variant
all-reduces the gradient once per iteration.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.core.prox import Regularizer

Array = jax.Array


def pgd_history(obj, reg: Regularizer, X: Array, y: Array, w0: Array,
                iters: int = 100, record_every: int = 1,
                on_record=None) -> Tuple[Array, List[float]]:
    L = obj.lipschitz(X) + reg.lam1
    eta = 1.0 / L

    def smooth_loss(w, X, y):
        return obj.loss(w, X, y) + 0.5 * reg.lam1 * jnp.sum(w * w)

    reg_l1 = Regularizer(0.0, reg.lam2)
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

    w = w0
    emit(w)
    for i in range(iters):
        w = reg_l1.prox(w - eta * grad(w, X, y), eta)
        if (i + 1) % record_every == 0:
            emit(w)
    return w, hist
