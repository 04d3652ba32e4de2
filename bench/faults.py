"""Faults planted under the timed path, to show that the check which
decides `correct` fails them.

Each fault takes `patch(obj, name, value)`, which replaces an attribute:
pytest's `monkeypatch.setattr` in the tests, the plain `setattr` in
`bench/calibrate.py --fault`, which reads a fault at a cell's own size on
the chip.  Plant a fault before the first solve: the program keeps its
traced functions.
"""
from __future__ import annotations

import numpy as np


def state_unchanged(patch):
    """Every outer round returns the state it was given."""
    from repro.core import pscope
    patch(pscope, "_outer_step_lazy_core",
          lambda obj, reg, cfg, state, *a, **k: state._replace(t=state.t + 1))


def half_batch(patch):
    """The anchor gradient taken as the mean over half of each shard's
    rows."""
    from repro.core import svrg
    real = svrg.sparse_linear_model_full_gradient

    def half(h_prime, w, vals, cols, y, d):
        n = vals.shape[0] // 2
        return real(h_prime, w, vals[:n], cols[:n], y[:n], d)
    patch(svrg, "sparse_linear_model_full_gradient", half)


def answer_altered(patch):
    """The largest coordinate of the solve's answer negated where it is
    produced."""
    from repro.core import pscope
    real = pscope.run_scanned

    def altered(*a, **k):
        w, *rest = real(*a, **k)
        w = np.array(w)
        i = int(np.argmax(np.abs(w)))
        w[i] = -w[i]
        return (w, *rest)
    patch(pscope, "run_scanned", altered)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  answer_altered)}
