"""The benchmark's one generator of inputs: rows, labels and the
row-to-worker assignment, all made from the run's seed.

A configuration file gives the data's shape (rows, features, nonzeros
per row) and the label model; a workload file gives the assignment
rule.  Nothing here imports the program: the harness hands what this
module makes to the program as plain arrays.
"""
from __future__ import annotations

import numpy as np

# streams of one seed, so that the data and the assignment never share
# draws (the same seed always gives the same rows and the same split)
_DATA, _ASSIGN = 0, 1


def _zipf_columns(n: int, d: int, k: int, exponent: float, rng):
    """(n, k) column indices, distinct within a row, each draw taking the
    column of popularity rank r with weight r^-exponent; a draw that
    repeats a column of its row is drawn again.  Ranks map to column
    indices by a permutation from the seed, as a dataset's feature ids
    carry no order of popularity."""
    cdf = np.cumsum(1.0 / np.arange(1, d + 1) ** exponent)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.random((n, k))).astype(np.int32)
    while True:
        order = np.argsort(ranks, axis=1, kind="stable")
        srt = np.take_along_axis(ranks, order, axis=1)
        again = np.zeros(srt.shape, bool)
        again[:, 1:] = srt[:, 1:] == srt[:, :-1]
        if not again.any():
            break
        np.put_along_axis(again, order, again.copy(), axis=1)
        ranks[again] = np.searchsorted(cdf, rng.random(int(again.sum())))
    return rng.permutation(d).astype(np.int32)[ranks]


def make_rows(cfg: dict, seed: int):
    """TF-IDF rows of a text collection with +-1 labels from a sparse
    separator.

    Columns follow Zipf's law of term frequency (`zipf_exponent`),
    distinct within a row.  Values are rcv1's "ltc" weights: (1 + ln tf)
    ln(n / df), with the term count tf geometric (`tf_geometric_p`) and
    df the column's document frequency in these rows, each row scaled to
    unit norm.  A separator with `support_frac` of the features set to
    2 N(0, 1) gives the labels by its sign, and a `label_noise` share of
    them is flipped.

    Returns (vals (n, k) f32, cols (n, k) i32, y (n,) f32).
    """
    rng = np.random.default_rng([seed, _DATA])
    n, d, k = cfg["rows"], cfg["features"], cfg["nnz_per_row"]
    cols = _zipf_columns(n, d, k, cfg["zipf_exponent"], rng)
    tf = rng.geometric(cfg["tf_geometric_p"], size=(n, k))
    df = np.bincount(cols.ravel(), minlength=d)
    idf = np.log(n / np.maximum(df, 1))
    vals = ((1.0 + np.log(tf)) * idf[cols]).astype(np.float32)
    vals /= np.maximum(np.linalg.norm(vals, axis=1, keepdims=True), 1e-12)
    w_true = np.zeros(d, np.float32)
    support = rng.choice(d, size=max(1, int(d * cfg["support_frac"])),
                         replace=False)
    w_true[support] = 2.0 * rng.standard_normal(len(support),
                                                dtype=np.float32)
    margin = np.sum(vals * w_true[cols], axis=1)
    y = np.sign(margin + 1e-9).astype(np.float32)
    y[rng.random(n) < cfg["label_noise"]] *= -1.0
    return vals, cols, y


def _uniform(y: np.ndarray, p: int, rng) -> np.ndarray:
    """pi_1 of Section 7.4: a uniform random split into p equal shards;
    the n mod p rows left over are dropped."""
    n_k = len(y) // p
    return rng.permutation(len(y))[: n_k * p].reshape(p, n_k)


RULES = {"uniform": _uniform}


def assign(y: np.ndarray, p: int, rule: dict, seed: int) -> np.ndarray:
    """(p, n_k) row indices for the workers, by the workload's rule,
    such as {"rule": "uniform"}."""
    params = dict(rule)
    name = params.pop("rule")
    if name not in RULES:
        raise ValueError(f"unknown assignment rule {name!r}; "
                         f"have {sorted(RULES)}")
    rng = np.random.default_rng([seed, _ASSIGN])
    return RULES[name](np.asarray(y), p, rng, **params).astype(np.int64)
