"""Signed feature hashing (the hashing trick) for ingest-time projection.

avazu/kdd-class datasets carry feature spaces far past what a dense
iterate wants to hold; the standard fix (Weinberger et al. 2009, and
what Vowpal Wabbit does on exactly these datasets) is to project every
feature index j to ``h(j) mod 2^k`` and multiply its value by a sign
bit ``s(j) in {-1, +1}`` drawn from a second hash.  The sign trick
makes the hashed inner product an unbiased estimator of the original:

    E_h[<phi(x), phi(x')>] = <x, x'>

because colliding pairs contribute s(j)s(j') with zero mean (the
unbiasedness test in tests/test_datasets.py checks this over hash
seeds).  Collisions inside one vector just sum — identical to the
duplicate-column convention of `repro.data.sparse.CSRMatrix`, so
hashed chunks flow through the shard store unchanged.

The hash is a splitmix64 finalizer over (index, seed) — stateless and
vectorized, so determinism across runs/hosts is by construction.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):                      # mod-2^64 mixing
        z = x + _GOLD
        z = (z ^ (z >> np.uint64(30))) * _M1
        z = (z ^ (z >> np.uint64(27))) * _M2
        return z ^ (z >> np.uint64(31))


@dataclasses.dataclass(frozen=True)
class FeatureHasher:
    """Signed hash of feature indices into ``2^dim_log2`` buckets."""

    dim_log2: int
    seed: int = 0

    @property
    def dim(self) -> int:
        return 1 << self.dim_log2

    def __call__(self, cols: np.ndarray, vals: np.ndarray):
        """Map (cols, vals) -> (hashed cols, sign-flipped vals).

        Shapes are preserved; any integer col array works (flat ragged
        chunk arrays or padded (n, k) matrices alike).
        """
        with np.errstate(over="ignore"):                  # mod-2^64 keying
            key = np.uint64(self.seed) * _GOLD
            h = _splitmix64(np.asarray(cols, np.uint64) + key)
        new_cols = (h & np.uint64(self.dim - 1)).astype(np.int64)
        # an independent bit (top bit of the mix) drives the sign
        sign = 1.0 - 2.0 * (h >> np.uint64(63)).astype(np.float32)
        return new_cols, np.asarray(vals, np.float32) * sign
