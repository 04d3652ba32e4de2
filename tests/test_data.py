"""Synthetic datasets + block-sparse representation."""
import numpy as np

from repro.data.synthetic import (make_dataset, make_block_sparse,
                                  pad_features, make_sparse_classification)


def test_block_sparse_roundtrip():
    X, _, _ = make_sparse_classification(32, 200, density=0.05, seed=0)
    X = pad_features(X, 64)
    vals, bids = make_block_sparse(X, 64)
    # reconstruct dense from blocks
    n, d = X.shape
    rec = np.zeros_like(X)
    for i in range(n):
        for j, b in enumerate(bids[i]):
            rec[i, b * 64:(b + 1) * 64] += vals[i, j]
    np.testing.assert_allclose(rec, X, atol=1e-7)
    # padding ids are distinct within each row (no write collisions)
    for i in range(n):
        assert len(set(bids[i].tolist())) == len(bids[i])


def test_dataset_specs():
    X, y, w = make_dataset("rcv1", scale=0.02)
    assert X.shape[1] == 4096
    assert set(np.unique(y)).issubset({-1.0, 1.0})
    density = (X != 0).mean()
    assert density < 0.05
