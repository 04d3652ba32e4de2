"""Gradient compression for the pSCOPE anchor-gradient all-reduce.

Top-k sparsification with error feedback (Stich et al. 2018; Lin et al.
2018 DGC): each round only the `ratio` largest-magnitude entries of
(gradient + residual) are communicated; the remainder is fed back next
round.  pSCOPE communicates the anchor gradient once per OUTER round
(already ~M x fewer bytes than per-step DP); compression stacks
multiplicatively on top — at ratio=0.01 the cross-pod bytes per round
drop ~100x (the z all-reduce is the only cross-pod traffic).

The dense mask-based form below is what lowers under jit; on a
real deployment the masked tensor is sent as (indices, values) pairs —
bytes accounting in benchmarks uses 2 * ratio * size (values + indices).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


def topk_compress(tree, ef_tree, ratio: float) -> Tuple[Any, Any]:
    """Returns (sparse_tree, new_error_feedback)."""

    def comp(g, ef):
        acc = g + ef
        k = max(1, int(acc.size * ratio))
        thresh = jax.lax.top_k(jnp.abs(acc).reshape(-1), k)[0][-1]
        mask = (jnp.abs(acc) >= thresh).astype(acc.dtype)
        sent = acc * mask
        return sent, acc - sent

    out = jax.tree_util.tree_map(comp, tree, ef_tree)
    sent = jax.tree_util.tree_map(lambda o: o[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    ef = jax.tree_util.tree_map(lambda o: o[1], out,
                                is_leaf=lambda x: isinstance(x, tuple))
    return sent, ef


def topk_decompress_add(base_tree, sparse_tree):
    return jax.tree_util.tree_map(lambda b, s: b + s, base_tree, sparse_tree)


def compressed_bytes(tree, ratio: float) -> int:
    """Wire bytes of the (indices, values) encoding."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        k = max(1, int(leaf.size * ratio))
        total += k * (4 + leaf.dtype.itemsize)
    return total
