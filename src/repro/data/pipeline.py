"""Worker-major padded-CSR shards for the sparse lazy-prox engine.

`csr_partition` builds the `core.pscope` lazy inner loop's data layout
from a flat `CSRMatrix` + a (p, n_k) partition index array.  The
streaming ingestion subsystem (`repro.datasets`) persists exactly this
layout on disk, so `ShardStore.csr_p` is a drop-in (zero-copy)
producer for every `csr_partition` consumer; see docs/data.md.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.data.sparse import CSRMatrix, shard_rows


def csr_partition(csr: CSRMatrix, y, idx) -> Tuple[CSRMatrix, jax.Array]:
    """Worker-major CSR shards: idx (p, n_k) -> ((p, n_k, k) CSR, (p, n_k) y).

    The sparse analogue of `repro.partition.stack_partition`; the
    result feeds `core.pscope.run` with `inner_path="lazy"` directly,
    or — with leading axis sharded over a mesh axis — the distributed
    shard_map outer step.  Registry code should prefer
    `Partition.csr_p`, which caches this layout per partition instead
    of rebuilding it per solver run.
    """
    idx = np.asarray(idx)
    return shard_rows(csr, idx), jnp.asarray(y)[idx]

