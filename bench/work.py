"""The inner epoch's algorithmic bytes, counted from the problem.

The count follows what pSCOPE's inner epoch has to read and write,
whatever implements it: for every sampled row its nonzeros (value and
column), its label and the anchor's margin term, and at every column it
touches a read and a write of the iterate and a read of the anchor
gradient; once per epoch the final catch-up of all d coordinates, which
reads the iterate and the anchor gradient and writes the iterate.  No
term depends on how an implementation lays out its plan, pads its slots
or tiles the iterate, so the count does not move when the
implementation changes.  All values are 4-byte floats or indices.
"""
from __future__ import annotations

ITEM = 4        # bytes of one float32 value or int32 index


def sample_bytes(nnz: int) -> int:
    """Bytes one sampled row moves: CSR value + column per nonzero,
    label and anchor margin, iterate read + write and anchor-gradient
    read per touched column."""
    return nnz * 2 * ITEM + 2 * ITEM + nnz * 3 * ITEM


def epoch_bytes(steps: int, batch: int, nnz: int, d: int) -> int:
    """One worker's inner epoch: `steps` microbatches of `batch` rows,
    each row with `nnz` nonzeros, and the final catch-up over d
    coordinates (iterate read, anchor gradient read, iterate write)."""
    return steps * batch * sample_bytes(nnz) + 3 * d * ITEM


def least_seconds(nbytes: float, peak: dict) -> float:
    """The least time the chip needs to move `nbytes` at its HBM peak.
    The epoch does a few operations per byte, far below the ridge point
    of any chip in the table, so it is bound by memory."""
    return nbytes / peak["hbm_bytes_per_s"]
