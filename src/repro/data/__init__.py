from repro.data.synthetic import (make_sparse_classification,
                                  make_sparse_regression, DATASET_SPECS,
                                  make_dataset, make_csr_dataset,
                                  make_block_sparse)
from repro.data.sparse import (CSRMatrix, dense_to_csr, csr_to_dense,
                               shard_rows, make_csr_classification,
                               make_csr_regression)
from repro.data.pipeline import csr_partition

__all__ = [
    "make_sparse_classification", "make_sparse_regression", "DATASET_SPECS",
    "make_dataset", "make_csr_dataset", "make_block_sparse",
    "CSRMatrix", "dense_to_csr", "csr_to_dense", "shard_rows",
    "make_csr_classification", "make_csr_regression", "csr_partition",
]
