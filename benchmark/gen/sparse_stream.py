"""`gen/sparse_mesh.py`'s rows for a HOST chunk ladder streamed through one
chip, and their blocked-ELL `ChunkedBatch`.

The rows are `sparse_mesh.sharded_coo`'s as it stands, a chunk where it
has a shard: the fixed pattern at the ladder's row count, ``seed``
ordering the rows WITHIN each chunk's contiguous range only and drawing
values (never zero), planted weights and labels. A chunk ladder's array
shapes follow from each chunk's OWN counts exactly as a sharded layout's do
from each shard's (one builder, `shard_blocked_ell` with S = n_chunks), so
every seed has the same common padded shapes and compiles nothing anew —
and at four chunks of 2,097,152 rows they are the mesh cell's shard shapes.
"""
from __future__ import annotations

from benchmark.gen.sparse_mesh import sharded_coo


def chunked_coo(seed: int, rows: int, features: int, nnz: int, zipf: float,
                hot_signal: int, n_chunks: int, cache_dir: str):
    """(indices (n, k+1) int32, values (n, k+1) f32, labels (n,) f32)."""
    return sharded_coo(seed, rows=rows, features=features, nnz=nnz,
                       zipf=zipf, hot_signal=hot_signal, n_shards=n_chunks,
                       cache_dir=cache_dir)


def chunked_batch(ind, va, y, features: int, d_dense: int, chunk_rows: int,
                  feature_dtype):
    """The host `ChunkedBatch` of a `chunked_coo` problem: one blocked-ELL
    chunk ladder for ONE device a chunk, every value leaf stored as
    ``feature_dtype``."""
    from photon_tpu.data.dataset import chunk_blocked_ell, make_batch
    from photon_tpu.data.matrix import SparseRows

    return chunk_blocked_ell(
        make_batch(SparseRows(ind, va, features), y), chunk_rows,
        d_dense=d_dense, feature_dtype=feature_dtype, n_shards=1)
