"""`gen/sparse.py`'s power-law logistic rows for a ROW-SHARDED layout, and
their sharded blocked-ELL device batch.

The pattern is `sparse.pattern_columns`' (fixed seed, cached under
``shared``) at the mesh's row count. One change to the draw: ``seed``
orders the rows WITHIN each shard's contiguous range only. A sharded
layout's array shapes follow from each shard's OWN counts (rows per ELL
width are the most any shard has; a column's occurrence bucket comes from
its max-local count), so a permutation of all rows — `sparse.sparse_coo`'s
— would move rows between shards, change the shapes with the seed and
compile anew in every run. Within a shard the order is free: every shard
keeps its set of rows, and every seed gives the same leaf shapes. Values,
planted weights and labels are drawn from ``seed`` with `sparse_coo`'s
distributions, in its order (the values as f32 draws).
"""
from __future__ import annotations

import numpy as np

from benchmark.gen.sparse import pattern_columns


def shard_row_order(rng, rows: int, n_shards: int) -> np.ndarray:
    """(rows,) row ids: a permutation that keeps every row inside its
    shard's contiguous range [s·rows/S, (s+1)·rows/S)."""
    if rows % n_shards != 0:
        raise ValueError(f"{rows} rows do not divide {n_shards} shards")
    n_loc = rows // n_shards
    return np.concatenate([s * n_loc + rng.permutation(n_loc)
                           for s in range(n_shards)])


NUDGE = np.float32(2.0 ** -24)


def never_zero(values: np.ndarray) -> np.ndarray:
    """``values`` with every exact 0.0 replaced by 2^-24, in place. An f32
    normal draw is exactly zero once in 2^23 (some forty of a run's 268M),
    and a zero is no entry to the layout builders: one in a tail slot takes
    a nonzero off its row and its column, which now and then moves a row to
    a narrower ELL bucket or a column to a smaller occurrence bucket — the
    shapes, and so the compiled solve, would change with the seed (PR 31's
    first chip runs: three of six seeds compiled anew)."""
    values[values == 0.0] = NUDGE
    return values


def sharded_coo(seed: int, rows: int, features: int, nnz: int, zipf: float,
                hot_signal: int, n_shards: int, cache_dir: str):
    """(indices (n, k+1) int32, values (n, k+1) f32, labels (n,) f32): the
    host COO of `sparse.sparse_coo`, its rows reordered inside each of the
    ``n_shards`` contiguous ranges only."""
    n, k, d = rows, nnz, features
    rng = np.random.default_rng(seed)
    col = pattern_columns(n, d, k, zipf, cache_dir)[
        shard_row_order(rng, n, n_shards)]
    ind = np.empty((n, k + 1), np.int32)
    ind[:, :k] = col
    ind[:, k] = d - 1
    del col
    va = np.empty((n, k + 1), np.float32)
    # drawn in f32 (268M float64 draws are a third of a minute of set-up
    # on four held chips); the distribution is `sparse_coo`'s
    va[:, :k] = never_zero(rng.standard_normal(size=(n, k),
                                               dtype=np.float32))
    va[:, k] = 1.0
    w_true = np.zeros(d, np.float32)
    hot = min(hot_signal, d - 1)
    w_true[:hot] = rng.normal(size=hot) / np.sqrt(np.arange(1, hot + 1))
    w_true[d - 1] = -0.2
    margin = np.einsum("nk,nk->n", va, w_true[ind])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return ind, va, y


def sharded_batch(ind, va, y, features: int, d_dense: int, mesh):
    """The device batch of a `sharded_coo` problem, one shard a device of
    ``mesh``: sharded blocked-ELL layout, the hot block built in bf16 ON
    the device that keeps each shard's rows, every other value leaf cast
    to bf16 on the host, then placed as the sharded solves read it."""
    import jax.numpy as jnp

    from photon_tpu.data.dataset import (cast_features, make_batch,
                                         shard_blocked_ell_batch)
    from photon_tpu.data.matrix import SparseRows
    from photon_tpu.models.training import place_sharded_batch

    host = cast_features(shard_blocked_ell_batch(
        make_batch(SparseRows(ind, va, features), y),
        int(mesh.devices.size), d_dense=d_dense,
        device_dense_dtype=jnp.bfloat16, mesh=mesh))
    return place_sharded_batch(host, mesh)
