"""Seeded power-law sparse logistic rows and their blocked-ELL device batch.

Copied from bench.py (`sparse_coo`, `sparse_batch`) with the module-level
``S_*`` constants turned into arguments, so the sizes come from the
configuration file. bench.py's originals are superseded (PERF.md, Open
questions).

One change: the sparsity PATTERN is drawn from a fixed seed and ``seed``
gives the order of the rows, the values, the planted weights and the
labels. The layout's array shapes (rows per ELL width bucket, columns per
occurrence bucket) follow from the pattern's counts alone, so every seed
runs the same compiled programs on the same amount of work — a seed that
changed the shapes would compile anew in every run and move the work.
The pattern is the slow draw (67M zipf samples, ~14 s), so it is kept in
``cache_dir`` and every later run of any seed reads it back.
"""
from __future__ import annotations

import os

import numpy as np

PATTERN_SEED = 20240924  # fixed: which columns each row touches


def pattern_columns(rows: int, features: int, nnz: int, zipf: float,
                    cache_dir: str) -> np.ndarray:
    """(rows, nnz) int32 column ids, zipf-distributed over the non-intercept
    columns: drawn once from `PATTERN_SEED`, then read from `cache_dir`."""
    path = os.path.join(
        cache_dir, f"pattern-{PATTERN_SEED}-{rows}x{nnz}-{features}-{zipf}.npy")
    if os.path.exists(path):
        return np.load(path)
    col = ((np.random.default_rng(PATTERN_SEED).zipf(
        zipf, size=(rows, nnz)).astype(np.int64) - 1)
        % (features - 1)).astype(np.int32)
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.save(f, col)
    os.replace(tmp, path)  # a run that is cut leaves no half-written file
    return col


def sparse_coo(seed: int, rows: int, features: int, nnz: int, zipf: float,
               hot_signal: int, cache_dir: str):
    """(indices (n, k+1), values (n, k+1), labels (n,)): the host COO of
    power-law logistic rows with a planted hot-end signal; the last slot of
    every row is the intercept column ``features - 1``."""
    n, k, d = rows, nnz, features
    rng = np.random.default_rng(seed)
    # the same rows in another order
    col = pattern_columns(n, d, k, zipf, cache_dir)[rng.permutation(n)]
    val = rng.normal(size=(n, k)).astype(np.float32)
    ind = np.concatenate([col, np.full((n, 1), d - 1)], axis=1).astype(
        np.int32)
    va = np.concatenate([val, np.ones((n, 1), np.float32)], axis=1)
    w_true = np.zeros(d, np.float32)
    hot = min(hot_signal, d - 1)
    w_true[:hot] = rng.normal(size=hot) / np.sqrt(np.arange(1, hot + 1))
    w_true[d - 1] = -0.2
    margin = np.einsum("nk,nk->n", va, w_true[ind])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return ind, va, y


def sparse_batch(ind, va, y, features: int, d_dense: int):
    """The device batch of a `sparse_coo` problem: blocked-ELL layout, hot
    block built ON the device from the compact hot COO in bf16, every other
    value leaf cast to bf16 on the host, one `device_put`."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.data.dataset import cast_features, make_batch
    from photon_tpu.data.matrix import SparseRows, to_blocked_ell

    H = to_blocked_ell(SparseRows(ind, va, features), d_dense,
                       device_dense_dtype=jnp.bfloat16)
    return jax.device_put(cast_features(make_batch(H, y)))
