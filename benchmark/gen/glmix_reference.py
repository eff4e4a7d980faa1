"""Plain references for the GLMix cells: one entity's L2 logistic problem
solved by Newton steps, and the whole model's margins, loss and AUC — numpy
on the host, no vmap, no buckets, no projection tables, no code of the
program under test.

Float64, not the float32 `jax.numpy` a model reference would use: every
entity has its own (rows, touched columns) shape, so a jitted reference
would compile once per sampled entity, and float64 on the host needs no
``default_matmul_precision`` to be exact.
"""
from __future__ import annotations

import numpy as np

GRAD_TOL = 1e-6     # Newton stops at this gradient norm
MAX_STEPS = 50


def entity_problem(ind: np.ndarray, val: np.ndarray) -> tuple:
    """One entity's rows (padded-COO ``ind`` / ``val``, (r, k)) as (the
    sorted feature ids its nonzeros touch, the dense (r, c) float64 matrix
    over them); a feature a row names twice accumulates."""
    val = np.asarray(val, np.float64)
    cols = np.unique(ind[val != 0.0])
    X = np.zeros((ind.shape[0], cols.shape[0]), np.float64)
    np.add.at(X, (np.arange(ind.shape[0])[:, None],
                  np.searchsorted(cols, ind)), val)
    return cols, X


def bf16(a) -> np.ndarray:
    """``a`` rounded to bfloat16 and back: the operand of a matmul at the
    precision BELOW the configuration's float32 (on the TPU, what a float32
    matmul at default precision multiplies)."""
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _exact(a) -> np.ndarray:
    return a


def objective(X, y, offsets, w, l2: float, rd=_exact) -> float:
    """Σ log(1 + e^z) − y·z + ½·l2·‖w‖² at z = offsets + X·w (the
    intercept is regularized with the rest, as the configuration's
    coordinates are). ``rd`` rounds the operands of the product X·w
    (`bf16`: the lower-precision control)."""
    z = offsets + rd(X) @ rd(w)
    return float(np.sum(np.logaddexp(0.0, z) - y * z) + 0.5 * l2 * (w @ w))


def gradient(X, y, offsets, w, l2: float, rd=_exact) -> np.ndarray:
    z = offsets + rd(X) @ rd(w)
    return rd(X).T @ rd(1.0 / (1.0 + np.exp(-z)) - y) + l2 * w


def newton(X, y, offsets, l2: float, rd=_exact) -> tuple:
    """(w*, objective at w*, steps): damped Newton from w = 0 to a
    gradient norm of `GRAD_TOL`. The problem is strongly convex (l2 > 0),
    so a full step is halved only where it fails to descend. With rounded
    products (``rd``) the objective is flat below the rounding's
    resolution: the solve then ends where no step descends any more, as a
    solver at that precision would, instead of raising."""
    w = np.zeros(X.shape[1])
    f = objective(X, y, offsets, w, l2, rd)
    for step in range(MAX_STEPS):
        g = gradient(X, y, offsets, w, l2, rd)
        if np.linalg.norm(g) <= GRAD_TOL:
            return w, f, step
        p = 1.0 / (1.0 + np.exp(-(offsets + rd(X) @ rd(w))))
        A = X * np.sqrt(p * (1.0 - p))[:, None]  # the Hessian is A'A + l2·I
        if A.shape[0] < A.shape[1]:  # fewer rows than columns: solve in rows
            d = (g - A.T @ np.linalg.solve(
                A @ A.T + l2 * np.eye(A.shape[0]), A @ g)) / l2
        else:
            d = np.linalg.solve(A.T @ A + l2 * np.eye(A.shape[1]), g)
        t = 1.0
        while True:
            f_new = objective(X, y, offsets, w - t * d, l2, rd)
            if f_new <= f or t < 1e-8:
                break
            t *= 0.5
        if rd is not _exact and f_new >= f:
            return w, f, step
        w, f = w - t * d, f_new
    if rd is not _exact:
        return w, f, MAX_STEPS
    raise RuntimeError(f"Newton did not reach {GRAD_TOL} in {MAX_STEPS} "
                       f"steps (gradient norm {np.linalg.norm(g):.3g})")


def table_rows(train_ids, ids) -> np.ndarray:
    """The coefficient table's row of each of ``ids``: a table holds one row
    per entity SEEN in training, in the order of the sorted ids; an entity
    training never saw gets the row count, which scores 0."""
    keys = np.unique(train_ids)
    at = np.minimum(np.searchsorted(keys, ids), len(keys) - 1)
    return np.where(keys[at] == ids, at, len(keys))


def sparse_margins(ind, val, table, ids, rd=_exact) -> np.ndarray:
    """(n,) float64 x_i · table[ids_i] of padded-COO rows; id == the
    table's row count (an entity unseen in training) scores 0. ``rd``
    rounds both factors of every product."""
    seen = ids < table.shape[0]
    row = np.where(seen, ids, 0)
    out = np.zeros(ind.shape[0], np.float64)
    for j in range(ind.shape[1]):  # a slot at a time: (n,) temporaries
        out += (rd(val[:, j].astype(np.float64))
                * rd(table[row, ind[:, j]].astype(np.float64)))
    return out * seen


def log_loss(margin, y) -> float:
    """Σ log(1 + e^z) − y·z, float64."""
    z = np.asarray(margin, np.float64)
    return float(np.sum(np.logaddexp(0.0, z) - np.asarray(y, np.float64) * z))
