"""`gen/reference.py`'s float64 logistic objective over rows that do not
fit one (n, k) float64 gather: ONE pass in row blocks over the host COO
alone, a partial sum a shard, so that the whole objective and the objective
of a subset of the shards (the lost-shard control) come from it. Shares no
code with the program under test.

Beside the losses the pass gives the two readings a summed loss cannot: the
per-row MARGINS and the GRADIENT, at points where every cast the X pass
makes on its operands is exact — the margins at `probe_coefficients(w)`
(the returned ``w`` rounded to the storage type: the hot block's matmul
casts the coefficients to it, and products of two such numbers are exact
in f32), the gradient at w = 0 (the residual σ(0) − y is ±½, which the
transposed hot matmul's cast leaves alone). There a program that stores
the configuration's type and accumulates in f32 differs from float64 by
f32 summation noise, and one precision step lost anywhere — values stored
or drawn otherwise, products or sums kept in the storage type — shows at
that step's roundoff, four orders above it.
"""
from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.gen.reference import stored

BLOCK_ROWS = 1 << 20  # a (2^20, 33) float64 gather is 277 MB
# blocks in flight: each holds some 3 GB at 33 entries a row and 10M columns
THREADS = min(8, os.cpu_count() or 1)


def merged(ind, va, is_hot):
    """(indices, values float64) of a block of rows as a blocked-ELL layout
    keeps them, columns ascending within the row: a row's REPEATS of a hot
    column summed into the first of them and the others left at 0 (the
    hot block holds ONE number a (row, column), the sum rounded once — a
    zipf draw repeats column 0 some ten times in 33); a cold column's
    repeats stay entries of their own, as the tail stores them."""
    order = np.argsort(ind, axis=1, kind="stable")
    cols = np.take_along_axis(ind, order, axis=1)
    vals = np.take_along_axis(np.asarray(va, np.float64), order, axis=1)
    first = ~is_hot[cols]
    first[:, 0] = True
    first[:, 1:] |= cols[:, 1:] != cols[:, :-1]
    starts = np.flatnonzero(first)
    out = np.zeros(vals.size)
    out[starts] = np.add.reduceat(vals.reshape(-1), starts)
    return cols, out.reshape(vals.shape)


def probe_coefficients(w, storage_dtype) -> np.ndarray:
    """``w`` rounded to the storage type, float64."""
    return stored(np.asarray(w, np.float32), storage_dtype)


def shard_pass(ind, va, y, w, n_shards: int, storage_dtype,
               hot_columns) -> dict:
    """{"stored": r, "unrounded": r, "lower": r}, each r a dict of float64
    "loss" (S,): Σ log(1 + e^z) − y·z over each shard's contiguous rows at
    coefficients ``w``; "margins" (n,): z at `probe_coefficients(w)`;
    "grad0" (S, d): Σ (½ − y)·x over each shard's rows, the data gradient
    at w = 0; "grad0_scale" (d,): ½·(Σ x²)^½ over all rows, what a column's
    gradient at w = 0 would be worth with labels drawn by a coin — the
    yardstick of `gradient_error`. "stored": the values as the device
    stores them (`merged` over the layout's ``hot_columns``, rounded to
    ``storage_dtype``) — the reference. "unrounded": the values as drawn
    (a control in the reference's place). "lower": the stored values
    computed one precision step down, in the GENTLEST way — every product
    and every result rounded to ``storage_dtype``, every sum exact (a
    control in the program's place; its "loss" is (S,) parts whose SUM is
    to be rounded, its "grad0" (d,)). The row blocks go to a few threads
    (numpy holds no lock in these passes); each adds into its shard's sums
    under the shard's lock."""
    n, d = ind.shape[0], int(np.asarray(w).shape[0])
    if n % n_shards != 0:
        raise ValueError(f"{n} rows do not divide {n_shards} shards")
    n_loc = n // n_shards
    w64 = np.asarray(w, np.float64)
    is_hot = np.zeros(d, bool)
    is_hot[np.asarray(hot_columns)] = True

    def down(a):  # by way of f32: ml_dtypes casts float64 ten times slower
        return np.asarray(a, np.float32).astype(storage_dtype).astype(
            np.float32).astype(np.float64)

    out = {name: {"loss": np.zeros(n_shards), "margins": np.empty(n),
                  "grad0": np.zeros((n_shards, d))}
           for name in ("stored", "unrounded")}
    lower_margins, squares = np.empty(n), np.zeros((n_shards, d))
    locks = [threading.Lock() for _ in range(n_shards)]

    def block(s, rows):
        cols, exact = merged(ind[rows], va[rows], is_hot)
        wg = w64[cols]
        wq = down(wg)  # the gather of the rounded w
        cols = cols.reshape(-1)
        y64 = np.asarray(y[rows], np.float64)
        for name, values in (("stored", down(exact)), ("unrounded", exact)):
            r = out[name]
            z = np.einsum("nk,nk->n", values, wg)
            loss = float(np.sum(np.logaddexp(0.0, z) - y64 * z))
            r["margins"][rows] = np.einsum("nk,nk->n", values, wq)
            grad0 = np.bincount(
                cols, weights=((0.5 - y64)[:, None] * values).reshape(-1),
                minlength=d)
            if name == "stored":
                lower_margins[rows] = down(np.sum(down(values * wq), axis=1))
                square = np.bincount(
                    cols, weights=np.square(values).reshape(-1), minlength=d)
            with locks[s]:
                r["loss"][s] += loss
                r["grad0"][s] += grad0
                if name == "stored":
                    squares[s] += square

    blocks = [(s, slice(r0, min(r0 + BLOCK_ROWS, (s + 1) * n_loc)))
              for s in range(n_shards)
              for r0 in range(s * n_loc, (s + 1) * n_loc, BLOCK_ROWS)]
    with ThreadPoolExecutor(max_workers=min(THREADS, len(blocks))) as pool:
        for done in [pool.submit(block, *b) for b in blocks]:
            done.result()
    kept = out["stored"]
    # ±½ · x is exact in the storage type: only the result is rounded
    out["lower"] = {"loss": kept["loss"], "margins": lower_margins,
                    "grad0": down(np.sum(kept["grad0"], axis=0))}
    for r in out.values():
        r["grad0_scale"] = 0.5 * np.sqrt(np.sum(squares, axis=0))
    return out


def gradient_error(grad, reference_grad, scale) -> float:
    """Root mean square, over the columns that have entries, of a
    gradient's distance from the reference's in units of the column's
    ``scale``. A norm over the whole vector would be the intercept's and
    the most popular columns' alone — sums of n like-signed terms, where
    every other column's is a random walk of its few — and would shrink a
    rounding of the values by n^-½; by the column it reads the roundoff
    itself, whatever n."""
    has = scale > 0
    return float(np.sqrt(np.mean(np.square(
        (np.asarray(grad)[has] - reference_grad[has]) / scale[has]))))


def objective(data_losses, w, l2: float) -> float:
    """The data losses of the shards that are counted + ½·l2·‖w‖²."""
    w64 = np.asarray(w, np.float64)
    return float(np.sum(data_losses) + 0.5 * l2 * np.dot(w64, w64))
