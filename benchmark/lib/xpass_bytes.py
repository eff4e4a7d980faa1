"""Bytes one value-and-gradient evaluation must move over a blocked-ELL
layout, from the layout's own array shapes and dtypes.

The least traffic the algorithm allows: the forward pass (X·w) reads the hot
block and the ELL tail buckets (column ids and values) and the row
reassembly index; the transposed pass (Xᵀ·r) reads the hot block again and
the occurrence buckets (row ids and values), which are the tail stored a
second time in column order. Per lane it reads `w` once, writes the
gradient once, writes and reads back the (n,) margin and reads y, weights
and offsets once. Nothing is counted for solver state or temporaries.
"""
from __future__ import annotations

import numpy as np


def _nbytes(a) -> int:
    return int(np.prod(a.shape)) * int(np.dtype(a.dtype).itemsize)


def xpass_evaluation_bytes(X, lanes: int) -> dict:
    """{part: bytes} and their "total" for one evaluation over layout X
    (a `BlockedEllRows`) with ``lanes`` coefficient columns."""
    n, d = int(X.shape[0]), int(X.shape[1])
    f32 = 4
    parts = {
        "hot_block_twice": 2 * _nbytes(X.dense),
        "ell_tail_forward": sum(_nbytes(c) + _nbytes(v) for c, v in
                                zip(X.ell_pcols, X.ell_vals))
        + _nbytes(X.row_pos),
        "occ_tail_transposed": sum(_nbytes(r) + _nbytes(v) for r, v in
                                   zip(X.bucket_rows, X.bucket_vals)),
        "w_and_gradient": 2 * d * lanes * f32,
        "margin_write_read": 2 * n * lanes * f32,
        "labels_weights_offsets": 3 * n * f32,
    }
    parts["total"] = sum(parts.values())
    return parts
