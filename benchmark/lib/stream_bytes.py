"""Bytes a streamed solve must hand the chip: what ONE chunk of a host
chunk ladder is on the wire, and what a pass over the ladder moves.

A chunk crosses the host link whole, every pass: its hot block, its ELL
and occurrence buckets (ids and values), its `row_pos`, the ladder's two
(d,) permutation vectors (a leaf of every one-device blocked-ELL chunk:
`BlockedEllRows.perm_cols` / `inv_perm`) and its rows' labels, weights and
offsets. Nothing less lets the chip evaluate the chunk, so this is the
least traffic the algorithm allows a pass, as `xpass_bytes` is for an
evaluation's HBM traffic. Counted from the HOST leaves' shapes and dtypes
by this file's own walk (`photon_tpu` gives the ladder, not the count).
"""
from __future__ import annotations

import numpy as np


def _nbytes(a) -> int:
    return int(np.prod(np.shape(a))) * int(np.dtype(a.dtype).itemsize)


def chunk_upload_bytes(chunked_batch) -> dict:
    """{part: bytes} and their "total" for chunk 0 of a `ChunkedBatch`
    over a one-device blocked-ELL ladder (chunks are uniform)."""
    X = chunked_batch.X.chunks[0]
    rows = int(chunked_batch.X.chunk_rows)
    parts = {
        "hot_block": _nbytes(X.dense),
        "ell_tail": sum(_nbytes(c) + _nbytes(v)
                        for c, v in zip(X.ell_pcols, X.ell_vals)),
        "row_pos": _nbytes(X.row_pos),
        "occ_tail": sum(_nbytes(r) + _nbytes(v)
                        for r, v in zip(X.bucket_rows, X.bucket_vals)),
        "permutation": _nbytes(X.perm_cols) + _nbytes(X.inv_perm),
        "labels_weights_offsets": 3 * rows * 4,
    }
    parts["total"] = sum(parts.values())
    return parts


def ladder_bytes(chunked_batch) -> int:
    """Bytes of the whole ladder: what a RESIDENT data set would hold on
    the device, and what one pass hands it chunk by chunk."""
    return (chunk_upload_bytes(chunked_batch)["total"]
            * int(chunked_batch.X.n_chunks))
