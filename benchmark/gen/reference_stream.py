"""`gen/reference_blocked.py`'s float64 pass for a cell whose host already
holds the data set it streams: the same pass, a partial sum a CHUNK, sized
for a one-chip machine's memory.

`reference_blocked.shard_pass` keeps `THREADS` row blocks of `BLOCK_ROWS`
rows in flight, some 3 GB each at 33 entries a row and 10M columns: 24 GB
on the four-chip host it was written for. Here the 17.5 GB chunk ladder
and the COO are resident beside it on a 40 GiB host, so the blocks are a
quarter as tall and six are in flight (0.75 GB each, 4.5 GB). The pass is
imported, not copied: its two module constants are set for the call and
put back.
Nothing of the arithmetic changes — a block's sums are exact in float64 to
the last few bits whatever its height.
"""
from __future__ import annotations

import contextlib

from benchmark.gen import reference_blocked

BLOCK_ROWS = 1 << 18
THREADS = 6


@contextlib.contextmanager
def _sized(rows: int, threads: int):
    saved = reference_blocked.BLOCK_ROWS, reference_blocked.THREADS
    reference_blocked.BLOCK_ROWS, reference_blocked.THREADS = rows, threads
    try:
        yield
    finally:
        reference_blocked.BLOCK_ROWS, reference_blocked.THREADS = saved


def chunk_pass(ind, va, y, w, n_chunks: int, storage_dtype,
               hot_columns) -> dict:
    """`reference_blocked.shard_pass` with a chunk where it has a shard:
    {"stored", "unrounded", "lower"}, each with "loss" (n_chunks,),
    "margins" (n,), "grad0", "grad0_scale"."""
    with _sized(BLOCK_ROWS, THREADS):
        return reference_blocked.shard_pass(ind, va, y, w, n_chunks,
                                            storage_dtype, hot_columns)
