"""Labeled data containers.

Reference parity: com.linkedin.photon.ml.data.LabeledPoint (label, features,
offset, weight) and the GameDatum 4-tuple. A GLMBatch is the whole (or one
device-shard of the) dataset as arrays-of-structs: TPU-friendly, statically
shaped. Padding rows carry weight 0 so all reductions ignore them.
"""
from __future__ import annotations

import dataclasses
import time as _time
from typing import Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu import telemetry
from photon_tpu.data.matrix import (
    BlockedEllRows,
    HybridRows,
    Matrix,
    PermutedHybridRows,
    PinnedRows,
    ShardedBlockedEllRows,
    ShardedHybridRows,
    ShardedPermutedHybridRows,
    SparseRows,
    _place_chunk,
    rows_from_caller,
    shard_blocked_ell,
    shard_hybrid,
)


class GLMBatch(NamedTuple):
    X: Matrix
    y: jax.Array  # (n,)
    weights: jax.Array  # (n,) — 0.0 marks padding
    offsets: jax.Array  # (n,)

    @property
    def n(self) -> int:
        return self.y.shape[0]


def _stored_rows(X, v) -> jax.Array:
    """Caller-ordered per-row ``v`` as an f32 device array in the order X
    stores its rows; host data is permuted on the host."""
    if not isinstance(v, jax.Array):
        v = np.asarray(v, np.float32)
    return jnp.asarray(rows_from_caller(X, v), jnp.float32)


def make_batch(X, y, weights=None, offsets=None) -> GLMBatch:
    """The batch of design matrix X and the CALLER-ordered per-row y /
    weights / offsets (row i of each belongs to row i of the matrix X was
    built from). A GLMBatch is in the order X STORES its rows throughout:
    for a `to_blocked_ell` layout, which stores them in tail-bucket order,
    the three are permuted by its `row_order` here, once, so no evaluation
    gathers by row. For every other X that order is the caller's. Per-row
    results leave a batch through `data.matrix.rows_to_caller`."""
    y = _stored_rows(X, y)
    n = y.shape[0]
    weights = (jnp.ones((n,), jnp.float32) if weights is None
               else _stored_rows(X, weights))
    offsets = (jnp.zeros((n,), jnp.float32) if offsets is None
               else _stored_rows(X, offsets))
    if not isinstance(X, (SparseRows, HybridRows, ShardedHybridRows,
                          PermutedHybridRows, ShardedPermutedHybridRows,
                          BlockedEllRows, ShardedBlockedEllRows)):
        import jax

        # host numpy transfers as f32; an already-device FLOATING array
        # keeps its storage dtype (a bf16 shard must not silently double
        # its HBM through an f32 upcast — matvec accumulates f32 either
        # way). Integer device arrays still normalize to f32: matvec
        # would otherwise truncate w to the feature dtype.
        if not (isinstance(X, jax.Array)
                and jnp.issubdtype(X.dtype, jnp.floating)):
            X = jnp.asarray(X, jnp.float32)
    return GLMBatch(X, y, weights, offsets)


def pad_batch(batch: GLMBatch, target_n: int) -> GLMBatch:
    """Pad with zero-weight rows so shards divide evenly across the mesh."""
    n = batch.n
    if target_n == n:
        return batch
    extra = target_n - n
    X = batch.X
    if isinstance(X, (ShardedHybridRows, ShardedPermutedHybridRows,
                      ShardedBlockedEllRows)):
        raise ValueError(
            "cannot pad a sharded batch (per-shard tails are already laid "
            "out); pad before shard_hybrid_batch/shard_permuted_batch/"
            "shard_blocked_ell_batch")
    if isinstance(X, HybridRows):
        import dataclasses

        # Tail COO row ids already point at real rows; only the dense block
        # grows.
        X = dataclasses.replace(
            X, dense=jnp.concatenate(
                [X.dense, jnp.zeros((extra, X.dense.shape[1]),
                                    X.dense.dtype)]))
    elif isinstance(X, PermutedHybridRows):
        import dataclasses

        # Padding rows have no tail nnz: the dense block grows and the
        # row-bound prefix extends flat at the total nnz count.
        X = dataclasses.replace(
            X,
            dense=jnp.concatenate(
                [X.dense, jnp.zeros((extra, X.dense.shape[1]),
                                    X.dense.dtype)]),
            row_bounds=jnp.concatenate(
                [jnp.asarray(X.row_bounds),
                 jnp.full((extra,), jnp.asarray(X.row_bounds)[-1],
                          jnp.asarray(X.row_bounds).dtype)]))
    elif isinstance(X, BlockedEllRows):
        import dataclasses

        # Padding rows have no tail nnz: the dense block grows and the new
        # rows read a zero of the bucket concatenation — in a stored-order
        # layout their own (they land after the tail-free rows, which is
        # still concatenation order), else the shared slot B.
        if X.row_order is None:
            new_pos = jnp.full((extra,), X.tail_rows, jnp.int32)
            row_order = None
        else:
            new_pos = jnp.arange(n, target_n, dtype=jnp.int32)
            row_order = jnp.concatenate(
                [jnp.asarray(X.row_order), new_pos])
        X = dataclasses.replace(
            X,
            dense=jnp.concatenate(
                [X.dense, jnp.zeros((extra, X.dense.shape[1]),
                                    X.dense.dtype)]),
            row_pos=jnp.concatenate([jnp.asarray(X.row_pos), new_pos]),
            row_order=row_order)
    elif isinstance(X, SparseRows):
        X = SparseRows(
            jnp.concatenate([X.indices, jnp.zeros((extra, X.indices.shape[1]), jnp.int32)]),
            jnp.concatenate([X.values, jnp.zeros((extra, X.values.shape[1]), X.values.dtype)]),
            X.n_features,
        )
    else:
        X = jnp.concatenate([X, jnp.zeros((extra, X.shape[1]), X.dtype)])
    zeros = jnp.zeros((extra,), jnp.float32)
    return GLMBatch(
        X,
        jnp.concatenate([batch.y, zeros]),
        jnp.concatenate([batch.weights, zeros]),
        jnp.concatenate([batch.offsets, zeros]),
    )


def shard_hybrid_batch(batch: GLMBatch, n_shards: int,
                       d_dense: int = 1024) -> GLMBatch:
    """Pad a sparse batch to the mesh and re-lay its X as ShardedHybridRows
    (data.matrix.shard_hybrid): the mesh-ready form of the hot-dense /
    cold-tail representation. models.training.train_glm routes such batches
    through shard_map so each device keeps its own tail — the TPU answer to
    the reference's per-partition sparse vectors under treeAggregate."""
    from photon_tpu.parallel.mesh import pad_to_multiple

    if not isinstance(batch.X, (SparseRows, HybridRows)):
        raise TypeError("shard_hybrid_batch expects SparseRows or HybridRows")
    batch = pad_batch(batch, pad_to_multiple(batch.n, n_shards))
    return batch._replace(X=shard_hybrid(batch.X, n_shards, d_dense))


def shard_permuted_batch(batch: GLMBatch, n_shards: int,
                         d_dense: int = 1024,
                         device_dense_dtype=None) -> GLMBatch:
    """Pad a sparse batch to the mesh and re-lay its X as
    ShardedPermutedHybridRows (data.matrix.shard_permuted_hybrid): the
    mesh-ready form of the SCATTER-FREE permuted layout — each device gets
    its own cumsum flat tail + local-row bucket matrices under one global
    column permutation, so the sharded solve compiles to one all-reduce,
    zero other collectives, and zero scatters (tests/test_multihost.py)."""
    from photon_tpu.data.matrix import shard_permuted_hybrid
    from photon_tpu.parallel.mesh import pad_to_multiple

    if not isinstance(batch.X, SparseRows):
        raise TypeError("shard_permuted_batch expects SparseRows")
    batch = pad_batch(batch, pad_to_multiple(batch.n, n_shards))
    return batch._replace(X=shard_permuted_hybrid(
        batch.X, n_shards, d_dense, device_dense_dtype=device_dense_dtype))


def shard_blocked_ell_batch(batch: GLMBatch, n_shards: int,
                            d_dense: int = 1024,
                            device_dense_dtype=None,
                            mesh=None) -> GLMBatch:
    """Pad a sparse batch to the mesh and re-lay its X as
    ShardedBlockedEllRows (data.matrix.shard_blocked_ell): the mesh-ready
    form of the blocked-ELL layout — each device gets its own ELL row
    buckets + occurrence buckets under one global column permutation, so
    the sharded solve compiles to one all-reduce and zero scatters of any
    kind (models/training's `sharded_blocked_ell_value_and_grad`
    contract). A device-built hot block (`device_dense_dtype`) needs the
    ``mesh`` the solve will run on: it is built shard by shard, each on
    the device that keeps it (no device ever holds the whole block)."""
    from photon_tpu.parallel.mesh import pad_to_multiple

    if not isinstance(batch.X, SparseRows):
        raise TypeError("shard_blocked_ell_batch expects SparseRows")
    batch = pad_batch(batch, pad_to_multiple(batch.n, n_shards))
    return batch._replace(X=shard_blocked_ell(
        batch.X, n_shards, d_dense, device_dense_dtype=device_dense_dtype,
        mesh=mesh))


def with_offsets(batch: GLMBatch, offsets) -> GLMBatch:
    """``batch`` with the CALLER-ordered ``offsets`` (translated into the
    batch's stored row order, as `make_batch` does)."""
    return batch._replace(offsets=_stored_rows(batch.X, offsets))


def cast_features(batch: GLMBatch, dtype=jnp.bfloat16) -> GLMBatch:
    """Recast feature STORAGE (dense X or SparseRows values) — typically to
    bfloat16: halves feature HBM traffic and feeds the MXU its native input
    width, while every contraction still accumulates in f32
    (data.matrix matvec/rmatvec use preferred_element_type=float32).
    Labels/weights/offsets and all solver state stay f32."""
    X = batch.X
    if isinstance(X, (BlockedEllRows, ShardedBlockedEllRows)):
        import dataclasses

        # Every value leaf (hot block, ELL tail, occurrence buckets)
        # recasts; matvec/rmatvec then MULTIPLY in the storage dtype and
        # accumulate f32 (the blocked_ell_x_passes contract pins it).
        X = dataclasses.replace(
            X, dense=X.dense.astype(dtype),
            ell_vals=tuple(v.astype(dtype) for v in X.ell_vals),
            bucket_vals=tuple(v.astype(dtype) for v in X.bucket_vals))
    elif isinstance(X, (PermutedHybridRows, ShardedPermutedHybridRows)):
        import dataclasses

        X = dataclasses.replace(
            X, dense=X.dense.astype(dtype),
            tail_vals=X.tail_vals.astype(dtype),
            bucket_vals=tuple(v.astype(dtype) for v in X.bucket_vals))
    elif isinstance(X, (HybridRows, ShardedHybridRows)):
        import dataclasses

        X = dataclasses.replace(X, dense=X.dense.astype(dtype),
                                tail_vals=X.tail_vals.astype(dtype))
    elif isinstance(X, SparseRows):
        X = SparseRows(X.indices, X.values.astype(dtype), X.n_features)
    else:
        X = X.astype(dtype)
    return batch._replace(X=X)


def total_weight(batch: GLMBatch) -> float:
    return float(np.sum(np.asarray(batch.weights)))


# --------------------------------------------------------------------------
# Host-resident chunked datasets (the out-of-HBM streamed-objective regime).
#
# Reference parity: the dataset in a DistributedGLMLossFunction solve never
# lives in one executor's memory — Spark partitions stream through each
# treeAggregate. Here the dataset lives on HOST in uniform row chunks and
# streams through the device chunk by chunk: HBM only ever holds one or two
# chunks plus solver state, so a single chip trains datasets far bigger than
# its HBM (BASELINE config 4's 100M-row regime).


# One `device_put` of a multi-GB host array is the slow way onto a v5e: the
# runtime stages the whole array in fresh host memory first (4.29 GB of
# bf16 went over at 0.44–0.57 GB/s and left three times its bytes in the
# process's RSS; the same bytes in row pieces of 16–128 MB at 12.8–14.0
# GB/s, RSS flat: chip runs of PR 34, PERF.md §6). So a large leaf goes up
# in row pieces of this many bytes, each laid into a preallocated device
# buffer in place (`data.matrix._place_chunk`), and no more than
# `_UPLOAD_PIECES_IN_FLIGHT` of them wait on the device for their write at
# once: a piece's device buffer is allocated when it is issued, so a leaf
# issued whole would hold its bytes twice. Four in flight reach the rate
# of all of them on a quiet host (14.0 against 13.95 GB/s); sixteen (0.5
# GB) cost nothing there and lose less when the issuing thread is late —
# a solve beside four memory-streaming neighbours took 38.6 / 42.0 s
# against 39.9 / 53.6, beside 26 spinning ones 67.9 against 107.8 (one
# process, alternating: the noise round's chip run, PERF.md §6).
_UPLOAD_PIECE_BYTES = 32 << 20
_UPLOAD_PIECES_IN_FLIGHT = 16


def device_put_in_pieces(tree, device=None):
    """`jax.device_put(tree, device)` for a tree of HOST arrays, with every
    leaf of more than two pieces' bytes uploaded in row pieces of
    `_UPLOAD_PIECE_BYTES` and assembled in place on the device (a
    `PinnedRows` leaf, a ladder's hot block already in pinned host memory,
    in the pieces it is kept in: no staging copy). The call
    returns once all but the last `_UPLOAD_PIECES_IN_FLIGHT` pieces of
    every large leaf have landed; the result's big leaves are the last
    in-place write's output, ready when every piece has."""
    from collections import deque

    from jax.sharding import SingleDeviceSharding

    def put(leaf):
        if isinstance(leaf, PinnedRows):  # its own pieces, already pinned
            dev = device or jax.devices()[0]
            to = SingleDeviceSharding(dev, memory_kind=dev.default_memory(
            ).kind)
            pieces = leaf.pieces()
        elif (not isinstance(leaf, np.ndarray) or leaf.ndim == 0
                or leaf.nbytes <= 2 * _UPLOAD_PIECE_BYTES):
            return jax.device_put(leaf, device)
        else:
            n, to = leaf.shape[0], device
            rows = max(1, _UPLOAD_PIECE_BYTES // max(leaf.nbytes // n, 1))
            pieces = ((r0, leaf[r0:r0 + rows]) for r0 in range(0, n, rows))
        buf = jnp.zeros(leaf.shape, leaf.dtype, device=device)
        in_flight: deque = deque()
        for r0, host_piece in pieces:
            if len(in_flight) == _UPLOAD_PIECES_IN_FLIGHT:
                in_flight.popleft().block_until_ready()
            piece = jax.device_put(host_piece, to)
            in_flight.append(piece)
            buf = _place_chunk(buf, piece, np.int32(r0))
        return buf

    return jax.tree_util.tree_map(put, tree)


def _pins_host_blocks() -> bool:
    """Whether a one-device chunk ladder keeps its hot block in pinned host
    memory: on an accelerator whose runtime has that memory kind. A CPU
    backend's device memory IS host memory: nothing crosses a link."""
    dev = jax.devices()[0]
    return dev.platform != "cpu" and any(
        m.kind == "pinned_host" for m in dev.addressable_memories())


@dataclasses.dataclass(frozen=True)
class ChunkedMatrix:
    """A design matrix as HOST-resident uniform row chunks.

    `chunks` are numpy dense (c, d) blocks, host-backed SparseRows with a
    shared nnz width, or host-backed BlockedEllRows cut from ONE
    `shard_blocked_ell` ladder (`chunk_blocked_ell`) — every chunk the
    same shape, so the per-chunk device programs compile exactly once.
    The LAST chunk is padded with all-zero rows up to the chunk height
    (`n_real` marks where real rows end; the owning ChunkedBatch gives pad
    rows weight 0, so every reduction ignores them).

    Blocked-ELL chunks carry the ladder's GLOBAL column permutation in
    `perm_cols`/`inv_perm`/`last_col_pos` — chunk partials then accumulate
    in ONE shared permuted (d,)-space across the whole stream, and
    models.training translates at its public boundary exactly as for the
    resident permuted layouts. The other device-locality layouts
    (Hybrid/Permuted) stay deliberately unsupported: without a shared
    cross-chunk permutation their per-chunk gradients would not align.
    """

    chunks: tuple  # host numpy / SparseRows / BlockedEllRows, uniform
    n_real: int  # real rows (pre-padding)
    n_features: int
    perm_cols: object = None      # (d,) np.int32 — blocked-ELL chunks only
    inv_perm: object = None       # (d,) np.int32 — blocked-ELL chunks only
    last_col_pos: int | None = None

    @property
    def n_chunks(self) -> int:
        return len(self.chunks)

    @property
    def permuted(self) -> bool:
        return self.perm_cols is not None

    @property
    def chunk_rows(self) -> int:
        c = self.chunks[0]
        return int((c.indices if isinstance(c, SparseRows) else c).shape[0])

    @property
    def n_padded(self) -> int:
        return self.n_chunks * self.chunk_rows

    @property
    def shape(self) -> tuple:
        return (self.n_real, self.n_features)

    @property
    def chunk_shards(self) -> int:
        """Device shards each chunk was laid for: >1 iff the chunks are
        ShardedBlockedEllRows groups from a mesh ladder
        (`chunk_blocked_ell(..., n_shards=D)`), else 1."""
        c = self.chunks[0]
        return c.n_shards if isinstance(c, ShardedBlockedEllRows) else 1

    def nbytes(self) -> int:
        total = 0
        for c in self.chunks:
            if isinstance(c, SparseRows):
                total += c.indices.nbytes + c.values.nbytes
            elif isinstance(c, (BlockedEllRows, ShardedBlockedEllRows)):
                total += sum(int(leaf.nbytes) for leaf in
                             jax.tree_util.tree_leaves(c))
            else:
                total += c.nbytes
        return total


class ChunkedBatch(NamedTuple):
    """A GLMBatch-shaped dataset living on HOST as uniform chunks.

    Scalars are full (n_padded,) numpy vectors (12 bytes/row — the feature
    chunks dominate); `chunk(i)` slices out one host GLMBatch, and
    `iter_device()` streams device-resident chunks with the next transfer
    overlapping the current chunk's compute — onto one device, or (with
    ``mesh=``) row-sharded across a whole mesh, each device slot fed its
    own host slice. models.training.train_glm dispatches a ChunkedBatch to
    the streamed solvers automatically.
    """

    X: ChunkedMatrix
    y: np.ndarray  # (n_padded,)
    weights: np.ndarray  # (n_padded,) — 0.0 marks padding
    offsets: np.ndarray  # (n_padded,)

    @property
    def n(self) -> int:
        return self.X.n_real

    @property
    def n_chunks(self) -> int:
        return self.X.n_chunks

    @property
    def chunk_rows(self) -> int:
        return self.X.chunk_rows

    def chunk(self, i: int) -> GLMBatch:
        """Host-side GLMBatch of chunk i (numpy leaves)."""
        c = self.X.chunk_rows
        sl = slice(i * c, (i + 1) * c)
        return GLMBatch(self.X.chunks[i], self.y[sl], self.weights[sl],
                        self.offsets[sl])

    def chunk_nbytes(self) -> int:
        """Host bytes of ONE chunk as `chunk(i)` hands it to `device_put`:
        every leaf of its matrix (a blocked-ELL chunk carries the ladder's
        two (d,) permutation vectors) and its three scalar columns. Chunks
        are uniform, so chunk 0 speaks for all."""
        if self.n_chunks == 0:
            return 0
        return sum(int(leaf.nbytes if hasattr(leaf, "nbytes")
                       else np.asarray(leaf).nbytes)
                   for leaf in jax.tree_util.tree_leaves(self.chunk(0)))

    def mesh_chunk_rows(self, mesh) -> int:
        """Per-chunk row count after padding to the mesh (every chunk pads
        to the same height, so the per-chunk device programs still compile
        exactly once)."""
        from photon_tpu.parallel.mesh import pad_to_multiple

        return pad_to_multiple(self.X.chunk_rows, int(mesh.devices.size))

    def mesh_chunk(self, i: int, mesh, _cache: dict | None = None
                   ) -> GLMBatch:
        """Chunk i row-sharded over ALL mesh axes: each device slot's host
        slice is device_put straight onto its device (multi-host: this
        process uploads only its own slots' rows — features never cross
        DCN), pad rows carry weight 0.

        A ShardedBlockedEllRows chunk (mesh ladder —
        `chunk_blocked_ell(..., n_shards=D)`) uploads shard-major: its
        dense block row-shards, the per-shard ELL/occurrence buckets go
        one leading index per device (`parallel.mesh.shard_stacked`),
        and the shared column permutation replicates ONCE per stream
        pass (``_cache``, threaded by `iter_device`)."""
        from photon_tpu.parallel.mesh import shard_rows

        pad = self.mesh_chunk_rows(mesh)
        X = self.X.chunks[i]
        if isinstance(X, BlockedEllRows):
            raise TypeError(
                "single-device blocked-ELL chunks cannot row-shard over a "
                "mesh; rebuild the ladder for the mesh with "
                "data.dataset.chunk_blocked_ell(batch, chunk_rows, "
                f"n_shards={len(mesh.devices.reshape(-1))}) — or stream "
                "SparseRows chunks, or solve resident with "
                "data.dataset.shard_blocked_ell_batch")
        if isinstance(X, ShardedBlockedEllRows):
            Xs = mesh_chunk_matrix(X, mesh, _cache)
        elif isinstance(X, SparseRows):
            Xs = SparseRows(shard_rows(X.indices, mesh, pad_rows=pad),
                            shard_rows(X.values, mesh, pad_rows=pad),
                            X.n_features)
        else:
            Xs = shard_rows(X, mesh, pad_rows=pad)
        c = self.X.chunk_rows
        sl = slice(i * c, (i + 1) * c)
        return GLMBatch(Xs,
                        shard_rows(self.y[sl], mesh, pad_rows=pad),
                        shard_rows(self.weights[sl], mesh, pad_rows=pad),
                        shard_rows(self.offsets[sl], mesh, pad_rows=pad))

    def chunk_scalars_sharded(self, i: int, mesh) -> tuple:
        """(y, weights) of chunk i row-sharded over the mesh — the 8 B/row
        a streamed line-search trial re-uploads alongside its cached
        margins (no feature stream)."""
        from photon_tpu.parallel.mesh import shard_rows

        pad = self.mesh_chunk_rows(mesh)
        c = self.X.chunk_rows
        sl = slice(i * c, (i + 1) * c)
        return (shard_rows(self.y[sl], mesh, pad_rows=pad),
                shard_rows(self.weights[sl], mesh, pad_rows=pad))

    def iter_device(self, device=None, mesh=None,
                    prefetch=2) -> Iterator:
        """Yield (i, device-resident GLMBatch) chunk by chunk, PREFETCHED:
        up to ``prefetch`` chunks (default 2 — the classic double buffer)
        are in flight at once, so chunk i+`k`'s host→device transfer
        overlaps the caller's compute on chunk i (jax transfers are
        asynchronous). Peak device footprint is ~``prefetch`` chunks, never
        the dataset. With ``mesh=``, every chunk is row-sharded across the
        whole mesh (`mesh_chunk`) instead of landing on one device.

        ``prefetch`` may also be a stall-driven controller
        (`data.ingest_plane.AdaptivePrefetch`): each pass then runs at the
        controller's current depth, and the pass's measured stall/compute
        totals feed `observe` at exhaustion — the window widens while
        uploads stall, bounded by the controller's byte budget, and every
        decision lands in telemetry (``prefetch_decision`` events). Depth
        never changes results — it is purely an overlap knob.

        The iterator times how long it stalls waiting for each prefetched
        chunk's transfer and how long it spends in the upload calls;
        per-pass totals land in the telemetry counters
        (`stream.chunk_uploads` / `stream.upload_bytes` /
        `stream.stall_seconds` / `stream.issue_seconds` /
        `stream.compute_seconds`: the pass's wall is the three seconds
        counters together, the last the consumer's own time), and when
        the time waited on transfers (stall + issue) exceeds the
        consumer's it logs the imbalance at INFO — the
        signal that a deeper prefetch or a bigger `objective_chunk_rows`
        would help."""
        from collections import deque

        from photon_tpu.checkpoint.faults import kill_point

        n = self.n_chunks
        if n == 0:
            return
        ctl = prefetch if hasattr(prefetch, "observe") else None
        depth = max(int(ctl.depth if ctl is not None else prefetch), 1)
        if mesh is not None:
            # per-pass upload cache: stream-wide replicated structures
            # (the blocked-ELL ladder's column permutation) upload once
            # per pass, not once per chunk
            mesh_cache: dict = {}
            put = lambda i: self.mesh_chunk(i, mesh,  # noqa: E731
                                            _cache=mesh_cache)
        else:
            put = lambda i: device_put_in_pieces(  # noqa: E731
                self.chunk(i), device)

        window: deque = deque()
        issued = 0
        stall = issue = 0.0
        t_start = _time.perf_counter()
        for i in range(n):
            # keep chunks i..i+depth-1 issued (async) before blocking on i
            while issued < min(i + depth, n):
                t0 = _time.perf_counter()
                window.append(put(issued))
                issue += _time.perf_counter() - t0
                issued += 1
            cur = window.popleft()
            # fault-injection site: a preemption mid-upload-stream (the
            # checkpoint parity tests kill and resume here). Disarmed:
            # one global load + one branch per chunk.
            kill_point("chunk_upload")
            t0 = _time.perf_counter()
            jax.block_until_ready(cur)
            stall += _time.perf_counter() - t0
            yield i, cur
        # the consumer's own time: the pass's wall less the waits for a
        # chunk and less the upload calls
        compute = (_time.perf_counter() - t_start) - stall - issue
        telemetry.count("stream.passes")
        telemetry.count("stream.chunk_uploads", n)
        telemetry.count("stream.upload_bytes", n * self.chunk_nbytes())
        telemetry.count("stream.stall_seconds", stall)
        telemetry.count("stream.issue_seconds", issue)
        telemetry.count("stream.compute_seconds", max(compute, 0.0))
        telemetry.gauge("stream.prefetch_depth", depth)
        from photon_tpu import profiling

        profiling.attribute("ingest.upload", "upload", max(stall, 0.0))
        if ctl is not None:
            ctl.observe(stall + issue, max(compute, 0.0), n,
                        self.X.nbytes() // max(self.X.n_chunks, 1))
        _log_stream_stall(stall + issue, compute, n, depth)

    def device_ring(self, device=None, mesh=None,
                    prefetch=2) -> "DeviceChunkRing":
        """A persistent cross-pass upload ring over this dataset's chunks
        (see `DeviceChunkRing`) — the streamed solvers' upload/compute
        overlap regime. `iter_device` is the one-shot per-pass form."""
        return DeviceChunkRing(self, device=device, mesh=mesh,
                               prefetch=prefetch)


class DeviceChunkRing:
    """A PERSISTENT double-buffered upload ring over one ChunkedBatch:
    the cross-pass form of `ChunkedBatch.iter_device`.

    `iter_device` overlaps chunk i+1's host→device copy with chunk i's
    compute WITHIN a pass, but the window drains at pass end — so the
    next evaluation's first uploads serialize behind the current
    evaluation's close: the mesh psum (`_MeshChunkOps.finish`), its host
    readback, and the Wolfe host step all run with the link idle. The
    ring keeps the window primed ACROSS passes instead: chunk indices
    wrap (the streamed solvers re-stream the same chunks every
    evaluation), so while the caller closes pass p — partials, psum,
    readback — the first chunks of pass p+1 are already in flight.

    The ring owns its depth — never more than `depth` chunks allocated on
    the device — and donation alone does not give it: a donated leaf with
    no output of its size to alias (the hot block, every tail bucket) is
    not donated at all, so a consumed chunk stays on the device for as
    long as the consumer's loop variable names it. So a one-device
    consumer hands each chunk program's outputs to `consumed()`, straight
    after the dispatch, and THAT is where the ring acts: it frees the
    chunk handed out before this one (whose program ran ahead of this
    chunk's in-place writes on the device's one queue, so it finished
    before this chunk landed: no wait), then issues the next upload. The
    program just dispatched therefore runs AHEAD of the next chunk's
    in-place writes and BESIDE its transfers — an upload call returns when
    all but its last pieces have crossed the link, and a program
    dispatched only after that call runs with the link idle (54 ms a
    4.45 GB chunk on the chip, PERF.md §6, PR 35). With depth 2 the
    device holds the chunk being computed on and the chunk being
    uploaded; at the north-star chunk a third does not fit beside the
    solver. A consumer that says nothing gets the next upload when it
    resumes the generator, and frees its chunks itself by dropping them —
    one rule: the ring tops itself up to `depth` chunks, counting the one
    whose program it was told of, whenever it is given the word. A solve
    `close()`s its ring when it ends: what was primed for a pass that
    never comes is dropped once it has landed, so the next solve's ring
    never meets it on the chip.

    Per-pass semantics are `iter_device`'s exactly: `stream_pass()`
    yields ``(i, device_chunk)`` in order with the same telemetry
    counters (`stream.upload_bytes` is the host bytes of the chunks a pass
    CONSUMED, `ChunkedBatch.chunk_nbytes` each: chunks primed and then
    dropped by `close()` are not in it; `stream.issue_seconds` the host
    seconds the pass spent handing chunks to the runtime: an upload in
    pieces returns when all but its last pieces have crossed the link, so
    on a link-bound stream this is the wait for the link;
    `stream.compute_seconds` the rest of the pass's wall once that and
    the hand-out waits, `stream.stall_seconds`, are taken off: the
    consumer's own time;
    `stream.uploads_behind_compute` the uploads issued while the outputs
    `consumed()` was given were not ready yet: the program they hide
    behind), the same `chunk_upload`
    fault-injection site per chunk, ledger attribution
    (``ingest.upload`` stall + ``solve.compute``) and `AdaptivePrefetch`
    support.

    Where the ring makes the host wait it opens a span, under whatever
    span its caller holds (a solver's ``stream.pass``): ``stream.upload``
    around each upload call (``chunk``, the ladder index; ``behind``,
    whether the program it was issued behind was still running),
    ``stream.handout`` around the wait for the chunk about to be handed
    out (``chunk``), ``stream.release`` around the wait for a consumed
    chunk's program and the freeing of its leaves, and around `close()`'s
    wait for what was primed (``chunk``). ONE measurement, two sinks: with
    a run attached `stream.issue_seconds` / `stream.stall_seconds` are
    the sums of the upload / hand-out spans' own clock readings; with
    none the spans are `telemetry`'s shared no-op and the ring reads the
    clock itself, as it always did. A pass abandoned mid-way (an
    injected kill, any exception) resets the ring to a clean state — the
    next pass starts at chunk 0 with nothing stale in flight. Mesh mode
    additionally persists the replication cache across passes, so a
    blocked-ELL ladder's column permutation uploads once per SOLVE, not
    once per pass.
    """

    def __init__(self, batch: "ChunkedBatch", device=None, mesh=None,
                 prefetch=2):
        from collections import deque

        self.batch, self.mesh = batch, mesh
        self._ctl = prefetch if hasattr(prefetch, "observe") else None
        self._prefetch = prefetch
        self._window: deque = deque()
        self._next = 0  # chunk index the next upload issues (mod n_chunks)
        self._handed = None  # the chunk the consumer holds
        self._handed_index = None  # and its index in the ladder
        self._spoken = None  # (chunk, its program's outputs, its index),
        #                      once `consumed`
        self._issue = 0.0  # host seconds inside `_put` this pass
        self._behind = 0  # uploads issued behind a running program, this pass
        self._chunk_nbytes = batch.chunk_nbytes()
        if mesh is not None:
            mesh_cache: dict = {}  # persists across passes: perm uploads once
            self._put = lambda i: batch.mesh_chunk(i, mesh,
                                                   _cache=mesh_cache)
        else:
            self._put = lambda i: device_put_in_pieces(batch.chunk(i),
                                                       device)

    @property
    def depth(self) -> int:
        return max(int(self._ctl.depth if self._ctl is not None
                       else self._prefetch), 1)

    def consumed(self, outputs):
        """The outputs of the program that consumed the chunk just handed
        out, returned as they are: the consumer is done with that chunk
        and its program is dispatched. The ring frees the chunk spoken for
        before this one and issues the next upload behind this program
        (see the class note); a mesh ring only waits, its chunks share the
        replicated permutation's buffers."""
        self._release()
        self._spoken = (self._handed, outputs, self._handed_index)
        self._handed = None
        self._top_up()
        return outputs

    def _release(self) -> None:
        """Once the program `consumed()` was told of has run, free its
        chunk."""
        if self._spoken is None:
            return
        chunk, outputs, index = self._spoken
        with telemetry.span("stream.release", chunk=index):
            jax.block_until_ready(outputs)
            if self.mesh is None:
                for leaf in jax.tree_util.tree_leaves(chunk):
                    if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                        leaf.delete()
        self._spoken = None

    def _top_up(self) -> None:
        """Issue uploads until the ring holds its depth: the window and
        the chunk whose program is still the ring's to wait for."""
        n = self.batch.n_chunks
        held = self._spoken is not None
        while len(self._window) + held < min(self.depth, n):
            behind = held and not _is_ready(self._spoken[1])
            self._behind += behind
            t0 = _time.perf_counter()
            with telemetry.span("stream.upload", chunk=self._next,
                                behind=behind) as rec:
                self._window.append(self._put(self._next))
            self._issue += _span_seconds(rec, t0)
            self._next = (self._next + 1) % n

    def _fill(self) -> None:
        """The generator's turn: a chunk nobody spoke for is its
        consumer's to drop, and a window that `consumed()` left empty (a
        ring one deep) takes the spoken chunk's place."""
        self._handed = None
        if not self._window:
            self._release()
        self._top_up()

    def close(self) -> None:
        """Drop whatever is still in flight, once it has landed: after
        this the ring holds nothing on the device."""
        self._release()
        if self._window:
            n = self.batch.n_chunks
            with telemetry.span("stream.release",
                                chunk=(self._next - len(self._window)) % n):
                jax.block_until_ready(list(self._window))
                self._window.clear()
        self._next, self._handed = 0, None

    def stream_pass(self):
        """One pass: yield (i, device chunk) for every chunk, keeping the
        upload window full — including past the last chunk, into the
        next pass (the psum/readback overlap)."""
        from photon_tpu import profiling
        from photon_tpu.checkpoint.faults import kill_point

        n = self.batch.n_chunks
        if n == 0:
            return
        depth = self.depth
        stall, self._issue, self._behind = 0.0, 0.0, 0
        t_start = _time.perf_counter()
        ok = False
        try:
            for i in range(n):
                self._fill()
                cur = self._window.popleft()
                kill_point("chunk_upload")
                t0 = _time.perf_counter()
                with telemetry.span("stream.handout", chunk=i) as rec:
                    jax.block_until_ready(cur)
                stall += _span_seconds(rec, t0)
                self._handed, self._handed_index = cur, i
                del cur  # the ring's one name for it is `_handed`
                yield i, self._handed
            # prime the NEXT pass before the caller closes this one: a
            # consumer that spoke for the last chunk already has (its
            # `consumed` wrapped past chunk n-1); this tops the window up
            # for one that did not
            self._fill()
            ok = True
        finally:
            if not ok:
                # abandoned mid-pass (kill/exception): drop in-flight
                # uploads so the next pass starts clean at chunk 0
                self._window.clear()
                self._next, self._spoken, self._handed = 0, None, None
            # the consumer's own time: the pass's wall less the waits for
            # a chunk and less the upload calls
            compute = (_time.perf_counter() - t_start) - stall - self._issue
            telemetry.count("stream.passes")
            telemetry.count("stream.chunk_uploads", n)
            telemetry.count("stream.upload_bytes", n * self._chunk_nbytes)
            telemetry.count("stream.uploads_behind_compute", self._behind)
            telemetry.count("stream.stall_seconds", stall)
            telemetry.count("stream.issue_seconds", self._issue)
            telemetry.count("stream.compute_seconds", max(compute, 0.0))
            telemetry.gauge("stream.prefetch_depth", depth)
            profiling.attribute("ingest.upload", "upload", max(stall, 0.0))
            profiling.attribute("solve.compute", "compute",
                                max(compute, 0.0))
            if ok and self._ctl is not None:
                self._ctl.observe(
                    stall + self._issue, max(compute, 0.0), n,
                    self.batch.X.nbytes() // max(self.batch.X.n_chunks, 1))
            _log_stream_stall(stall + self._issue, compute, n, depth)


def _span_seconds(rec, t0: float) -> float:
    """Seconds of the block a `telemetry.span` just closed around: the
    span's own two clock readings where a run recorded it (``rec``), so a
    counter summed from these and the span are ONE measurement; else the
    time since the caller's own reading ``t0``."""
    return rec.seconds if rec is not None else _time.perf_counter() - t0


def _is_ready(tree) -> bool:
    """Whether every device array of a tree has been computed."""
    return all(leaf.is_ready() for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "is_ready"))


def mesh_chunk_matrix(X, mesh, _cache: dict | None = None):
    """Upload one ShardedBlockedEllRows chunk onto the mesh: the dense
    block row-shards over all mesh axes, every per-shard structure leaf
    (ELL row buckets, occurrence buckets, row_pos) goes one leading index
    per device slot, and the shared column permutation replicates —
    cached across chunks of a pass via ``_cache`` since the whole ladder
    carries ONE global permutation. Shared by `ChunkedBatch.mesh_chunk`
    and the GAME streamed scorer (`game.scoring.score_chunked_host`)."""
    import dataclasses as _dc

    from photon_tpu.data.matrix import ShardedBlockedEllRows as _SB
    from photon_tpu.parallel.mesh import (replicated, shard_rows,
                                          shard_stacked)

    if not isinstance(X, _SB):
        raise TypeError("mesh_chunk_matrix expects ShardedBlockedEllRows")
    n_dev = len(mesh.devices.reshape(-1))
    if X.n_shards != n_dev:
        raise ValueError(
            f"blocked-ELL chunk ladder was laid for {X.n_shards} device "
            f"shard(s) but the mesh has {n_dev}; rebuild with "
            f"data.dataset.chunk_blocked_ell(batch, chunk_rows, "
            f"n_shards={n_dev})")
    if _cache is None:
        _cache = {}
    perm = _cache.get("perm")
    if perm is None:
        rep = replicated(mesh)
        perm = (jax.device_put(np.asarray(X.perm_cols), rep),
                jax.device_put(np.asarray(X.inv_perm), rep))
        _cache["perm"] = perm
    return _dc.replace(
        X,
        dense=shard_rows(X.dense, mesh, pad_rows=X.dense.shape[0]),
        ell_pcols=tuple(shard_stacked(b, mesh) for b in X.ell_pcols),
        ell_vals=tuple(shard_stacked(b, mesh) for b in X.ell_vals),
        row_pos=shard_stacked(X.row_pos, mesh),
        bucket_rows=tuple(shard_stacked(b, mesh) for b in X.bucket_rows),
        bucket_vals=tuple(shard_stacked(b, mesh) for b in X.bucket_vals),
        perm_cols=perm[0], inv_perm=perm[1])


def _log_stream_stall(stall: float, compute: float, n_chunks: int,
                      prefetch: int) -> None:
    """One INFO line (plus a `stream.stalled_passes` telemetry counter)
    per streaming pass when the time waited on transfers (``stall``: the
    waits for a chunk AND the upload calls) exceeds the consumer's own —
    the signal that a deeper prefetch or a bigger chunk would overlap the
    host link better, and the plain truth of a link-bound stream
    (iter_device calls this at generator exhaustion with its
    measured per-pass totals). The log rides `photon_logger` with root
    propagation kept ON, so capturing harnesses and a configured root
    logger both see it."""
    from photon_tpu.utils.logging import photon_logger

    if n_chunks > 1 and stall > compute:
        telemetry.count("stream.stalled_passes")
        photon_logger("photon_tpu.streamed", propagate=True).info(
            "chunk upload outpaced compute: stalled %.3fs on transfers vs "
            "%.3fs compute over %d chunks (prefetch=%d) — a deeper "
            "prefetch or bigger chunks would overlap better",
            stall, compute, n_chunks, prefetch)


def _host_sparse(X: SparseRows) -> SparseRows:
    return SparseRows(np.asarray(X.indices), np.asarray(X.values),
                      X.n_features)


def chunk_matrix(X, chunk_rows: int) -> ChunkedMatrix:
    """Split a dense array or SparseRows into a host ChunkedMatrix (last
    chunk zero-padded to the uniform height)."""
    if isinstance(X, (HybridRows, ShardedHybridRows, PermutedHybridRows,
                      ShardedPermutedHybridRows, BlockedEllRows,
                      ShardedBlockedEllRows)):
        raise TypeError(
            f"{type(X).__name__} cannot be host-chunked (device-locality "
            "layout); chunk the SparseRows/dense form instead — or use "
            "chunk_blocked_ell to build a blocked-ELL chunk ladder from "
            "SparseRows")
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    sparse = isinstance(X, SparseRows)
    if sparse:
        X = _host_sparse(X)
        n, d = X.indices.shape[0], X.n_features
    else:
        X = np.asarray(X)
        n, d = X.shape
    chunks = []
    for lo in range(0, max(n, 1), chunk_rows):
        hi = min(lo + chunk_rows, n)
        pad = chunk_rows - (hi - lo)
        if sparse:
            ind = X.indices[lo:hi]
            val = X.values[lo:hi]
            if pad:
                ind = np.concatenate(
                    [ind, np.zeros((pad, ind.shape[1]), ind.dtype)])
                val = np.concatenate(
                    [val, np.zeros((pad, val.shape[1]), val.dtype)])
            chunks.append(SparseRows(ind, val, d))
        else:
            blk = X[lo:hi]
            if pad:
                blk = np.concatenate(
                    [blk, np.zeros((pad, d), blk.dtype)])
            chunks.append(blk)
    return ChunkedMatrix(tuple(chunks), n, d)


def make_chunked_batch(X: ChunkedMatrix, y, weights=None,
                       offsets=None) -> ChunkedBatch:
    """Assemble a ChunkedBatch from a ChunkedMatrix and (n_real,) scalar
    columns (device arrays are fetched to host; padding rows get weight 0)."""
    n, n_pad = X.n_real, X.n_padded

    def col(v, fill):
        if v is None:
            return np.full(n_pad, fill, np.float32)
        v = np.asarray(v, np.float32)
        if v.shape[0] == n_pad:
            return v
        if v.shape[0] != n:
            raise ValueError(
                f"scalar column has {v.shape[0]} rows; matrix has {n}")
        return np.concatenate([v, np.zeros(n_pad - n, np.float32)])

    y = col(y, 0.0)
    weights = col(weights, 1.0)
    if n_pad > n:
        weights = weights.copy()
        weights[n:] = 0.0  # padding must never enter a reduction
    return ChunkedBatch(X, y, weights, col(offsets, 0.0))


def chunk_batch(batch: GLMBatch, chunk_rows: int) -> ChunkedBatch:
    """Re-lay a (host or device) GLMBatch as a host-resident ChunkedBatch —
    the test/bench seam for streamed-vs-resident parity."""
    X = batch.X
    if isinstance(X, SparseRows):
        X = _host_sparse(X)
    else:
        X = np.asarray(X)
    return make_chunked_batch(
        chunk_matrix(X, chunk_rows), np.asarray(batch.y),
        np.asarray(batch.weights), np.asarray(batch.offsets))


def chunk_blocked_ell(batch: GLMBatch, chunk_rows: int,
                      d_dense: int = 1024,
                      feature_dtype=None,
                      n_shards: int = 1) -> ChunkedBatch:
    """Re-lay a SparseRows batch as a HOST blocked-ELL chunk ladder: one
    `shard_blocked_ell` pass with S = n_chunks builds a GLOBAL column
    permutation + per-chunk structures padded to COMMON shapes, so the
    streamed solve uploads gather-fused scatter-free chunks and compiles
    each per-chunk program exactly once (the out-of-HBM form of the
    blocked-ELL hot path — `train_glm` on the result dispatches to the
    streamed solvers and translates the permutation at its boundary).

    ``n_shards > 1`` lays the ladder for a MESH of that many devices (the
    pod-scale GAME fixed-effect regime): the builder runs with
    S = n_chunks × n_shards and each streamed chunk is the
    ShardedBlockedEllRows group of its ``n_shards`` consecutive shards —
    every chunk row-shards over the mesh (`ChunkedBatch.mesh_chunk`) with
    per-shard ELL/occurrence buckets and ONE global permutation, so the
    sharded per-chunk programs compile exactly once and each evaluation
    still closes with one psum. ``chunk_rows`` must be a multiple of
    ``n_shards``.

    ``feature_dtype`` (e.g. jnp.bfloat16) is what every chunk's values are
    STORED as — half the per-pass host→device feature bytes, f32
    accumulation unchanged. The hot block is built in it piece by piece
    (`data.matrix._dense_on_host`): the host never holds the ladder's f32
    form, which at 8.4M rows × 1024 columns would be 34 GB beside the 17
    that stay. The tail's small value leaves are cast chunk by chunk.

    On an accelerator a one-device ladder's hot block is laid straight
    into PINNED host memory, in the upload's own row pieces
    (`data.matrix.PinnedRows`; `_pins_host_blocks`): a chunk then crosses
    the host link by DMA alone, at the link's rate whatever the host's
    other tenants do, where pageable memory goes through the runtime's
    staging copy (see `PinnedRows`). Same values, same shapes, same
    programs; the mesh ladder and a CPU backend keep numpy blocks.
    """
    X = batch.X
    if not isinstance(X, SparseRows):
        raise TypeError("chunk_blocked_ell expects SparseRows")
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if chunk_rows % n_shards != 0:
        raise ValueError(
            f"chunk_rows={chunk_rows} must be a multiple of "
            f"n_shards={n_shards} (every device slot streams an equal "
            "row slice of every chunk)")
    n = batch.n
    n_pad = -(-max(n, 1) // chunk_rows) * chunk_rows
    host = batch._replace(X=_host_sparse(X), y=np.asarray(batch.y),
                          weights=np.asarray(batch.weights),
                          offsets=np.asarray(batch.offsets))
    padded = pad_batch(host, n_pad)
    S = (n_pad // chunk_rows) * n_shards
    ladder = shard_blocked_ell(
        _host_sparse(padded.X), S, d_dense,
        host_dense_dtype=np.float32 if feature_dtype is None
        else feature_dtype,
        host_pinned_piece_bytes=_UPLOAD_PIECE_BYTES
        if n_shards == 1 and _pins_host_blocks() else 0)

    def recast(c):
        if feature_dtype is None:
            return c
        return dataclasses.replace(
            c, ell_vals=tuple(np.asarray(v).astype(feature_dtype)
                              for v in c.ell_vals),
            bucket_vals=tuple(np.asarray(v).astype(feature_dtype)
                              for v in c.bucket_vals))

    if n_shards == 1:
        chunks = tuple(recast(ladder.chunk(i))
                       for i in range(n_pad // chunk_rows))
    else:
        chunks = tuple(
            recast(ladder.shard_slice(i * n_shards, (i + 1) * n_shards))
            for i in range(n_pad // chunk_rows))
    cm = ChunkedMatrix(chunks, n, X.n_features,
                       perm_cols=np.asarray(ladder.perm_cols),
                       inv_perm=np.asarray(ladder.inv_perm),
                       last_col_pos=ladder.last_col_pos)
    return make_chunked_batch(cm, np.asarray(padded.y)[:n_pad],
                              np.asarray(padded.weights)[:n_pad],
                              np.asarray(padded.offsets)[:n_pad])
