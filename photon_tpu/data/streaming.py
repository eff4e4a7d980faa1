"""Streaming ingestion: block-streamed Avro → bounded-memory GameData.

Reference parity: com.linkedin.photon.ml.data.avro.AvroDataReader reads
partitioned HDFS data through Spark — the dataset never materializes on one
host. The TPU-native analog here:

- `iter_game_chunks`: an iterator of GameData CHUNKS, assembled container
  block by container block (native C++ decoder when available, pure Python
  otherwise). Host arena stays bounded by ~2 chunks regardless of dataset
  size; multi-file inputs stream file after file.
- `build_index_maps_streaming`: the training-path first pass — feature-key →
  id maps built over the same block stream, nothing else materialized
  (reference: FeatureIndexingJob's offline pass).
- `stream_to_device`: chunks go STRAIGHT into their device placement — per
  device, a preallocated host buffer of exactly one shard (n/D rows) fills
  from the chunk stream, is device_put to its device, and is released; the
  global array is assembled with `jax.make_array_from_single_device_arrays`.
  Peak host memory is one device-shard + one chunk, so a dataset bounded by
  the MESH's total HBM (the 1B-row regime) ingests through a small host.

Chunks are container-block-aligned: a chunk closes at the first block
boundary at or after `chunk_rows`, so concatenating the chunks reproduces
the one-shot `read_game_data` result exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import numpy as np

from photon_tpu.data.avro_io import (
    AvroContainerReader,
    avro_paths,
    read_datum,
)
from photon_tpu.data.feature_bags import coo_to_matrix
from photon_tpu.data.index_map import INTERCEPT_KEY, IndexMap, feature_key
from photon_tpu.data.ingest import (
    GameDataConfig,
    normalize_bag,
    records_to_game_data,
)
from photon_tpu.game.dataset import GameData


def _open_reader(p) -> AvroContainerReader:
    """Open one Avro container with transient-IO retry/backoff
    (checkpoint.faults.retry_io): a shared-filesystem hiccup at ingest
    backs off and retries instead of killing an N-hour run. Mid-stream
    read errors still propagate — a container cannot be safely resumed
    mid-block, so the recovery unit is the (restartable) ingest pass."""
    from photon_tpu.checkpoint.faults import retry_io

    return retry_io(lambda: AvroContainerReader(p), site="avro_open")


def scan_row_counts(path, block_index: Optional[dict] = None) -> list:
    """Per-file record counts from the container block HEADERS only — no
    payload decompression, no record decode. Cheap enough to run before
    streaming so device buffers can be preallocated exactly.

    ``block_index`` (path -> [(offset, count, size)], the shape
    `scan_ingest` returns) answers from the already-scanned index without
    touching the files again."""
    if block_index is not None:
        return [sum(c for _, c, _ in block_index[str(p)])
                for p in avro_paths(path)]
    counts = []
    for p in avro_paths(path):
        rd = _open_reader(p)
        counts.append(sum(c for c, _ in rd.blocks(skip_payload=True)))
    return counts


@dataclasses.dataclass
class IngestScan:
    """Everything one cold-start pass over the containers learns: the
    frozen per-shard index maps AND the per-file block index (offsets /
    record counts / compressed sizes). `scan_ingest` folds row counting
    into the (retried) map-building scan, so preallocating device buffers
    and planning the ingest plane's decode tasks costs no extra pass —
    before round 14 the driver header-scanned every container twice."""

    index_maps: dict
    block_index: dict  # path -> [(offset, count, size)]

    @property
    def row_counts(self) -> list:
        return [sum(c for _, c, _ in blocks)
                for blocks in self.block_index.values()]

    @property
    def n_rows(self) -> int:
        return sum(self.row_counts)


def scan_ingest(path, config: GameDataConfig,
                index_maps: Optional[dict] = None) -> IngestScan:
    """ONE pass over the containers: build whatever frozen index maps are
    missing (exactly `build_index_maps_streaming` semantics) while
    recording the block index as a side effect of the same walk. When
    every map is prebuilt the pass degrades to the header-only scan (no
    payload decompress)."""
    index_maps = dict(index_maps or {})
    todo = {s: cfg for s, cfg in config.shards.items() if s not in index_maps}
    index_out: dict = {}
    if todo:
        from photon_tpu import telemetry

        with telemetry.span("ingest.build_index_maps", shards=sorted(todo)):
            index_maps = _build_index_maps_streaming(path, config,
                                                     index_maps, todo,
                                                     index_out=index_out)
    else:
        for p in avro_paths(path):
            index_out[str(p)] = _open_reader(p).block_index()
    return IngestScan(index_maps, index_out)


def _frozen_maps_or_raise(config: GameDataConfig, index_maps,
                          sparse_k=None, uniform_sparse_k=True) -> dict:
    index_maps = dict(index_maps or {})
    missing = [s for s in config.shards if s not in index_maps]
    if missing:
        raise ValueError(
            f"streaming ingestion needs frozen index maps for every shard "
            f"(missing {missing}); run build_index_maps_streaming (or the "
            "FeatureIndexingDriver) first — ids cannot be assigned "
            "on-the-fly once early chunks have already been emitted")
    unfrozen = [s for s in config.shards if not index_maps[s].frozen]
    if unfrozen:
        raise ValueError(
            f"streaming ingestion needs FROZEN index maps; {unfrozen} are "
            "mutable — fresh ids assigned mid-stream would shift column "
            "meanings between chunks")
    if uniform_sparse_k:
        for s, cfg in config.shards.items():
            if (index_maps[s].n_features > cfg.dense_threshold
                    and sparse_k is None):
                raise ValueError(
                    f"shard {s!r} is sparse (d={index_maps[s].n_features} > "
                    f"dense_threshold={cfg.dense_threshold}): streaming "
                    "needs a fixed sparse_k so every chunk's SparseRows "
                    "share one nnz width (per-chunk max widths would make "
                    "chunks non-concatenable)")
    return index_maps


def build_index_maps_streaming(
    path,
    config: GameDataConfig,
    index_maps: Optional[dict] = None,
) -> dict:
    """One bounded-memory pass assigning feature ids (first-seen order,
    bags in shard-config order — identical to ingest.build_index_map).
    Existing maps in `index_maps` are kept as-is. Runs through the native
    block decoder when it applies (a pure-Python pass over a 1B-row input
    would gate the fast chunk stream behind days of record decoding)."""
    from photon_tpu import telemetry

    index_maps = dict(index_maps or {})
    todo = {s: cfg for s, cfg in config.shards.items() if s not in index_maps}
    if not todo:
        return index_maps
    with telemetry.span("ingest.build_index_maps", shards=sorted(todo)):
        return _build_index_maps_streaming(path, config, index_maps, todo)


def _build_index_maps_streaming(path, config: GameDataConfig, index_maps,
                                todo, index_out: Optional[dict] = None
                                ) -> dict:
    # Native pass over EXACTLY the shards being built: a sub-config keeps
    # only their bags and consumes nothing else — every other field
    # (including the real response/entity columns and prebuilt shards'
    # bags) generic-skips inside the C++ VM. Before round 4 one prebuilt
    # map dropped this whole first pass to the per-record Python road.
    sub = dataclasses.replace(config, shards=todo, entity_fields=(),
                              response_field="\x00unconsumed",
                              offset_field="\x00unconsumed",
                              weight_field="\x00unconsumed")
    # index_out passed only when collecting (keeps the 2-arg signature
    # test spies replace)
    nat = (_build_maps_native(path, sub) if index_out is None
           else _build_maps_native(path, sub, index_out=index_out))
    if nat is not None:
        index_maps.update(nat)
        return index_maps
    building = {s: IndexMap() for s in todo}
    bag_names = sorted({b for cfg in todo.values() for b in cfg.bags})
    for p in avro_paths(path):
        import io as _io

        rd = _open_reader(p)
        entries = []
        for off, count, size, payload in rd.walk_blocks():
            entries.append((off, count, size))
            buf = _io.BytesIO(payload)
            for _ in range(count):
                rec = read_datum(buf, rd.schema)
                norm = {b: normalize_bag(rec.get(b)) for b in bag_names}
                for s, cfg in todo.items():
                    imap = building[s]
                    for bag in cfg.bags:
                        for ntv in norm[bag]:
                            imap.index_of(feature_key(ntv.name, ntv.term))
        if index_out is not None:
            index_out[str(p)] = entries
    for s, cfg in todo.items():
        if cfg.has_intercept:
            building[s].index_of(INTERCEPT_KEY)
        index_maps[s] = building[s].freeze()
    return index_maps


def _build_maps_native(path, config: GameDataConfig,
                       index_out: Optional[dict] = None) -> Optional[dict]:
    """Native block-decode pass in BUILD mode, per-block arrays discarded —
    id assignment mirrors read_game_data_native exactly (same stores, same
    first-seen order). None when the native path doesn't apply.
    ``index_out`` collects the block index of the same walk."""
    from photon_tpu import native
    from photon_tpu.data.native_ingest import compile_plan

    if not native.available():
        return None
    paths = avro_paths(path)
    if not paths:
        return None
    readers = [_open_reader(p) for p in paths]
    plan0 = compile_plan(readers[0].schema, config)
    if plan0 is None:
        return None
    for rd in readers[1:]:
        if compile_plan(rd.schema, config) != plan0:
            return None
    shard_names = list(config.shards)
    stores = [native.NativeIndexStore(capacity_hint=1024)
              for _ in shard_names]
    from photon_tpu.data.native_ingest import build_decode_plan

    plan = build_decode_plan(plan0, config, shard_names)
    for rd in readers:
        entries = []
        for off, count, size, payload in rd.walk_blocks():
            entries.append((off, count, size))
            dec = native.decode_block(payload, count, 0, plan, stores, True)
            if not dec.ok:
                raise ValueError(f"{rd.path}: malformed Avro block")
            dec.free()
        if index_out is not None:
            index_out[str(rd.path)] = entries
    out = {}
    for si, s in enumerate(shard_names):
        cfg = config.shards[s]
        imap = IndexMap({k: i for i, k in
                         enumerate(stores[si].keys_in_order())},
                        frozen=True, has_intercept=cfg.has_intercept)
        if cfg.has_intercept:
            imap.index_of(INTERCEPT_KEY)  # no-op id; records metadata
        out[s] = imap
    return out




@dataclasses.dataclass
class ChunkStream:
    """Iterator state + arena accounting for one streaming read.

    `peak_arena_bytes` tracks the maximum bytes of numpy buffers the
    assembler held live at any point — the test contract is that it stays
    ≤ ~2 chunks regardless of how many files/rows stream through.
    """

    config: GameDataConfig
    index_maps: dict
    chunk_rows: int
    sparse_k: Optional[int]
    peak_arena_bytes: int = 0
    # With config.allow_missing_response: True once ANY streamed record
    # lacked a response (evaluator gating), and the per-row presence mask
    # of the MOST RECENTLY YIELDED chunk (the scoring driver reads it
    # right after next() to null out labels row by row).
    saw_missing_response: bool = False
    last_response_mask: Optional[np.ndarray] = None
    # Per-row presence of each OPTIONAL entity field in the most recently
    # yielded chunk ({field: (n,) bool}): chunk assembly folds a missing id
    # to "" for the column arrays, which conflates it with a legitimate
    # empty-string id — consumers that must tell the two apart (the
    # scoring driver's nullable ScoredItemAvro.uid) read this instead.
    last_entity_presence: Optional[dict] = None
    # uniform_sparse_k=False only: quantize each chunk's own SparseRows
    # nnz width up to a power of two, so the per-chunk device programs
    # compile a handful of shapes instead of one per distinct raggedness.
    quantize_k: bool = False

    def _note(self, live_bytes: int) -> None:
        if live_bytes > self.peak_arena_bytes:
            self.peak_arena_bytes = live_bytes


def _chunk_nbytes(data: GameData) -> int:
    """Numeric-buffer bytes of one assembled chunk (entity-id object arrays
    are host pointers either way and excluded)."""
    from photon_tpu.data.matrix import SparseRows

    total = data.y.nbytes + data.weights.nbytes + data.offsets.nbytes
    for X in data.shards.values():
        if isinstance(X, SparseRows):
            total += X.indices.nbytes + X.values.nbytes
        else:
            total += X.nbytes
    return int(total)


def iter_game_chunks(
    path,
    config: GameDataConfig,
    index_maps: dict,
    chunk_rows: int = 65536,
    sparse_k: Optional[int] = None,
    use_native: Optional[bool] = None,
    uniform_sparse_k: bool = True,
) -> tuple[ChunkStream, Iterator[GameData]]:
    """(stream handle, iterator of GameData chunks) over one file or a
    directory of .avro files. Needs frozen index maps for EVERY shard
    (training: build them with `build_index_maps_streaming` first;
    scoring: reuse the training maps — reference behavior).

    Chunks close at container-block boundaries, so sizes are
    ≥ `chunk_rows` (except the last) and concatenation equals the one-shot
    read. `use_native` as in ingest.read_game_data.

    `uniform_sparse_k=False` lifts the fixed-`sparse_k` requirement for
    sparse shards: each chunk gets its own max-nnz width. Only for
    consumers that process chunks INDEPENDENTLY (the scoring driver) —
    ragged widths make chunks non-concatenable.
    """
    index_maps = _frozen_maps_or_raise(config, index_maps, sparse_k,
                                       uniform_sparse_k)
    stream = ChunkStream(config, index_maps, chunk_rows, sparse_k,
                         quantize_k=(not uniform_sparse_k
                                     and sparse_k is None))
    if use_native is not False:
        # Availability / plannability checked EAGERLY (before the first
        # next()), so a forced use_native=True fails at the call site.
        it = _native_chunks(path, stream)
        if it is not None:
            return stream, it
        if use_native:
            raise RuntimeError(
                "native streaming requested but unavailable (toolchain "
                "missing or schema not plannable)")
    return stream, _python_chunks(path, stream)


def _quantize_widths(stream: ChunkStream, data: GameData) -> GameData:
    """Pad each SparseRows shard's nnz width up to the next power of two
    (stream.quantize_k; padding slots are (index 0, value 0) no-ops)."""
    from photon_tpu.data.matrix import SparseRows, next_pow2

    if not stream.quantize_k:
        return data
    shards = {}
    changed = False
    for s, X in data.shards.items():
        if isinstance(X, SparseRows):
            k = X.indices.shape[1]
            kq = next_pow2(max(k, 1))
            if kq != k:
                pad = ((0, 0), (0, kq - k))
                X = SparseRows(np.pad(np.asarray(X.indices), pad),
                               np.pad(np.asarray(X.values), pad),
                               X.n_features)
                changed = True
        shards[s] = X
    if not changed:
        return data
    return GameData(data.y, data.weights, data.offsets, shards,
                    data.entity_ids)


def _python_chunks(path, stream: ChunkStream) -> Iterator[GameData]:
    """Pure-Python fallback: records buffered per chunk, then the standard
    records→GameData assembly with the frozen maps. Chunks close at
    container-BLOCK boundaries, exactly like the native path, so chunking
    is identical whichever decoder runs."""
    return _python_chunks_from_readers(
        [_open_reader(p) for p in avro_paths(path)], stream)


def _python_chunks_from_readers(readers, stream: ChunkStream
                                ) -> Iterator[GameData]:
    """The reader-level body of `_python_chunks`: any AvroContainerReader-
    shaped sources (including the ingest plane's per-worker block slices)
    stream through the SAME record buffering and assembly, so a worker's
    chunk is bit-identical to the serial stream's by construction."""
    import io

    buf: list = []

    def flush():
        from photon_tpu.data.ingest import entity_id_or_none, numeric_or_none

        if stream.config.allow_missing_response:
            f = stream.config.response_field
            # numeric_or_none, not a bare None check: a populated
            # NON-numeric union branch reads as absent on both decoders —
            # the mask must agree or such rows would enter the metric
            # accumulators as labeled y=0 examples on this path only
            mask = np.asarray(
                [numeric_or_none(r.get(f)) is not None for r in buf])
            stream.last_response_mask = mask
            if not mask.all():
                stream.saw_missing_response = True
        stream.last_entity_presence = {
            e: np.asarray([entity_id_or_none(r.get(e)) is not None
                           for r in buf])
            for e in stream.config.optional_entity_fields}
        data, _ = records_to_game_data(buf, stream.config, stream.index_maps,
                                       stream.sparse_k, host=True)
        data = _quantize_widths(stream, data)
        # the record buffer and the assembled chunk coexist briefly
        stream._note(2 * _chunk_nbytes(data))
        buf.clear()
        return data

    for rd in readers:
        for count, payload in rd.blocks():
            b = io.BytesIO(payload)
            buf.extend(read_datum(b, rd.schema) for _ in range(count))
            if len(buf) >= stream.chunk_rows:
                yield flush()
    if buf:
        yield flush()


def _native_chunks(path, stream: ChunkStream):
    """C++ block decoder path; None when unavailable/unplannable."""
    from photon_tpu import native

    if not native.available():
        return None
    paths = avro_paths(path)
    if not paths:
        return None
    return _native_chunks_from_readers(
        [_open_reader(p) for p in paths], stream)


def _native_chunks_from_readers(readers, stream: ChunkStream):
    """The reader-level body of `_native_chunks` (shared with the ingest
    plane's per-worker block slices); None when the schema is not
    native-plannable."""
    from photon_tpu import native
    from photon_tpu.data.native_ingest import compile_plan

    if not native.available() or not readers:
        return None
    config = stream.config
    plan0 = compile_plan(readers[0].schema, config)
    if plan0 is None:
        return None
    for rd in readers[1:]:
        if compile_plan(rd.schema, config) != plan0:
            return None  # schema drift across files: caller falls back

    from photon_tpu.data.native_ingest import build_decode_plan, frozen_stores

    shard_names = list(config.shards)
    stores = frozen_stores(stream.index_maps, shard_names)
    plan = build_decode_plan(plan0, config, shard_names)

    optional_ents = set(config.optional_entity_fields)

    def generator():
        ys, offs, wts, ysets = [], [], [], []
        coos = [[] for _ in shard_names]
        ents = [[] for _ in config.entity_fields]
        rows_in_chunk = 0
        live = 0

        def assemble() -> GameData:
            nonlocal rows_in_chunk, live
            n = rows_in_chunk
            if config.allow_missing_response:
                stream.last_response_mask = np.concatenate(ysets)
                ysets.clear()
            y = np.concatenate(ys).astype(np.float32)
            offsets = np.concatenate(offs).astype(np.float32)
            weights = np.concatenate(wts).astype(np.float32)
            shards = {}
            for si, s in enumerate(shard_names):
                cfg = config.shards[s]
                imap = stream.index_maps[s]
                rows = np.concatenate([c[0] for c in coos[si]])
                cols = np.concatenate([c[1] for c in coos[si]]).astype(
                    np.int64)
                vals = np.concatenate([c[2] for c in coos[si]])
                if cfg.has_intercept:
                    rows = np.concatenate(
                        [rows, np.arange(n, dtype=np.int64)])
                    cols = np.concatenate(
                        [cols, np.full(n, imap.intercept_id, np.int64)])
                    vals = np.concatenate([vals, np.ones(n, np.float32)])
                shards[s] = coo_to_matrix(rows, cols, vals, n,
                                          imap.n_features,
                                          cfg.dense_threshold,
                                          k=stream.sparse_k, host=True)
            ids = {}
            presence: dict = {}
            for e_i, e in enumerate(config.entity_fields):
                col = np.concatenate(ents[e_i])
                if e in optional_ents:
                    presence[e] = np.asarray([v is not None for v in col])
                if any(v is None for v in col):
                    if e not in optional_ents:
                        raise ValueError(f"records missing entity id {e!r}")
                    col = np.asarray(["" if v is None else v for v in col],
                                     object)
                ids[e] = np.asarray([str(v) for v in col])
            stream.last_entity_presence = presence
            out = _quantize_widths(
                stream, GameData(y, weights, offsets, shards, ids))
            # block pieces + the assembled chunk coexist briefly
            stream._note(live + _chunk_nbytes(out))
            ys.clear(); offs.clear(); wts.clear()                  # noqa: E702
            for c in coos:
                c.clear()
            for e in ents:
                e.clear()
            rows_in_chunk = 0
            live = 0
            return out

        for rd in readers:
            for count, payload in rd.blocks():
                dec = native.decode_block(payload, count, rows_in_chunk,
                                          plan, stores, False)
                if not dec.ok:
                    raise ValueError(f"{rd.path}: malformed Avro block")
                y, y_set = dec.scalars(0)
                if not y_set.all():
                    if not config.allow_missing_response:
                        raise ValueError(
                            f"{rd.path}: record missing response")
                    stream.saw_missing_response = True
                    y = np.where(y_set, y, 0.0)
                if config.allow_missing_response:
                    ysets.append(y_set)
                off, off_set = dec.scalars(1)
                wt, wt_set = dec.scalars(2)
                ys.append(y)
                offs.append(np.where(off_set, off, 0.0))
                wts.append(np.where(wt_set, wt, 1.0))
                live += y.nbytes * 3
                for si in range(len(shard_names)):
                    c = dec.coo(si)
                    coos[si].append(c)
                    live += sum(a.nbytes for a in c)
                for e in range(len(config.entity_fields)):
                    ents[e].append(dec.entities(e))
                dec.free()
                rows_in_chunk += count
                if rows_in_chunk >= stream.chunk_rows:
                    yield assemble()
        if rows_in_chunk:
            yield assemble()

    return generator()


def stream_to_host(
    path,
    config: GameDataConfig,
    index_maps: dict,
    chunked_shards=(),
    chunk_rows: int = 65536,
    objective_chunk_rows: int = 1 << 20,
    sparse_k: Optional[int] = None,
    use_native: Optional[bool] = None,
    feature_dtype=None,
    chunk_hook=None,
    n_rows: Optional[int] = None,
    workers: int = 0,
    cache_dir=None,
    block_index: Optional[dict] = None,
) -> tuple[GameData, int]:
    """Stream a dataset into HOST-RESIDENT form for the out-of-HBM
    streamed-objective solve (drivers.train auto-trips here when the
    device-resident estimate exceeds the POOLED HBM budget — per-chip
    budget × mesh size).

    ``workers``/``cache_dir``/``block_index`` engage the round-14 ingest
    plane (data.ingest_plane.open_chunk_source): ``workers > 0`` decodes
    container blocks in a sharded worker pool (chunk order preserved
    bit-for-bit; a dead worker degrades that chunk to in-process decode),
    ``cache_dir`` opens/commits the decode-once columnar chunk cache, and
    ``block_index`` reuses `scan_ingest`'s block offsets so the cold
    start touches each container's headers once.

    Shards named in `chunked_shards` are assembled as
    data.dataset.ChunkedMatrix — uniform `objective_chunk_rows`-row host
    chunks the streamed solvers re-upload pass by pass (on a single chip,
    or row-sharded across a whole mesh via `ChunkedBatch.iter_device(
    mesh=...)`, each device fed its own slice of every chunk), so HBM
    holds O(chunk + solver state) per device instead of O(dataset). Every other shard and
    the scalar columns assemble as full host numpy (the GAME layer
    device-puts what it needs — random-effect buckets must be resident).

    Chunk hooks / sparse-k / native-decoder semantics match
    stream_to_device; `feature_dtype` casts feature values of EVERY shard
    (chunked ones at buffer fill, resident ones at concat). Host memory
    holds the whole dataset — this mode trades host RAM (cheap, big) for
    HBM (scarce), exactly as the reference trades executor memory for
    HDFS-backed partitions.

    Returns (GameData, n_real); GameData.n == n_real (only the
    ChunkedMatrix pads internally, weight-0-masked by the solve batches).
    """
    from photon_tpu.data.dataset import ChunkedMatrix
    from photon_tpu.data.matrix import SparseRows

    index_maps = _frozen_maps_or_raise(config, index_maps, sparse_k)
    chunked_shards = set(chunked_shards)
    unknown = chunked_shards - set(config.shards)
    if unknown:
        raise ValueError(f"chunked_shards not in config: {sorted(unknown)}")
    if n_rows is not None:
        n_real = int(n_rows)
    else:
        n_real = sum(scan_row_counts(path, block_index=block_index))
    c_rows = max(int(objective_chunk_rows), 1)

    dense_shards = {s: index_maps[s].n_features <= cfg.dense_threshold
                    for s, cfg in config.shards.items()}
    f_dtype = np.float32 if feature_dtype is None else feature_dtype
    for s in chunked_shards:
        if not dense_shards[s] and sparse_k is None:
            raise ValueError(
                f"chunked shard {s!r} is sparse: pass a fixed sparse_k so "
                "every chunk shares one nnz width")

    def alloc(s):
        d = index_maps[s].n_features
        if dense_shards[s]:
            return np.zeros((c_rows, d), f_dtype)
        return (np.zeros((c_rows, sparse_k), np.int32),
                np.zeros((c_rows, sparse_k), f_dtype))

    bufs = {s: alloc(s) for s in chunked_shards}
    done_chunks: dict = {s: [] for s in chunked_shards}
    filled = 0  # rows filled in the current uniform chunk buffers

    scal_parts: dict = {k: [] for k in ("y", "weights", "offsets")}
    res_parts: dict = {s: [] for s in config.shards if s not in chunked_shards}
    entity_cols: dict = {e: [] for e in config.entity_fields}

    def flush():
        nonlocal bufs, filled
        for s in chunked_shards:
            done_chunks[s].append(bufs[s])
        bufs = {s: alloc(s) for s in chunked_shards}
        filled = 0

    from photon_tpu import telemetry
    from photon_tpu.data.ingest_plane import open_chunk_source

    stream, chunks = open_chunk_source(path, config, index_maps,
                                       chunk_rows=chunk_rows,
                                       sparse_k=sparse_k,
                                       use_native=use_native,
                                       workers=workers,
                                       cache_dir=cache_dir,
                                       block_index=block_index)
    row = 0
    for chunk in chunks:
        telemetry.count("ingest.chunks")
        telemetry.count("ingest.rows", chunk.n)
        if chunk_hook is not None:
            chunk_hook(chunk)
        scal_parts["y"].append(np.asarray(chunk.y))
        scal_parts["weights"].append(np.asarray(chunk.weights))
        scal_parts["offsets"].append(np.asarray(chunk.offsets))
        for e in config.entity_fields:
            entity_cols[e].append(np.asarray(chunk.entity_ids[e]))
        for s in res_parts:
            X = chunk.shards[s]
            if isinstance(X, SparseRows):
                res_parts[s].append((np.asarray(X.indices),
                                     np.asarray(X.values).astype(f_dtype)))
            else:
                res_parts[s].append(np.asarray(X).astype(f_dtype))
        host_mat = {}
        for s in chunked_shards:
            X = chunk.shards[s]
            host_mat[s] = (np.asarray(X) if dense_shards[s]
                           else (np.asarray(X.indices), np.asarray(X.values)))
        c0, n_c = 0, chunk.n
        while c0 < n_c:
            take = min(n_c - c0, c_rows - filled)
            sl = slice(c0, c0 + take)
            dst = slice(filled, filled + take)
            for s in chunked_shards:
                if dense_shards[s]:
                    bufs[s][dst] = host_mat[s][sl].astype(f_dtype)
                else:
                    ind, val = bufs[s]
                    h_ind, h_val = host_mat[s]
                    k_c = h_ind.shape[1]
                    ind[dst, :k_c] = h_ind[sl]
                    val[dst, :k_c] = h_val[sl].astype(f_dtype)
            filled += take
            c0 += take
            row += take
            if filled == c_rows:
                flush()
    if filled or (chunked_shards and not done_chunks[next(iter(
            chunked_shards))]):
        flush()  # partial tail chunk (pad rows are all-zero → weight 0)

    def concat(parts, width=None, dtype=np.float32):
        if parts:
            return np.concatenate(parts)
        shape = (0,) if width is None else (0, width)
        return np.zeros(shape, dtype)

    shards: dict = {}
    for s in config.shards:
        d = index_maps[s].n_features
        if s in chunked_shards:
            cs = tuple(c if dense_shards[s] else SparseRows(c[0], c[1], d)
                       for c in done_chunks[s])
            shards[s] = ChunkedMatrix(cs, n_real, d)
        elif dense_shards[s]:
            shards[s] = concat(res_parts[s], width=d, dtype=f_dtype)
        else:
            k = sparse_k if sparse_k is not None else 1
            ind = concat([p[0] for p in res_parts[s]], width=k,
                         dtype=np.int32)
            val = concat([p[1] for p in res_parts[s]], width=k,
                         dtype=f_dtype)
            shards[s] = SparseRows(ind, val, d)

    ids = {e: (np.concatenate([np.asarray(c, dtype=np.str_) for c in cols])
               if cols else np.zeros(0, dtype="U1"))
           for e, cols in entity_cols.items()}
    data = GameData(concat(scal_parts["y"]), concat(scal_parts["weights"]),
                    concat(scal_parts["offsets"]), shards, ids)
    return data, n_real


def _local_task_chunks(tasks, config, index_maps, sparse_k, use_native,
                       local_rows):
    """The ``local_only`` chunk source: yields ``(chunk, n_rows)`` for
    tasks whose global row range overlaps any of this process's
    ``local_rows`` ``[lo, hi)`` intervals (decoded in-process through the
    exact serial assembly path — bit-identical to the serial chunk at
    that position) and ``(None, n_rows)`` skip markers for everything
    else, whose container blocks are never read. Tasks come from
    `ingest_plane.plan_chunk_tasks`, so chunk boundaries — and therefore
    every decoded chunk's contents — match the serial stream exactly."""
    from photon_tpu.data.ingest_plane import _decode_task, _DecodeState

    state = _DecodeState(config, index_maps, sparse_k, use_native)
    r0 = 0
    for task in tasks:
        r1 = r0 + task.n_rows
        if any(r0 < hi and r1 > lo for lo, hi in local_rows):
            chunk = _decode_task(state, task)[0]
            yield chunk, chunk.n
        else:
            yield None, task.n_rows
        r0 = r1


def stream_to_device(
    path,
    config: GameDataConfig,
    index_maps: dict,
    mesh=None,
    chunk_rows: int = 65536,
    sparse_k: Optional[int] = None,
    use_native: Optional[bool] = None,
    feature_dtype=None,
    chunk_hook=None,
    n_rows: Optional[int] = None,
    prefetch=2,
    _local_mask=None,
    workers: int = 0,
    cache_dir=None,
    block_index: Optional[dict] = None,
    local_only: bool = False,
) -> tuple[GameData, int]:
    """Stream a dataset STRAIGHT into its device placement.

    `n_rows`: the dataset's total row count, when the caller already ran
    `scan_row_counts` (the training driver's auto-streaming check does) —
    skips a second pass over every container-block header. `block_index`
    (from `scan_ingest`) serves the same purpose AND hands the ingest
    plane its decode-task boundaries; `workers`/`cache_dir` as in
    `stream_to_host`.

    `prefetch`: how many per-device shard uploads may be in flight at once
    (device_put is asynchronous; the default 2 keeps the classic double
    buffer — the next shard fills while the previous one transfers). Each
    completed shard's transfer is awaited once the window fills, bounding
    how far the host can run ahead of the link. An
    `data.ingest_plane.AdaptivePrefetch` controller may be passed instead
    of an int: the window then WIDENS while uploads actually stall, up to
    the controller's byte budget (stall-driven prefetch, round 14).

    With a mesh: rows are contiguously sharded over all mesh axes; per
    device a preallocated host buffer of exactly one shard fills from the
    chunk stream, is device_put onto ITS device, and is released — host
    peak = one shard + one chunk, not the dataset. Rows pad (weight 0) to a
    device multiple, entity ids pad with "". Without a mesh: one
    preallocated buffer and a single transfer.

    MULTI-HOST safe: only shards for THIS process's addressable devices
    are filled and device_put (rows belonging to other processes stream
    past without materializing), and the global array assembles from the
    local shards via `make_array_from_single_device_arrays` — every
    process must run the same stream_to_device call, as with any jax
    multi-controller collective. Entity-id columns stay host-side and
    GLOBAL on every process (they factorize on host for entity bucketing).

    ``local_only=True`` (round 17, the per-process ingest split) goes one
    step further: chunk tasks whose row ranges fall ENTIRELY in other
    processes' device slots are never decoded — their container blocks
    are never even read (`_BlockSliceReader` random-accesses only the
    decoded tasks' block entries), so each process's disk + decode work
    is its own row partition, exactly the RDD-partition role of the
    reference's executors. Requires a mesh; boundary chunks overlapping a
    local slot decode in full (their non-local rows still stream past).
    Caveats: entity-id columns of skipped chunks fill with "" (GAME
    entity bucketing needs the default global decode), `chunk_hook` runs
    only on the chunks this process decodes, and ``cache_dir`` is
    refused (a partial decode must never commit a global cache entry).

    `feature_dtype` (e.g. jnp.bfloat16) casts feature VALUES as chunks
    arrive — the storage-dtype path of data.dataset.cast_features without a
    full-size intermediate.

    `chunk_hook(chunk)` runs on every GameData chunk BEFORE it fills device
    buffers — the bounded-memory seam for per-chunk validation and
    mergeable statistics (the drivers validate and summarize here instead
    of reading the assembled dataset back off device).

    Returns (GameData with device-resident y/weights/offsets/shards, n_real)
    — entity ids stay host-side numpy (they factorize on host). n_real is
    the unpadded row count.
    """
    import jax

    from photon_tpu.data.matrix import SparseRows

    index_maps = _frozen_maps_or_raise(config, index_maps, sparse_k)
    local_tasks = None
    if local_only and mesh is not None:
        if cache_dir is not None:
            raise ValueError(
                "stream_to_device(local_only=True) cannot tee the chunk "
                "cache: this process decodes only its own block ranges, "
                "and a partial decode must never commit a global cache "
                "entry — pre-build the cache with a full decode, or drop "
                "local_only")
        from photon_tpu.data.ingest_plane import (plan_chunk_tasks,
                                                  scan_or_reuse_block_index)

        block_index = scan_or_reuse_block_index(path, block_index)
        local_tasks = plan_chunk_tasks(block_index, chunk_rows)
    if n_rows is not None:
        n_real = int(n_rows)
    elif local_tasks is not None:
        n_real = sum(t.n_rows for t in local_tasks)
    else:
        n_real = sum(scan_row_counts(path, block_index=block_index))
    n_dev = int(mesh.devices.size) if mesh is not None else 1
    from photon_tpu.parallel.mesh import pad_to_multiple

    n_pad = pad_to_multiple(max(n_real, 1), n_dev)
    n_local = n_pad // n_dev
    devices = (list(mesh.devices.reshape(-1)) if mesh is not None
               else [None])
    proc = jax.process_index()
    # _local_mask is the single-process test seam for the multi-host slot
    # arithmetic (a CPU test cannot make real devices non-addressable)
    local_mask = ([d is None or d.process_index == proc for d in devices]
                  if _local_mask is None else list(_local_mask))
    if not any(local_mask):
        raise ValueError(
            f"stream_to_device: no device in the mesh is addressable from "
            f"process {proc} — every process of a multi-host program must "
            "own at least one mesh device (run the same call on each "
            "process)")

    # Per-shard layout decided ONCE from the frozen maps (chunk-independent).
    dense_shards = {s: index_maps[s].n_features <= cfg.dense_threshold
                    for s, cfg in config.shards.items()}
    f_dtype = np.float32 if feature_dtype is None else feature_dtype
    SCALARS = ("y", "weights", "offsets")

    # Scalar columns and user-named shards live in SEPARATE namespaces —
    # a shard literally named "y"/"weights"/"offsets" must not collide.
    def alloc_local():
        scal = {k: np.zeros(n_local, np.float32) for k in SCALARS}
        mats = {}
        for s in config.shards:
            d = index_maps[s].n_features
            if dense_shards[s]:
                mats[s] = np.zeros((n_local, d), f_dtype)
            else:
                mats[s] = (np.zeros((n_local, sparse_k), np.int32),
                           np.zeros((n_local, sparse_k), f_dtype))
        return scal, mats

    scal_parts: dict = {k: [] for k in SCALARS}
    mat_parts: dict = {s: [] for s in config.shards}
    entity_cols: dict = {e: [] for e in config.entity_fields}

    dev_i = 0  # global device-slot cursor (advances on every slot)
    in_flight: list = []  # shipped shards whose transfer isn't awaited yet
    # prefetch: an int (fixed window) or a stall-driven controller
    # (data.ingest_plane.AdaptivePrefetch) whose depth widens while the
    # awaits below actually block, bounded by its byte budget.
    ctl = prefetch if hasattr(prefetch, "observe_wait") else None
    static_depth = 2 if ctl is not None else max(int(prefetch), 1)
    shard_nbytes = 0

    def _depth() -> int:
        return max(int(ctl.depth), 1) if ctl is not None else static_depth

    def ship(buf):
        """device_put one completed shard onto its device (asynchronous; at
        most `prefetch` shard transfers run ahead before the oldest is
        awaited); a None buf is a slot another process owns — just advance
        past it."""
        nonlocal dev_i, shard_nbytes
        if buf is not None:
            import time as _time

            scal, mats = buf
            if ctl is not None and not shard_nbytes:
                shard_nbytes = sum(v.nbytes for v in scal.values()) + sum(
                    (sum(a.nbytes for a in v) if isinstance(v, tuple)
                     else v.nbytes) for v in mats.values())
            dev = devices[dev_i] if mesh is not None else None
            shipped = []
            for k in SCALARS:
                scal_parts[k].append(jax.device_put(scal[k], dev))
                shipped.append(scal_parts[k][-1])
            for s, v in mats.items():
                if isinstance(v, tuple):
                    mat_parts[s].append(tuple(jax.device_put(a, dev)
                                              for a in v))
                else:
                    mat_parts[s].append(jax.device_put(v, dev))
                shipped.append(mat_parts[s][-1])
            in_flight.append(shipped)
            telemetry.count("ingest.device_shards")
            if len(in_flight) > _depth():
                t0 = _time.perf_counter()
                jax.block_until_ready(in_flight.pop(0))
                if ctl is not None:
                    ctl.observe_wait(_time.perf_counter() - t0, shard_nbytes)
        dev_i += 1

    def alloc_slot():
        """Fill buffer for device slot `dev_i`; None when that slot belongs
        to another process (its rows stream past without materializing)."""
        return alloc_local() if local_mask[min(dev_i, n_dev - 1)] else None

    buf = alloc_slot()
    filled = 0  # rows filled in the current local buffer
    row = 0     # global row cursor

    from photon_tpu import telemetry
    from photon_tpu.data.ingest_plane import open_chunk_source

    if local_tasks is not None:
        local_rows = [(j * n_local, (j + 1) * n_local)
                      for j in range(n_dev) if local_mask[j]]
        chunk_iter = _local_task_chunks(local_tasks, config, index_maps,
                                        sparse_k, use_native, local_rows)
    else:
        stream, chunks = open_chunk_source(path, config, index_maps,
                                           chunk_rows=chunk_rows,
                                           sparse_k=sparse_k,
                                           use_native=use_native,
                                           workers=workers,
                                           cache_dir=cache_dir,
                                           block_index=block_index)
        chunk_iter = ((c, c.n) for c in chunks)
    for chunk, n_c in chunk_iter:
        if chunk is None:
            # a skipped (non-local) chunk: rows advance through slots this
            # process does not own — buf stays None for all of them, so
            # the fill loop below degenerates to cursor arithmetic; only
            # the entity-id columns (host-global by convention) need a
            # placeholder column.
            telemetry.count("ingest.chunks_skipped")
            for e in config.entity_fields:
                entity_cols[e].append(np.full(n_c, "", dtype="U1"))
            c0 = 0
            while c0 < n_c:
                take = min(n_c - c0, n_local - filled)
                filled += take
                c0 += take
                row += take
                if filled == n_local and mesh is not None:
                    ship(buf)
                    buf = alloc_slot() if row < n_real else None
                    filled = 0
            continue
        telemetry.count("ingest.chunks")
        telemetry.count("ingest.rows", chunk.n)
        if chunk_hook is not None:
            chunk_hook(chunk)
        c0 = 0
        for e in config.entity_fields:
            entity_cols[e].append(np.asarray(chunk.entity_ids[e]))
        # Chunks are host numpy end to end (the assemblers build with
        # coo_to_matrix(host=True)), so these np.asarray calls are no-ops —
        # kept as a type normalization for any GameData-shaped source.
        host_scal = {"y": np.asarray(chunk.y),
                     "weights": np.asarray(chunk.weights),
                     "offsets": np.asarray(chunk.offsets)}
        host_mat = {}
        for s in config.shards:
            X = chunk.shards[s]
            host_mat[s] = (np.asarray(X) if dense_shards[s]
                           else (np.asarray(X.indices), np.asarray(X.values)))
        while c0 < n_c:
            take = min(n_c - c0, n_local - filled)
            if buf is not None:  # a None buf = another process's slot
                sl = slice(c0, c0 + take)
                dst = slice(filled, filled + take)
                scal, mats = buf
                for k in SCALARS:
                    scal[k][dst] = host_scal[k][sl]
                for s in config.shards:
                    if dense_shards[s]:
                        mats[s][dst] = host_mat[s][sl].astype(f_dtype)
                    else:
                        ind, val = mats[s]
                        h_ind, h_val = host_mat[s]
                        k_c = h_ind.shape[1]
                        ind[dst, :k_c] = h_ind[sl]
                        val[dst, :k_c] = h_val[sl].astype(f_dtype)
            filled += take
            c0 += take
            row += take
            if filled == n_local and mesh is not None:
                ship(buf)
                buf = alloc_slot() if row < n_real else None
                filled = 0

    if mesh is not None:
        if filled:  # partial tail shard (None when the slot isn't ours)
            ship(buf)
        # remaining devices get all-zero (weight-0) shards; slots owned by
        # other processes just advance
        while dev_i < n_dev:
            ship(alloc_slot())

        from jax.sharding import NamedSharding, PartitionSpec as P

        axes = tuple(mesh.axis_names)

        def assemble(parts):
            if isinstance(parts[0], tuple):
                return tuple(assemble([p[i] for p in parts])
                             for i in range(len(parts[0])))
            shape = (n_pad,) + parts[0].shape[1:]
            spec = P(axes) if parts[0].ndim == 1 else P(axes, None)
            return jax.make_array_from_single_device_arrays(
                shape, NamedSharding(mesh, spec), parts)
    else:
        if filled or not scal_parts["y"]:
            ship(buf)

        def assemble(parts):
            return (tuple(parts[0]) if isinstance(parts[0], tuple)
                    else parts[0])

    scalars = {k: assemble(v) for k, v in scal_parts.items()}
    shards = {}
    for s in config.shards:
        v = assemble(mat_parts[s])
        if dense_shards[s]:
            shards[s] = v
        else:
            shards[s] = SparseRows(v[0], v[1], index_maps[s].n_features)

    ids = {}
    for e in config.entity_fields:
        # chunk producers already emit str ndarrays; concatenate promotes
        # to the widest str dtype, no per-row Python loop (this runs over
        # the FULL row count — the one place a Python walk would cost
        # minutes in the 1B-row regime)
        cols = entity_cols[e] or [np.zeros(0, dtype="U1")]
        if n_pad > n_real:
            cols = cols + [np.full(n_pad - n_real, "", dtype="U1")]
        ids[e] = np.concatenate([np.asarray(c, dtype=np.str_) for c in cols])

    data = GameData(scalars["y"], scalars["weights"], scalars["offsets"],
                    shards, ids)
    return data, n_real
