"""GAME datasets: fixed-effect batches and entity-bucketed random-effect blocks.

Reference parity: com.linkedin.photon.ml.data.{FixedEffectDataset,
RandomEffectDataset, GameDatum}. The reference partitions random-effect data
by entity id across Spark executors and trains one Breeze solver per entity.
On TPU the same structure becomes dense batched tensors:

- entities are bucketed into a handful of block shapes (m rows × w solve
  width) planned by BYTES (`plan_buckets`): each entity starts in the cell
  of its power-of-two row count and, under an INDEX_MAP projection, the
  width class of the features its own active rows touch; cells are merged,
  cheapest added padding first, while a shape saves less memory than it is
  worth, so a handful of distinct XLA programs covers every entity size
  and no bucket is padded to a width only its widest entity needs;
- within a bucket, entities are stacked into (E, m, …) arrays — the per-entity
  solver is `vmap`'d over the leading axis, and that axis is shardable across
  the mesh's ``data`` axis, which is how per-entity training scales across
  chips (the Spark-partition analog);
- rows are padded with weight 0, so every reduction ignores padding.

The reference's active/passive split (`numActiveDataPointsUpperBound`,
RandomEffectDataset.activeData/passiveData) maps to `active_cap`: each
entity's first `active_cap` rows (after an optional shuffle) are trained on
and live in the blocks; the flat per-row layout kept alongside covers every
row. Who scores what: the one-dispatch update
(`random_effect.fused_update_program`) scores an ACTIVE row from its bucket
— the block times the bucket's fresh solution — and reads the (E, d) table
only for the PASSIVE rows (`RandomEffectDataset.scoring_plan`); every other
scorer (the block loop's `RandomEffectCoordinate.score`, validation,
`RandomEffectModel.score`, serving) scores all its rows, active and
passive, from the table via the flat layout.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu import telemetry
from photon_tpu.data.dataset import (ChunkedMatrix, GLMBatch, make_batch,
                                     make_chunked_batch)
from photon_tpu.data.matrix import (BlockedEllRows, HybridRows, Matrix,
                                    PermutedHybridRows, SparseRows)


@dataclasses.dataclass(frozen=True)
class GameData:
    """Host-side GAME training/scoring data: shared response + per-shard
    design matrices + per-coordinate entity ids.

    Reference: the GameDatum 4-tuple (response, offset, weight, feature
    shards) plus per-entity-type id columns.
    """

    y: np.ndarray  # (n,)
    weights: np.ndarray  # (n,)
    offsets: np.ndarray  # (n,) base offsets
    shards: dict  # feature-shard name -> Matrix (n rows)
    entity_ids: dict  # entity-type name -> (n,) raw ids (any hashable dtype)

    @property
    def n(self) -> int:
        return int(self.y.shape[0])

    @staticmethod
    def build(y, shards, entity_ids=None, weights=None, offsets=None) -> "GameData":
        y = np.asarray(y, np.float32)
        n = y.shape[0]
        weights = (
            np.ones(n, np.float32) if weights is None else np.asarray(weights, np.float32)
        )
        offsets = (
            np.zeros(n, np.float32) if offsets is None else np.asarray(offsets, np.float32)
        )
        return GameData(y, weights, offsets, dict(shards), dict(entity_ids or {}))

    def to_device(self, sharding=None) -> "GameData":
        """GameData with device-resident feature shards.

        Scoring walks the shards once per call; host numpy shards would be
        re-transferred host→device EVERY call (hundreds of MB at
        scale). Put them on device once and every subsequent score_game /
        predict_mean is a pure device program. Entity-id columns stay host
        numpy (they are factorized to int ids before any device work).
        """
        import jax

        put = (lambda x: jax.device_put(x, sharding)) if sharding is not None \
            else jax.device_put

        def put_shard(X):
            if isinstance(X, ChunkedMatrix):
                # streamed-objective shards are host-resident BY DESIGN:
                # scoring streams them chunk by chunk (chunked_margins /
                # game.scoring.score_chunked_host) — device-putting the
                # whole chunked shard would defeat the out-of-HBM regime
                return X
            if isinstance(X, (HybridRows, PermutedHybridRows,
                              BlockedEllRows)):
                if sharding is not None:
                    raise ValueError(
                        f"{type(X).__name__} shards cannot be row-sharded "
                        "(single-device representation)")
                return jax.device_put(X)  # registered pytree: one put
            if isinstance(X, SparseRows):
                return SparseRows(put(X.indices), put(X.values), X.n_features)
            if isinstance(X, jax.Array):
                # Idempotent: already-device shards are not round-tripped
                # through the host (np.asarray of a multi-host sharded array
                # would even raise).
                return X if sharding is None else put(X)
            # np (not jnp) conversion: device_put then transfers ONCE,
            # directly into the target sharding.
            return put(np.asarray(X, np.float32))

        return GameData(self.y, self.weights, self.offsets,
                        {k: put_shard(X) for k, X in self.shards.items()},
                        self.entity_ids)


def _shard_dim(X: Matrix) -> int:
    return X.n_features if isinstance(X, SparseRows) else X.shape[1]


def _host_rows(X: Matrix):
    """One host copy of a random-effect shard for entity bucketing: numpy
    (dense) or an (indices, values) pair (SparseRows)."""
    if isinstance(X, (HybridRows, PermutedHybridRows, BlockedEllRows)):
        raise TypeError(
            f"{type(X).__name__} shards are not supported for GAME entity bucketing "
            "(single-device fixed-effect representation); use SparseRows or "
            "dense shards for random-effect coordinates")
    if isinstance(X, ChunkedMatrix):
        raise TypeError(
            "random-effect coordinates need a resident shard (entity "
            "bucketing gathers rows); the training driver only chunks "
            "shards used exclusively by fixed effects — keep this shard "
            "out of the streamed-objective set")
    if isinstance(X, SparseRows):
        return np.asarray(X.indices), np.asarray(X.values)
    return np.asarray(X)


@dataclasses.dataclass(frozen=True)
class FixedEffectDataset:
    """One feature shard over all rows (reference: FixedEffectDataset)."""

    shard_name: str
    X: Matrix
    y: jnp.ndarray
    weights: jnp.ndarray

    @property
    def n(self) -> int:
        return int(self.y.shape[0])

    @property
    def dim(self) -> int:
        return _shard_dim(self.X)

    @staticmethod
    def build(data: GameData, shard_name: str) -> "FixedEffectDataset":
        import jax

        X = data.shards[shard_name]
        if isinstance(X, ChunkedMatrix):
            # Streamed-objective regime: the shard stays HOST-resident in
            # chunks, and so do the scalar columns (batch() below assembles
            # a ChunkedBatch; train_glm streams it through the device).
            return FixedEffectDataset(
                shard_name, X, np.asarray(data.y, np.float32),
                np.asarray(data.weights, np.float32))
        if not isinstance(X, (SparseRows, HybridRows,
                              PermutedHybridRows, BlockedEllRows)) and not (
                isinstance(X, jax.Array)
                and jnp.issubdtype(X.dtype, jnp.floating)):
            # host numpy (and integer device arrays) transfer/normalize as
            # f32; an already-device FLOATING array keeps its STORAGE
            # dtype — a bf16 shard placed by stream_to_device / device_put
            # must not round-trip through an f32 upcast (matvec handles
            # bf16 operands with f32 accumulation), while an int shard
            # must not truncate w via matvec's w.astype(X.dtype)
            X = jnp.asarray(X, jnp.float32)
        return FixedEffectDataset(
            shard_name, X, jnp.asarray(data.y), jnp.asarray(data.weights)
        )

    def batch(self, offsets) -> GLMBatch:
        if isinstance(self.X, ChunkedMatrix):
            # One (n,)-sized host fetch per solve when offsets live on
            # device (other coordinates' scores) — 4 bytes/row against the
            # feature stream the solve saves from HBM.
            return make_chunked_batch(self.X, self.y, self.weights,
                                      np.asarray(offsets, np.float32))
        # y / weights / offsets are held in the caller's row order;
        # make_batch puts them in the order X stores its rows
        return make_batch(self.X, self.y, self.weights, offsets)


@dataclasses.dataclass(frozen=True)
class REBlock:
    """One bucket of entities with identical padded shape (E, m, ...)."""

    m: int  # rows per entity (power of two)
    entity_index: np.ndarray  # (E,) dense entity ids (host)
    # (E,) rows each entity holds here (host): slots j < active_rows[e] of
    # `row_index` / `X` are its active rows — whatever their weight — and
    # the rest padding
    active_rows: np.ndarray
    row_index: jnp.ndarray  # (E, m) int32 original row positions (clamped for padding)
    y: jnp.ndarray  # (E, m)
    weights: jnp.ndarray  # (E, m); 0 marks padding
    X: object  # dense (E, m, d) jnp array, or (indices (E,m,k), values (E,m,k)) pair
    # Projected-space bucket (reference: RandomEffectDatasetInProjectedSpace):
    # dim = this bucket's feature dim when projected (X is dense (E, m, dim));
    # proj = the per-entity index map behind it (INDEX_MAP only).
    dim: Optional[int] = None
    proj: Optional[object] = None  # projector.BlockProjection
    # lanes of one chunk of this bucket's FULL variances (the bucket plan's
    # `variance_lanes`: width² a lane, counted against the device), and
    # where they take the rows form (m² + 2·m·width a lane)
    variance_lanes: int = 1
    variance_lanes_rows: int = 1

    @property
    def n_entities(self) -> int:
        return int(self.entity_index.shape[0])

    @property
    def held(self) -> np.ndarray:
        """(E, m) bool, host: the slots that hold a row (the rest pad)."""
        return np.arange(self.m)[None, :] < self.active_rows[:, None]


def _project_dense(Xd: np.ndarray, icpt, width: int) -> tuple:
    """INDEX_MAP-project a dense (E, m, d) bucket: per-entity active columns
    only, intercept pinned last, `width` projected columns."""
    from photon_tpu.game.projector import (
        build_index_map_projection,
        project_dense_block,
    )

    active = np.any(Xd != 0.0, axis=1)  # (E, d)
    if icpt is not None:
        active[:, icpt] = False
    sets = [np.nonzero(a)[0] for a in active]
    bp = build_index_map_projection(sets, icpt, width=width)
    return jnp.asarray(project_dense_block(Xd, bp)), bp


@partial(jax.jit, static_argnames=("width",))
def _densify(local, val, width: int):
    """(..., k) projected columns and values → the dense (..., width) rows: every
    slot compared against every column and summed over the slots, so a
    feature a row names twice accumulates (SparseRows matvec semantics)
    and nothing is scattered."""
    hit = local[..., None] == jnp.arange(width, dtype=local.dtype)
    return jnp.sum(jnp.where(hit, val[..., None], 0.0), axis=-2)


def _project_sparse(ind3: np.ndarray, val3: np.ndarray, icpt,
                    width: int) -> tuple:
    """INDEX_MAP-project a padded-COO (E, m, k) bucket to per-entity dense
    (E, m, width) blocks. The index map is built on the host; the block is
    laid out on the device from the (E, m, k) columns and values, k slots a
    row across the link instead of `width`."""
    from photon_tpu.game.projector import sparse_index_map

    bp, local = sparse_index_map(ind3, val3, icpt, width=width)
    return _densify(local, val3, bp.dim), bp


def _active_widths(Xg, mask: np.ndarray, icpt) -> np.ndarray:
    """(E,) INDEX_MAP solve width of each entity of a gathered (E, m, ...)
    group: the features its masked rows touch, plus the intercept's column."""
    extra = 1 if icpt is not None else 0
    if isinstance(Xg, tuple):
        ind, val = Xg
        live = (val != 0.0) & mask[..., None]
        if icpt is not None:
            live &= ind != icpt
        sentinel = np.iinfo(np.int32).max
        ids = np.sort(np.where(live, ind, sentinel).astype(np.int32)
                      .reshape(ind.shape[0], -1), axis=1)
        first = ids != sentinel
        first[:, 1:] &= ids[:, 1:] != ids[:, :-1]
        return first.sum(axis=1) + extra
    active = np.any((Xg != 0.0) & mask[..., None], axis=1)
    if icpt is not None:
        active[:, icpt] = False
    return active.sum(axis=1) + extra


# ------------------------------------------------------------- bucket plan
# A distinct block shape costs one solver compile and one launch per update;
# padding costs bytes on every pass of every solve. A merge of two shapes is
# taken while it adds fewer padded bytes than 1/_SHAPE_WORTH of the device's
# memory, down to _DEFAULT_SHAPES shapes (small problems: the compile is
# dearer than any padding). One coordinate's blocks may hold at most
# 1/_BLOCK_SHARE of the device: the other coordinates' blocks, the
# coefficient tables, the flat shards and the solver state live there too.
_SHAPE_WORTH = 128
_BLOCK_SHARE = 2
_DEFAULT_SHAPES = 3
_NOMINAL_DEVICE_BYTES = 16 << 30  # where the backend reports no limit (CPU)
# FULL coefficient variances factor one (width, width) Hessian a lane: the
# Gram, its Cholesky factor and the factor's inverse, width² f32 each, and
# what the compiler's blocked factorization keeps beside them — 2.2 to 5.6
# such matrices a lane in all, by the v5e compiler's own count at GLMix's
# widths 128 to 1,280 (PERF.md section 4). The lanes of one variance chunk
# are a power of two whose workspace holds at most 1/_VARIANCE_SHARE of
# the device, beside the resident fit.
_VARIANCE_SHARE = 16
_VARIANCE_MATRICES = 6


def _device_memory_bytes() -> Optional[int]:
    """The default device's memory limit; None where the backend reports
    none (the CPU), and then no plan is refused for its size."""
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats["bytes_limit"]) if stats.get("bytes_limit") else None


def _pow2_ceil(x: np.ndarray, floor: int) -> np.ndarray:
    """`data.matrix.next_pow2`, elementwise: the smallest floor·2^j ≥ x."""
    x = np.maximum(np.asarray(x, np.int64), floor)
    return floor << np.ceil(np.log2(x / floor)).astype(np.int64)


def _width_class(width: np.ndarray) -> np.ndarray:
    """Projected-width classes: a power of two up to one 128-lane tile,
    whole tiles above it (a power of two would pad 679 columns to 1024)."""
    width = np.asarray(width, np.int64)
    return np.where(width <= 128, _pow2_ceil(width, 2),
                    -(-width // 128) * 128)


def variance_lanes(width: int, device_bytes: Optional[int] = None,
                   rows: Optional[int] = None) -> int:
    """Lanes of one chunk of a bucket's FULL variances: the largest power of
    two whose `_VARIANCE_MATRICES` (width, width) f32 matrices a lane fit in
    1/`_VARIANCE_SHARE` of the device (at least one lane). With ``rows``
    under ``width`` the lanes of the rows form (models/variance.py), whose
    matrices are (rows, rows) and (rows, width): as many of rows² +
    2·rows·width floats a lane (the v5e compiler's own count of the rows
    form's workspace is about one such, PERF.md section 6)."""
    width = int(width)
    floats = (width ** 2 if rows is None or rows >= width
              else int(rows) ** 2 + 2 * int(rows) * width)
    budget = (device_bytes or _NOMINAL_DEVICE_BYTES) // _VARIANCE_SHARE
    lanes = budget // (_VARIANCE_MATRICES * floats * 4)
    return 1 << max(int(lanes).bit_length() - 1, 0)


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Block shapes for one random-effect coordinate: ``buckets`` is a list
    of (m rows, w solve width, entity ids) in (m, w) order; the two byte
    counts are of the blocks' feature values (rows × width × 4, or a sparse
    block's k slots × 8); ``variance_lanes`` the lanes of one chunk of each
    bucket's FULL variances (`variance_lanes`), ``variance_lanes_rows``
    the same where they take the rows form."""

    buckets: list
    bytes_real: int     # Σ entities: active rows × own width
    bytes_padded: int   # Σ buckets: entities × m × w
    variance_lanes: tuple = ()
    variance_lanes_rows: tuple = ()


def plan_buckets(rows: np.ndarray, widths: np.ndarray, slot_bytes: int,
                 width_classes: bool = False, min_block_rows: int = 4,
                 max_blocks: Optional[int] = None,
                 device_bytes: Optional[int] = None,
                 name: str = "",
                 solve_width: Optional[int] = None) -> BucketPlan:
    """Plan block shapes by bytes. ``rows`` / ``widths``: each entity's
    active rows and solve width; ``width_classes``: widths differ by entity
    (an INDEX_MAP projection) and a bucket's is a `_width_class`;
    ``slot_bytes``: bytes of one (row, column) slot. The merge cost is the
    padded bytes a merge adds; merging stops at `_DEFAULT_SHAPES` shapes or
    when the cheapest merge costs more than a shape is worth on this device.
    ``max_blocks``, where a caller passes it, is an upper limit that is met
    whatever it costs. A plan over the device's share raises here, before
    anything is allocated, naming its largest bucket. The plan also sizes
    the chunks a bucket's FULL variances are computed in, from the same
    device bytes (`variance_lanes`), at the bucket's width or, where a
    planned width is a count of sparse slots, at ``solve_width``."""
    rows = np.asarray(rows, np.int64)
    widths = np.asarray(widths, np.int64)
    m_of = _pow2_ceil(rows, min_block_rows)
    w_of = _width_class(widths) if width_classes else widths
    cells, cell_of = np.unique(np.stack([m_of, w_of], axis=1), axis=0,
                               return_inverse=True)
    cell_of = cell_of.reshape(-1)
    M = cells[:, 0].astype(np.float64)
    W = cells[:, 1].astype(np.float64)
    groups = [np.nonzero(cell_of == c)[0] for c in range(len(cells))]
    E = np.asarray([len(g) for g in groups], np.float64)
    forced = max_blocks is not None
    limit = max_blocks if forced else _DEFAULT_SHAPES
    worth = (device_bytes or _NOMINAL_DEVICE_BYTES) // _SHAPE_WORTH
    while len(groups) > limit:
        held = M * W * E
        cost = (np.maximum.outer(M, M) * np.maximum.outer(W, W)
                * np.add.outer(E, E) - np.add.outer(held, held)) * slot_bytes
        cost[np.tril_indices(len(groups))] = np.inf
        i, j = np.unravel_index(int(np.argmin(cost)), cost.shape)
        if not forced and cost[i, j] >= worth:
            break
        M[i], W[i], E[i] = max(M[i], M[j]), max(W[i], W[j]), E[i] + E[j]
        groups[i] = np.concatenate([groups[i], groups[j]])
        M, W, E = (np.delete(a, j) for a in (M, W, E))
        del groups[j]
    order = np.lexsort((W, M))
    buckets = [(int(M[b]), int(W[b]), groups[b]) for b in order]
    sizes = [m * w * len(g) * slot_bytes for m, w, g in buckets]
    padded = int(sum(sizes))
    if device_bytes is not None and padded > device_bytes // _BLOCK_SHARE:
        m, w, g = buckets[int(np.argmax(sizes))]
        raise ValueError(
            f"random-effect coordinate {name!r}: its blocks would hold "
            f"{padded:,} bytes, over 1/{_BLOCK_SHARE} of the device's "
            f"{device_bytes:,}; the largest bucket is {len(g):,} entities x "
            f"{m} rows x {w} columns ({max(sizes):,} bytes). Lower "
            "active_cap, or shard the coordinate over a mesh")
    real = int((rows * widths).sum()) * slot_bytes
    lanes = tuple(min(variance_lanes(solve_width or w, device_bytes), len(g))
                  for _, w, g in buckets)
    lanes_rows = tuple(
        min(variance_lanes(solve_width or w, device_bytes, rows=m), len(g))
        for m, w, g in buckets)
    return BucketPlan(buckets, real, padded, lanes, lanes_rows)


class ScoringPlan(NamedTuple):
    """Where the one-dispatch update reads each training row's margin.

    The update lays its buckets' block margins `X_b · w_b`, flattened
    (E_b · m,) and concatenated in block order, in front of the table
    margins of the passive sub-shard; ``slot_of_row[i]`` is row i's place in
    that vector. A row a block holds (slot j < its entity's `active_rows`)
    points at its slot, never at a padding slot; every other row — beyond
    the cap, or of an entity dropped for carrying no weight (dense id E) —
    points at Σ_b E_b·m + its rank among such rows."""

    slot_of_row: jax.Array   # (n,) int32
    X_passive: Matrix        # the passive rows of the flat shard, in rank order
    ids_passive: jax.Array   # (n_passive,) int32 their dense entity ids

    @property
    def n_table_rows(self) -> int:
        return int(self.ids_passive.shape[0])

    @property
    def n_block_rows(self) -> int:
        return int(self.slot_of_row.shape[0]) - self.n_table_rows


class LaneScoringPlan(NamedTuple):
    """`ScoringPlan` for a regularization grid's lanes, whose update keeps
    no (E, d) table: the passive rows read their k coefficients out of the
    buckets' (E_b, width_b, G) solutions laid end to end, ONE index a slot
    for all G lanes (`RandomEffectDataset.lane_positions`)."""

    slot_of_row: jax.Array   # (n,) int32, as `ScoringPlan`'s
    values: jax.Array        # (n_passive, k) the passive rows' values
    positions: jax.Array     # (n_passive, k) int32 where their slots lie

    @property
    def n_table_rows(self) -> int:
        return int(self.positions.shape[0])

    @property
    def n_block_rows(self) -> int:
        return int(self.slot_of_row.shape[0]) - self.n_table_rows


@partial(jax.jit, static_argnames=("shape",))
def _lane_positions(maps: tuple, dense_ids, cols, shape: tuple):
    """`RandomEffectDataset.lane_positions`: every bucket's flat positions,
    numbered end to end in block order, written through its index map
    (``maps``: `RandomEffectDataset.table_maps`) into an (E, d) int32
    table of one past the end, and read back at (entity, feature) — a
    dense id E, no entity, reads one past the end too."""
    d = shape[1]
    widths = [d if cols_b is None else cols_b.shape[1] for _, cols_b in maps]
    total = sum(ents.shape[0] * w for (ents, _), w in zip(maps, widths))
    table = jnp.full(shape, total, jnp.int32)
    base = 0
    for (ents, cols_b), w in zip(maps, widths):
        n = ents.shape[0]
        at = (ents,) if cols_b is None else (ents[:, None], cols_b)
        table = table.at[at].set(
            base + jnp.arange(n * w, dtype=jnp.int32).reshape(n, w),
            mode="drop")
        base += n * w
    return table.at[dense_ids[:, None], cols].get(mode="fill",
                                                   fill_value=total)


@dataclasses.dataclass(frozen=True)
class RandomEffectDataset:
    """Entity-bucketed random-effect data (reference: RandomEffectDataset).

    `blocks` hold the active training rows; `entity_dense` + the shard give
    the flat per-row view every table scorer reads (all rows, passive too).
    The one-dispatch update scores the active rows from the blocks and only
    the rest from the table: `scoring_plan`.
    """

    entity_name: str
    shard_name: str
    entity_keys: np.ndarray  # (E,) raw keys, dense id = position
    key_to_index: dict  # raw key -> dense id
    blocks: list  # list[REBlock]
    X: Matrix  # flat per-row design matrix (all n rows), FULL feature space
    entity_dense: np.ndarray  # (n,) dense entity id per row
    n_active: int  # rows used for training
    n_passive: int  # rows only scored
    # Feature-space projection (reference: RandomEffectDatasetInProjectedSpace):
    # the ProjectionConfig that built the blocks and, for RANDOM, the shared
    # projector.RandomProjector. INDEX_MAP keeps its per-bucket maps on the
    # blocks themselves (REBlock.proj).
    projection: Optional[object] = None  # projector.ProjectionConfig
    projector: Optional[object] = None  # projector.RandomProjector
    # The bucket plan's byte counts (`plan_buckets`): the feature values the
    # entities' active rows hold at their own solve widths, and the padded
    # blocks allocated for them.
    block_bytes_real: int = 0
    block_bytes_padded: int = 0

    @property
    def n_entities(self) -> int:
        return int(self.entity_keys.shape[0])

    @property
    def dim(self) -> int:
        return _shard_dim(self.X)

    @functools.cached_property
    def scoring_plan(self) -> ScoringPlan:
        """The `ScoringPlan` of these blocks, built and placed on the device
        once a dataset (every coordinate over it shares the one copy of the
        passive sub-shard)."""
        slot = np.full(self.entity_dense.shape[0], -1, np.int64)
        base = 0
        for block in self.blocks:
            at = np.nonzero(block.held.reshape(-1))[0]
            slot[np.asarray(block.row_index).reshape(-1)[at]] = base + at
            base += block.n_entities * block.m
        passive = np.nonzero(slot < 0)[0]
        slot[passive] = base + np.arange(passive.shape[0])
        rows = jnp.asarray(passive.astype(np.int32))
        X = self.X
        X_passive = (SparseRows(X.indices[rows], X.values[rows], X.n_features)
                     if isinstance(X, SparseRows) else X[rows])
        return ScoringPlan(jnp.asarray(slot.astype(np.int32)), X_passive,
                           jnp.asarray(self.entity_dense[passive]))

    @functools.cached_property
    def table_maps(self) -> tuple:
        """Each bucket's (entity ids, index map as table columns or None),
        on the device: where its (E_b, width_b) coefficients lie in the
        (E, d) table. A padding column points past the table, where a read
        fills 0 and a write drops."""
        d = self.dim
        return tuple(
            (jnp.asarray(block.entity_index),
             None if block.proj is None else jnp.asarray(
                 np.where(block.proj.proj_mask > 0, block.proj.proj_idx, d)
                 .astype(np.int32)))
            for block in self.blocks)

    def lane_positions(self, dense_ids, cols) -> jax.Array:
        """Where the coefficient of each (row, slot) — the entity of dense
        id ``dense_ids[i]`` at feature ``cols[i, j]`` — lies in the
        buckets' solutions laid end to end in block order: its flat
        position, or one past the end (a read there fills 0) where the
        entity's bucket has no such column, or there is no such entity.
        Found on the device (`_lane_positions`)."""
        return _lane_positions(self.table_maps,
                               jnp.asarray(dense_ids, jnp.int32),
                               jnp.asarray(cols, jnp.int32),
                               (self.n_entities, self.dim))

    @functools.cached_property
    def lane_scoring_plan(self) -> LaneScoringPlan:
        """The `LaneScoringPlan` of these blocks, built and placed on the
        device once a dataset."""
        plan = self.scoring_plan
        X = self.X
        # the passive rows, in rank order: the slots past the blocks'
        held = sum(block.n_entities * block.m for block in self.blocks)
        passive = np.nonzero(np.asarray(plan.slot_of_row) >= held)[0]
        rows = jnp.asarray(passive)
        if isinstance(X, SparseRows):
            cols, values = X.indices[rows], X.values[rows]
        else:
            cols = jnp.broadcast_to(jnp.arange(X.shape[1]),
                                    (passive.shape[0], X.shape[1]))
            values = X[rows]
        return LaneScoringPlan(plan.slot_of_row, values, self.lane_positions(
            self.entity_dense[passive], cols))

    @staticmethod
    def build(
        data: GameData,
        entity_name: str,
        shard_name: str,
        active_cap: Optional[int] = None,
        min_block_rows: int = 4,
        seed: int = 0,
        projection=None,
        max_blocks: Optional[int] = None,
    ) -> "RandomEffectDataset":
        X = data.shards[shard_name]
        raw = np.asarray(data.entity_ids[entity_name])
        keys, entity_dense = np.unique(raw, return_inverse=True)
        entity_dense = entity_dense.astype(np.int32)
        n = data.n
        E = keys.shape[0]
        w_np = np.asarray(data.weights, np.float32)

        # Entities with NO weight-carrying rows (mesh padding's ""-id tail,
        # streamed down-sampling that zeroed a whole entity) are dropped
        # from training: the row-dropping form would never have seen them,
        # and an all-weight-0 entity trains to the regularized zero anyway.
        # Their rows keep dense id E, the unseen-entity convention — every
        # scorer gathers the appended zero row for them.
        carrying = np.bincount(
            entity_dense, weights=(w_np != 0.0).astype(np.float64),
            minlength=E) > 0
        if carrying.any() and not carrying.all():
            E_live = int(carrying.sum())
            remap = np.full(E, E_live, np.int32)
            remap[carrying] = np.arange(E_live, dtype=np.int32)
            keys = keys[carrying]
            entity_dense = remap[entity_dense]
            E = E_live

        # Group rows by entity: stable sort keeps original row order per
        # entity; dropped-entity rows (id E) sort last and are never inside
        # any entity's [start, start+count) range.
        order = np.argsort(entity_dense, kind="stable")
        counts = np.bincount(entity_dense, minlength=E + 1)[:E]
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])

        if active_cap is not None:
            # Down-sample each oversized entity's active rows uniformly
            # (reference: random-effect data config numActiveDataPointsUpperBound).
            if (counts > active_cap).any():
                # One pass for every entity: within an entity (the rows of
                # `order` are grouped by it) rows sort by a uniform draw.
                # Weight-0 rows (streamed down-sampling) must never
                # displace weight-carrying rows from the capped active
                # set, so carrying rows come first, uniformly sampled
                # among themselves.
                rng = np.random.default_rng(seed)
                order = order[np.lexsort((rng.random(n),
                                          w_np[order] == 0.0,
                                          entity_dense[order]))]
            active_counts = np.minimum(counts, active_cap)
        else:
            active_counts = counts

        # Optional feature-space projection (reference:
        # projector.* / RandomEffectDatasetInProjectedSpace).
        projector_obj = None
        icpt = None
        index_map = False
        if projection is not None:
            from photon_tpu.data.matrix import last_column_is_intercept
            from photon_tpu.game.projector import ProjectorType, RandomProjector

            icpt = _shard_dim(X) - 1 if last_column_is_intercept(X) else None
            if projection.projector is ProjectorType.RANDOM:
                projector_obj = RandomProjector.build(
                    _shard_dim(X),
                    projection.projected_dim,
                    keep_intercept=icpt is not None,
                    seed=projection.seed,
                )
            else:
                index_map = True

        sparse = isinstance(X, SparseRows)
        # one host copy of the shard for every gather below
        X_host = _host_rows(X)
        y, w = data.y, data.weights

        def gather(ents, m):
            """(row positions (E_b, m), real-row mask, gathered features) of
            entities `ents` padded to m rows; padding slots are clamped to
            the entity's first row and silenced by the mask."""
            pos = np.arange(m)
            mask = pos[None, :] < active_counts[ents][:, None]
            row_idx = order[starts[ents][:, None]
                            + np.where(mask, pos[None, :], 0)]
            if sparse:
                return row_idx, mask, (X_host[0][row_idx], X_host[1][row_idx])
            return row_idx, mask, X_host[row_idx]

        # Block shapes, planned by bytes (`plan_buckets`): rows from the
        # active counts; the solve width is the shard's (or the RANDOM
        # projection's) for every entity, and under INDEX_MAP each
        # entity's own — the features its active rows touch.
        if index_map:
            widths = np.zeros(E, np.int64)
            m_of = _pow2_ceil(active_counts, min_block_rows)
            for m in np.unique(m_of):
                ents = np.nonzero(m_of == m)[0]
                _, mask, Xg = gather(ents, int(m))
                widths[ents] = _active_widths(Xg, mask, icpt)
            slot_bytes = 4
        elif projector_obj is not None:
            widths, slot_bytes = np.full(E, projector_obj.dim_out), 4
        elif sparse:  # (index, value) pairs, k slots a row
            widths, slot_bytes = np.full(E, X_host[0].shape[-1]), 8
        else:
            widths, slot_bytes = np.full(E, _shard_dim(X)), 4
        if max_blocks is not None and max_blocks < 1:
            raise ValueError(f"max_blocks must be >= 1, got {max_blocks}")
        plan = plan_buckets(active_counts, widths, slot_bytes,
                            width_classes=index_map,
                            min_block_rows=min_block_rows,
                            max_blocks=max_blocks,
                            device_bytes=_device_memory_bytes(),
                            name=entity_name,
                            solve_width=(_shard_dim(X) if slot_bytes == 8
                                         else None))
        telemetry.count("game_re.block_bytes_real", plan.bytes_real)
        telemetry.count("game_re.block_bytes_padded", plan.bytes_padded)

        blocks = []
        for (m, width, ents), var_lanes, var_lanes_rows in zip(
                plan.buckets, plan.variance_lanes, plan.variance_lanes_rows):
            # Difficulty-sorted chunk packing: lanes that share a vmapped
            # lax.while_loop chunk all run until the SLOWEST lane converges
            # (random_effect dispatches buckets in fixed-size lane chunks),
            # so stack each bucket's entities in active-row-count order —
            # neighbours in a chunk then have homogeneous cost and a big
            # entity never holds a chunk of tiny ones hostage. Pure
            # packing: entity_index carries the permutation, and the
            # row_index / INDEX_MAP projection below are built in the same
            # (sorted) order, so scatter-back and projection are unchanged.
            ents = ents[np.argsort(active_counts[ents], kind="stable")]
            row_idx, mask, Xg = gather(ents, m)
            wb = np.where(mask, w[row_idx], 0.0).astype(np.float32)
            yb = y[row_idx].astype(np.float32)
            block_dim = None
            block_proj = None
            if sparse:
                ind3 = Xg[0]
                val3 = (Xg[1] * mask[..., None]).astype(np.float32)
                if projector_obj is not None:
                    Xb = jnp.asarray(projector_obj.project_sparse_rows(ind3, val3))
                    block_dim = projector_obj.dim_out
                elif index_map:
                    Xb, block_proj = _project_sparse(ind3, val3, icpt, width)
                    block_dim = block_proj.dim
                else:
                    Xb = (jnp.asarray(ind3), jnp.asarray(val3))
            else:
                Xd = (Xg * mask[..., None]).astype(np.float32)
                if projector_obj is not None:
                    Xb = jnp.asarray(projector_obj.project_rows(Xd))
                    block_dim = projector_obj.dim_out
                elif index_map:
                    Xb, block_proj = _project_dense(Xd, icpt, width)
                    block_dim = block_proj.dim
                else:
                    Xb = jnp.asarray(Xd)
            blocks.append(
                REBlock(
                    m=m,
                    entity_index=ents.astype(np.int32),
                    active_rows=active_counts[ents].astype(np.int32),
                    row_index=jnp.asarray(row_idx.astype(np.int32)),
                    y=jnp.asarray(yb),
                    weights=jnp.asarray(wb),
                    X=Xb,
                    dim=block_dim,
                    proj=block_proj,
                    variance_lanes=var_lanes,
                    variance_lanes_rows=var_lanes_rows,
                )
            )

        n_active = int(active_counts.sum())
        # the flat scoring shard lives on the device: a host shard would
        # cross to it again on every coordinate update
        X = (SparseRows(jnp.asarray(X.indices), jnp.asarray(X.values),
                        X.n_features) if sparse
             else jnp.asarray(X, jnp.float32))
        return RandomEffectDataset(
            entity_name=entity_name,
            shard_name=shard_name,
            entity_keys=keys,
            key_to_index={k: i for i, k in enumerate(keys.tolist())},
            blocks=blocks,
            X=X,
            entity_dense=entity_dense,
            n_active=n_active,
            n_passive=n - n_active,
            projection=projection,
            projector=projector_obj,
            block_bytes_real=plan.bytes_real,
            block_bytes_padded=plan.bytes_padded,
        )

    def block_batch(self, block: REBlock, offsets_full=None) -> GLMBatch:
        """Batched (E, m, ...) GLMBatch for one bucket, offsets gathered from
        the full per-row offset vector (other coordinates' scores); with
        none given the batch carries no offsets (the one-dispatch update
        lays them in inside its program)."""
        offs = (None if offsets_full is None else
                jnp.asarray(offsets_full, jnp.float32)[block.row_index])
        if block.dim is None and isinstance(self.X, SparseRows):
            Xb = SparseRows(*block.X, self.X.n_features)
        else:  # dense, or projected (always dense)
            Xb = block.X
        return GLMBatch(Xb, block.y, block.weights, offs)
