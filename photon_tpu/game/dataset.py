"""GAME datasets: fixed-effect batches and entity-bucketed random-effect blocks.

Reference parity: com.linkedin.photon.ml.data.{FixedEffectDataset,
RandomEffectDataset, GameDatum}. The reference partitions random-effect data
by entity id across Spark executors and trains one Breeze solver per entity.
On TPU the same structure becomes dense batched tensors:

- entities are bucketed by row count into power-of-two block shapes
  (bucket m = smallest power of two ≥ the entity's active rows), so a handful
  of distinct XLA programs covers every entity size;
- within a bucket, entities are stacked into (E, m, …) arrays — the per-entity
  solver is `vmap`'d over the leading axis, and that axis is shardable across
  the mesh's ``data`` axis, which is how per-entity training scales across
  chips (the Spark-partition analog);
- rows are padded with weight 0, so every reduction ignores padding.

The reference's active/passive split (`numActiveDataPointsUpperBound`,
RandomEffectDataset.activeData/passiveData) maps to `active_cap`: each
entity's first `active_cap` rows (after an optional shuffle) are trained on;
all rows — active and passive — are scored via the flat per-row layout kept
alongside the blocks.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

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


def _gather_rows(X: Matrix, idx: np.ndarray):
    """Host-side row gather; returns numpy (dense) or numpy-backed SparseRows."""
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
        ind = np.asarray(X.indices)[idx]
        val = np.asarray(X.values)[idx]
        return ind, val
    return np.asarray(X)[idx]


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
    row_index: jnp.ndarray  # (E, m) int32 original row positions (clamped for padding)
    y: jnp.ndarray  # (E, m)
    weights: jnp.ndarray  # (E, m); 0 marks padding
    X: object  # dense (E, m, d) jnp array, or (indices (E,m,k), values (E,m,k)) pair
    # Projected-space bucket (reference: RandomEffectDatasetInProjectedSpace):
    # dim = this bucket's feature dim when projected (X is dense (E, m, dim));
    # proj = the per-entity index map behind it (INDEX_MAP only).
    dim: Optional[int] = None
    proj: Optional[object] = None  # projector.BlockProjection

    @property
    def n_entities(self) -> int:
        return int(self.entity_index.shape[0])


def _next_pow2(x: int, floor: int = 4) -> int:
    from photon_tpu.data.matrix import next_pow2

    return next_pow2(x, floor)


def _project_dense(Xd: np.ndarray, icpt) -> tuple:
    """INDEX_MAP-project a dense (E, m, d) bucket: per-entity active columns
    only, intercept pinned last."""
    from photon_tpu.game.projector import (
        build_index_map_projection,
        project_dense_block,
    )

    active = np.any(Xd != 0.0, axis=1)  # (E, d)
    if icpt is not None:
        active[:, icpt] = False
    sets = [np.nonzero(a)[0] for a in active]
    bp = build_index_map_projection(sets, icpt)
    return jnp.asarray(project_dense_block(Xd, bp)), bp


def _project_sparse(ind3: np.ndarray, val3: np.ndarray, icpt) -> tuple:
    """INDEX_MAP-project a padded-COO (E, m, k) bucket to per-entity dense
    (E, m, p) blocks."""
    from photon_tpu.game.projector import (
        build_index_map_projection,
        project_sparse_block,
    )

    E = ind3.shape[0]
    sets = []
    for e in range(E):
        feats = np.unique(ind3[e][val3[e] != 0.0])
        if icpt is not None:
            feats = feats[feats != icpt]
        sets.append(feats)
    bp = build_index_map_projection(sets, icpt)
    return jnp.asarray(project_sparse_block(ind3, val3, bp)), bp


@dataclasses.dataclass(frozen=True)
class RandomEffectDataset:
    """Entity-bucketed random-effect data (reference: RandomEffectDataset).

    `blocks` hold the active training rows; `entity_dense` + the shard give
    the flat per-row view used for scoring (covers passive rows too).
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

    @property
    def n_entities(self) -> int:
        return int(self.entity_keys.shape[0])

    @property
    def dim(self) -> int:
        return _shard_dim(self.X)

    @staticmethod
    def build(
        data: GameData,
        entity_name: str,
        shard_name: str,
        active_cap: Optional[int] = None,
        min_block_rows: int = 4,
        seed: int = 0,
        projection=None,
        max_blocks: int = 3,
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
            rng = np.random.default_rng(seed)
            if (counts > active_cap).any():
                parts = []
                for e in range(E):
                    seg = starts[e] + rng.permutation(counts[e])
                    # Weight-0 rows (streamed down-sampling) must never
                    # displace weight-carrying rows from the capped active
                    # set — stable-sort so carrying rows come first,
                    # uniformly sampled among themselves.
                    zero = w_np[order[seg]] == 0.0
                    if zero.any():
                        seg = seg[np.argsort(zero, kind="stable")]
                    parts.append(seg)
                perm = np.concatenate(parts)
            else:
                perm = np.arange(n)
            order = order[perm]
            active_counts = np.minimum(counts, active_cap)
        else:
            active_counts = counts

        buckets: dict[int, list[int]] = {}
        for e in range(E):
            m = _next_pow2(max(int(active_counts[e]), 1), min_block_rows)
            buckets.setdefault(m, []).append(e)

        # Each distinct block shape costs one solver compile (~tens of
        # seconds on TPU via the remote compiler) while padded-row compute in
        # the vmapped solves is nearly free — so greedily merge adjacent
        # power-of-two buckets (padding the smaller one up) until at most
        # ``max_blocks`` shapes remain. Merge the pair that adds the fewest
        # padded row-slots.
        if max_blocks < 1:
            raise ValueError(f"max_blocks must be >= 1, got {max_blocks}")
        while len(buckets) > max_blocks:
            sizes = sorted(buckets)
            costs = [len(buckets[sizes[i]]) * (sizes[i + 1] - sizes[i])
                     for i in range(len(sizes) - 1)]
            i = int(np.argmin(costs))
            buckets[sizes[i + 1]] = buckets.pop(sizes[i]) + buckets[sizes[i + 1]]

        # Optional feature-space projection (reference:
        # projector.* / RandomEffectDatasetInProjectedSpace).
        projector_obj = None
        icpt = None
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

        y, w = data.y, data.weights
        blocks = []
        for m in sorted(buckets):
            ents = np.asarray(buckets[m], np.int64)
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
            st, ct = starts[ents], active_counts[ents]
            pos = np.arange(m)
            mask = pos[None, :] < ct[:, None]  # (E_b, m)
            # Clamp padding slots to the entity's first row; weight 0 silences them.
            idx2d = st[:, None] + np.where(mask, pos[None, :], 0)
            row_idx = order[idx2d]  # (E_b, m) original row positions
            wb = np.where(mask, w[row_idx], 0.0).astype(np.float32)
            yb = y[row_idx].astype(np.float32)
            Xg = _gather_rows(X, row_idx.reshape(-1))
            E_b = len(ents)
            block_dim = None
            block_proj = None
            if isinstance(X, SparseRows):
                ind, val = Xg
                k = ind.shape[-1]
                ind3 = ind.reshape(E_b, m, k)
                val3 = (val.reshape(E_b, m, k) * mask[..., None]).astype(np.float32)
                if projector_obj is not None:
                    Xb = jnp.asarray(projector_obj.project_sparse_rows(ind3, val3))
                    block_dim = projector_obj.dim_out
                elif projection is not None:
                    Xb, block_proj = _project_sparse(ind3, val3, icpt)
                    block_dim = block_proj.dim
                else:
                    Xb = (jnp.asarray(ind3), jnp.asarray(val3))
            else:
                d = Xg.shape[-1]
                Xd = (Xg.reshape(E_b, m, d) * mask[..., None]).astype(np.float32)
                if projector_obj is not None:
                    Xb = jnp.asarray(projector_obj.project_rows(Xd))
                    block_dim = projector_obj.dim_out
                elif projection is not None:
                    Xb, block_proj = _project_dense(Xd, icpt)
                    block_dim = block_proj.dim
                else:
                    Xb = jnp.asarray(Xd)
            blocks.append(
                REBlock(
                    m=m,
                    entity_index=ents.astype(np.int32),
                    row_index=jnp.asarray(row_idx.astype(np.int32)),
                    y=jnp.asarray(yb),
                    weights=jnp.asarray(wb),
                    X=Xb,
                    dim=block_dim,
                    proj=block_proj,
                )
            )

        n_active = int(active_counts.sum())
        if not isinstance(X, SparseRows):
            X = jnp.asarray(X, jnp.float32)
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
        )

    def block_batch(self, block: REBlock, offsets_full) -> GLMBatch:
        """Batched (E, m, ...) GLMBatch for one bucket, offsets gathered from
        the full per-row offset vector (other coordinates' scores)."""
        offs = jnp.asarray(offsets_full, jnp.float32)[block.row_index]
        if block.dim is not None:  # projected buckets are always dense
            Xb = block.X
        elif isinstance(self.X, SparseRows):
            ind, val = block.X
            Xb = SparseRows(ind, val, self.X.n_features)
        else:
            Xb = block.X
        return GLMBatch(Xb, block.y, block.weights, offs)
