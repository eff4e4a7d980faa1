"""Vectorized GAME regularization grids: coordinate descent with a lane axis.

Reference parity: com.linkedin.photon.ml.estimators.GameEstimator's grid
mode trains one full Spark job per GameOptimizationConfiguration. Here every
grid point becomes a LANE: the whole coordinate-descent state (fixed-effect
coefficients, per-entity random-effect coefficients, per-coordinate scores)
carries a leading lane axis, and each coordinate update solves ALL lanes in
one vmapped device program sharing every pass over the lane-invariant design
matrices — the fixed effect's per-lane matvec becomes one (n, d)×(d, G)
matmul, and the per-entity random-effect solves vmap over (entity × lane)
with each entity's (m, d) block shared by its G lanes.

Semantics vs the sequential path: identical per grid point — each lane runs
the same sweeps, warm-starting every coordinate update from that lane's own
previous state — EXCEPT that warm starts cannot chain ACROSS grid points
(lanes run concurrently; every lane starts from zeros), the same contract as
models.training.train_glm_grid. Feature-space projection and non-identity
normalization keep the sequential path (game.estimator gates them).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu.data.dataset import GLMBatch, pad_batch
from photon_tpu.data.matrix import matvec
from photon_tpu.game.fixed_effect import FixedEffectCoordinate
from photon_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
    _padded_coeffs,
    score_rows,
)
from photon_tpu.data.matrix import next_pow2
from photon_tpu.game.random_effect import (
    _MAX_SOLVE_LANES,
    RETrainStats,
    _pad_axis0,
    dispatch_chunked,
)
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.models.training import (
    lane_weight_arrays,
    make_objective,
    solve,
)
from photon_tpu.models.variance import VarianceComputationType, compute_variances
from photon_tpu.ops.losses import TaskType, loss_fns
from photon_tpu.parallel.mesh import data_sharding, pad_to_multiple, replicated


@partial(jax.jit, static_argnames=("config", "variance", "task"))
def _fixed_grid_update(batch, offs, w0s, obj, l2s, l1s, config, variance,
                       task):
    """One fixed-effect coordinate update for every lane: vmapped solve with
    per-lane offsets (other coordinates' scores differ per lane) + the
    coordinate's new margins + the per-lane total objective, fused into one
    device program."""
    loss, _, _ = loss_fns(task)

    def one(off, w0, l2v, l1v):
        o = dataclasses.replace(obj, l2=l2v)
        b = batch._replace(offsets=off)
        res = solve(o, b, w0, config, l1_weight=l1v)
        var = compute_variances(o, res.w, b, variance)
        margin = matvec(batch.X, res.w)
        objective = jnp.sum(batch.weights * loss(off + margin, batch.y))
        return res, var, margin, objective

    if l1s is None:
        return jax.vmap(lambda off, w0, l2v: one(off, w0, l2v, None))(
            offs, w0s, l2s)
    return jax.vmap(one)(offs, w0s, l2s, l1s)


# vmap axis trees for the (entity × lane) random-effect solve: the outer
# vmap maps the entity axis of every batch leaf; the inner vmap maps only
# the per-lane offsets (and w0 / reg weights) — X, y, weights are shared by
# a given entity's G lanes.
_BATCH_LANE_AXES = GLMBatch(X=None, y=None, weights=None, offsets=0)
_BATCH_ENTITY_AXES = GLMBatch(X=0, y=0, weights=0, offsets=0)

# Module-level cache (cf. random_effect._RE_SOLVERS): keyed on the
# weight-normalized config + variance type; the Objective and the lane
# weights are runtime arguments, so repeated fits and different grids share
# compilations per block shape.
_RE_GRID_SOLVERS: dict = {}


def _re_grid_solver(with_l1: bool, cfg, variance):
    key = (with_l1, cfg, variance)
    fn = _RE_GRID_SOLVERS.get(key)
    if fn is not None:
        return fn

    def one(obj, l2v, lam, batch, w0):
        o = dataclasses.replace(obj, l2=l2v)
        res = solve(o, batch, w0, cfg, l1_weight=lam)
        var = compute_variances(o, res.w, batch, variance)
        return res, var

    if with_l1:
        lanes = jax.vmap(one, in_axes=(None, 0, 0, _BATCH_LANE_AXES, 0))
        raw = jax.vmap(
            lanes, in_axes=(None, None, None, _BATCH_ENTITY_AXES, 0))
    else:
        def smooth(obj, l2v, batch, w0):
            return one(obj, l2v, None, batch, w0)

        lanes = jax.vmap(smooth, in_axes=(None, 0, _BATCH_LANE_AXES, 0))
        raw = jax.vmap(lanes, in_axes=(None, None, _BATCH_ENTITY_AXES, 0))
    fn = (jax.jit(raw), raw)
    _RE_GRID_SOLVERS[key] = fn
    return fn


def _run_block_grid(solver, obj, l2s, l1s, batch, w0, e_real: int,
                    n_lanes: int, mesh: Optional[Mesh]):
    """Chunked dispatch of one bucket's (entity × lane) solves: the entity
    chunk shrinks by the lane count so each COMPILE stays within the
    compile-friendly _MAX_SOLVE_LANES total, and the chunks lax.scan into
    one dispatch (game.random_effect.dispatch_chunked)."""
    n_dev = mesh.devices.size if mesh is not None else 1
    cap = max(1, _MAX_SOLVE_LANES // max(n_lanes, 1))
    chunk = min(cap, next_pow2(max(e_real, 1), 1))
    chunk = pad_to_multiple(chunk, n_dev)
    e_pad = pad_to_multiple(e_real, chunk)
    args = _pad_axis0((batch, w0), e_pad)
    head = (obj, l2s) + (() if l1s is None else (l1s,))
    return dispatch_chunked(solver, head, args, chunk, e_pad, mesh)


@partial(jax.jit, static_argnames=("g",))
def _lane_offsets(base, scores, g):
    """(G, n) per-lane offsets: base + every other coordinate's lane scores."""
    total = jnp.broadcast_to(base[None, :], (g, base.shape[0]))
    for s in scores:
        total = total + s
    return total


@jax.jit
def _gather_block_inputs(offs, row_index, C, ents):
    """Per-block (offsets, w0) with entity-leading axes: offsets (E_b, G, m)
    gathered from the (G, n) lane offsets, w0 (E_b, G, d) from the (G, E, d)
    lane coefficients."""
    off_b = jnp.transpose(offs[:, row_index], (1, 0, 2))
    w0_b = jnp.transpose(C[:, ents, :], (1, 0, 2))
    return off_b, w0_b


@jax.jit
def _scatter_block(C, ents, w_raw):
    """Slice one bucket's solved (E_pad, G, d) coefficients to its real
    entities and write them back into the (G, E, d) lane state (buckets
    partition the entities — disjoint)."""
    w_new = jnp.transpose(w_raw[: ents.shape[0]], (1, 0, 2))
    return C.at[:, ents, :].set(w_new)


@jax.jit
def _grid_block_stats(acc, conv, fail, iters):
    """Accumulate per-lane (converged, failed, iterations) sums over one
    bucket's real entities; (E_real, G) inputs (pre-sliced), ``acc`` a (3, G)
    running total or None."""
    s = jnp.stack([jnp.sum(conv, axis=0), jnp.sum(fail, axis=0),
                   jnp.sum(iters, axis=0)])
    return s if acc is None else acc + s


# Fused single-dispatch block update (single-device path): per-lane offset
# gather, warm-start gather, the chunk-scanned (entity × lane) solves, the
# coefficient/variance scatter, and the stats reduction — ONE jitted program
# per block per update instead of ~9 eager dispatches. Cached on (raw solver, chunk, e_pad): the jit inside
# re-keys on shapes.
_BLOCK_UPDATE: dict = {}


def _block_update_fn(raw_fn, chunk: int, e_pad: int):
    key = (raw_fn, chunk, e_pad)
    fn = _BLOCK_UPDATE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def run(C, V, acc, offs, row_index, ents, batch_base, head):
        off_b = jnp.transpose(offs[:, row_index], (1, 0, 2))
        w0_b = jnp.transpose(C[:, ents, :], (1, 0, 2))
        batch = batch_base._replace(offsets=off_b)
        args = _pad_axis0((batch, w0_b), e_pad)
        if e_pad == chunk:
            res, var = raw_fn(*head, *args)
        else:
            k = e_pad // chunk
            stacked = jax.tree_util.tree_map(
                lambda x: x.reshape((k, chunk) + x.shape[1:]), args)

            def body(_, part):
                return None, raw_fn(*head, *part)

            _, (res, var) = jax.lax.scan(body, None, stacked)
            res, var = jax.tree_util.tree_map(
                lambda x: x.reshape((e_pad,) + x.shape[2:]), (res, var))
        e_real = ents.shape[0]
        C = C.at[:, ents, :].set(
            jnp.transpose(res.w[:e_real], (1, 0, 2)))
        if var is not None and V is not None:
            V = V.at[:, ents, :].set(
                jnp.transpose(var[:e_real], (1, 0, 2)))
        acc = _grid_block_stats(acc, res.converged[:e_real],
                                res.failed[:e_real], res.iterations[:e_real])
        return C, V, acc

    fn = run
    _BLOCK_UPDATE[key] = fn
    return fn


@partial(jax.jit, static_argnames=("task",))
def _re_lane_scores(task, C, X, dense_ids, y, w, offs):
    """(G, n) random-effect margins for every lane + per-lane total
    objective, one program."""
    margins = jax.vmap(
        lambda c: score_rows(X, _padded_coeffs(c, dense_ids)))(C)
    loss, _, _ = loss_fns(task)
    objective = jnp.sum(w * loss(offs + margins, y), axis=-1)
    return margins, objective


@jax.jit
def lane_re_margins(C, X, dense_ids):
    """(G, n) random-effect margins (validation scoring)."""
    return jax.vmap(lambda c: score_rows(X, _padded_coeffs(c, dense_ids)))(C)


@dataclasses.dataclass
class GridFitOutcome:
    """Per-lane results of a vectorized GAME grid fit."""

    lane_models: list  # [GameModel] in lane order
    objective_histories: list  # [[float]] per lane, one entry per update
    coordinate_stats: list  # [{name: [OptResult | RETrainStats]}] per lane
    stacked: dict  # name -> (G, d) W or (G, E, d) C, for batched scoring


def fit_game_grid(
    coordinates: dict,
    lane_weights: dict,
    y,
    weights,
    base_offsets,
    task: TaskType,
    update_sequence=None,
    n_sweeps: int = 1,
    mesh: Optional[Mesh] = None,
) -> GridFitOutcome:
    """Run the whole coordinate-descent grid with a lane axis.

    ``coordinates``: name -> FixedEffectCoordinate | RandomEffectCoordinate
    built from the BASE configs (reg weights are per-lane runtime values).
    ``lane_weights``: name -> G reg weights, one per grid point (constant
    lists for coordinates the grid doesn't vary).
    """
    seq = list(update_sequence) if update_sequence else list(coordinates)
    trained = list(dict.fromkeys(seq))
    G = len(next(iter(lane_weights.values())))
    y = jnp.asarray(y, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    base = jnp.asarray(base_offsets, jnp.float32)
    n = int(y.shape[0])

    # Per-coordinate preparation: lane weight arrays, objectives, batches.
    prep: dict = {}
    state: dict = {}
    for name in trained:
        coord = coordinates[name]
        l2s, l1s, static_cfg = lane_weight_arrays(
            coord.config, lane_weights[name])
        ds = coord.dataset
        if isinstance(coord, FixedEffectCoordinate):
            d = ds.dim
            batch = GLMBatch(ds.X, ds.y, ds.weights,
                             jnp.zeros((n,), jnp.float32))
            n_pad = n
            if mesh is not None:
                n_pad = pad_to_multiple(n, mesh.devices.size)
                batch = pad_batch(batch, n_pad)
                batch = jax.device_put(batch, data_sharding(mesh))
            obj = make_objective(task, coord.config, d)
            prep[name] = ("fixed", batch, obj, l2s, l1s, static_cfg, n_pad)
            state[name] = jnp.zeros((G, d), jnp.float32)
        else:
            if ds.projection is not None:
                raise ValueError(
                    "fit_game_grid does not support projected random-effect "
                    "coordinates (the estimator routes them sequentially)")
            d = ds.dim
            obj = coord._block_objective(d)
            solver = _re_grid_solver(l1s is not None, static_cfg,
                                     coord.variance)
            # Per-block batches (X/y/weights are sweep- and lane-invariant)
            # built ONCE; only the per-lane offsets are replaced per update.
            # Chunk sizing mirrors _run_block_grid; the fused single-device
            # update program is resolved here too.
            n_dev = mesh.devices.size if mesh is not None else 1
            cap = max(1, _MAX_SOLVE_LANES // max(G, 1))
            blocks = []
            for block in ds.blocks:
                chunk = min(cap, next_pow2(max(block.n_entities, 1), 1))
                chunk = pad_to_multiple(chunk, n_dev)
                e_pad = pad_to_multiple(block.n_entities, chunk)
                fused = (None if mesh is not None
                         else _block_update_fn(solver[1], chunk, e_pad))
                blocks.append((block, jnp.asarray(block.entity_index),
                               ds.block_batch(block,
                                              np.zeros((n,), np.float32)),
                               fused))
            prep[name] = ("random", ds, obj, l2s, l1s, solver, blocks)
            state[name] = jnp.zeros((G, ds.n_entities, d), jnp.float32)
    var_state = {
        name: (jnp.zeros_like(state[name])
               if prep[name][0] == "random"
               and coordinates[name].variance is not VarianceComputationType.NONE
               else None)
        for name in trained
    }

    scores: dict = {}
    history: list = []  # (G,) device scalars per update, device_get at end
    stats_acc: dict = {name: [] for name in trained}

    lane_sharding = None
    if mesh is not None:
        lane_sharding = NamedSharding(mesh, P(None, tuple(mesh.axis_names)))

    for _ in range(n_sweeps):
        for name in seq:
            coord = coordinates[name]
            offs = _lane_offsets(
                base, tuple(s for o, s in scores.items() if o != name), g=G)
            if prep[name][0] == "fixed":
                _, batch, obj, l2s, l1s, static_cfg, n_pad = prep[name]
                offs_in = offs
                if n_pad != n:
                    offs_in = jnp.pad(offs, ((0, 0), (0, n_pad - n)))
                if lane_sharding is not None:
                    offs_in = jax.device_put(offs_in, lane_sharding)
                    w0s = jax.device_put(state[name], replicated(mesh))
                else:
                    w0s = state[name]
                res, var, margin, objective = _fixed_grid_update(
                    batch, offs_in, w0s, obj, l2s, l1s, static_cfg,
                    coord.variance, task)
                state[name] = res.w
                var_state[name] = var
                scores[name] = margin[:, :n]
                stats_acc[name].append(("fixed", res))
                history.append(objective)
            else:
                _, ds, obj, l2s, l1s, solver, blocks = prep[name]
                head = (obj, l2s) + (() if l1s is None else (l1s,))
                acc = None
                for block, ents, batch_base, fused in blocks:
                    if fused is not None:  # single-device: one dispatch
                        state[name], var_state[name], acc = fused(
                            state[name], var_state[name], acc, offs,
                            block.row_index, ents, batch_base, head)
                        continue
                    off_b, w0_b = _gather_block_inputs(
                        offs, block.row_index, state[name], ents)
                    batch_b = batch_base._replace(offsets=off_b)
                    e_real = block.n_entities
                    res, var = _run_block_grid(
                        solver, obj, l2s, l1s, batch_b, w0_b, e_real, G, mesh)
                    state[name] = _scatter_block(state[name], ents,
                                                 res.w[:e_real])
                    if var is not None and var_state[name] is not None:
                        var_state[name] = _scatter_block(
                            var_state[name], ents, var[:e_real])
                    acc = _grid_block_stats(
                        acc, res.converged[:e_real], res.failed[:e_real],
                        res.iterations[:e_real])
                margins, objective = _re_lane_scores(
                    task, state[name], ds.X,
                    jnp.asarray(ds.entity_dense), y, weights, offs)
                scores[name] = margins
                stats_acc[name].append(("random", (ds.n_entities, acc)))
                history.append(objective)

    # ONE host transfer for everything the lanes produced.
    state_h, var_h, history_h, stats_h = jax.device_get(
        (state, var_state, history, stats_acc))
    histories = [[float(history_h[u][g]) for u in range(len(history_h))]
                 for g in range(G)]

    lane_models = []
    lane_stats = []
    for g in range(G):
        coords_g: dict = {}
        stats_g: dict = {}
        for name in trained:
            coord = coordinates[name]
            if prep[name][0] == "fixed":
                v = var_h[name]
                glm = GeneralizedLinearModel(
                    Coefficients(state_h[name][g],
                                 None if v is None else v[g]), task)
                coords_g[name] = FixedEffectModel(
                    glm, coord.dataset.shard_name)
            else:
                ds = coord.dataset
                v = var_h[name]
                coords_g[name] = RandomEffectModel(
                    entity_name=ds.entity_name,
                    feature_shard=ds.shard_name,
                    task=task,
                    coefficients=jnp.asarray(state_h[name][g]),
                    entity_keys=ds.entity_keys,
                    key_to_index=ds.key_to_index,
                    variances=None if v is None else jnp.asarray(v[g]),
                )
            per_update = []
            for kind, payload in stats_h[name]:
                if kind == "fixed":
                    per_update.append(
                        jax.tree_util.tree_map(lambda x, g=g: x[g], payload))
                else:
                    E, acc = payload
                    per_update.append(RETrainStats(
                        E, int(acc[0, g]), int(acc[1, g]), int(acc[2, g])))
            stats_g[name] = per_update
        lane_models.append(GameModel(coords_g, task))
        lane_stats.append(stats_g)

    return GridFitOutcome(
        lane_models=lane_models,
        objective_histories=histories,
        coordinate_stats=lane_stats,
        stacked={name: state_h[name] for name in trained},
    )
