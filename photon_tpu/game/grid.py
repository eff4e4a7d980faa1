"""Vectorized GAME regularization grids: coordinate descent with a lane axis.

Reference parity: com.linkedin.photon.ml.estimators.GameEstimator's grid
mode trains one full Spark job per GameOptimizationConfiguration. Here every
grid point becomes a LANE: the whole coordinate-descent state (fixed-effect
coefficients, per-entity random-effect coefficients, per-coordinate scores)
carries a lane axis, and each coordinate update solves ALL lanes in one
device program sharing every pass over the lane-invariant design matrices —
the fixed effect's per-lane matvec becomes one (n, d)×(d, G) matmul, and a
random effect's update is its one-dispatch update with a lane axis
(`random_effect._fused_re_fn`, driven by `coordinate_descent`'s own loop):
a bucket chunk's (entity × lane) solves are ONE lane-minor solve on the
entities' blocks, each block read once for its G lanes, and the descent
keeps no (E, d) table — the buckets' (E_b, width_b, G) solutions are its
state, and one gathered index reads all G lanes of a coefficient out of
them.

Semantics vs the sequential path: identical per grid point — each lane runs
the same sweeps, warm-starting every coordinate update from that lane's own
previous state — EXCEPT that warm starts cannot chain ACROSS grid points
(lanes run concurrently; every lane starts from zeros), the same contract as
models.training.train_glm_grid. INDEX_MAP projection runs on lanes; what
has no one-dispatch update keeps the sequential path (game.estimator gates
it): a RANDOM projection (its dense matrix lives on the host), non-identity
normalization, variances, a straggler budget — and projection on a mesh,
whose grid keeps its block loop.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu import telemetry
from photon_tpu.data.dataset import GLMBatch, pad_batch
from photon_tpu.data.matrix import matvec, next_pow2
from photon_tpu.game.coordinate_descent import coordinate_descent
from photon_tpu.game.fixed_effect import FixedEffectCoordinate
from photon_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
    score_entities,
)
from photon_tpu.game.random_effect import (
    _MAX_SOLVE_LANES,
    RETrainStats,
    _pad_axis0,
    _table_index,
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


@partial(jax.jit, static_argnames=("task",))
def _mesh_re_scores(task, C, X, dense_ids, y, w, offs):
    """(G, n) random-effect margins of the mesh route's (G, E, d) lane
    state, k coefficients a row for each lane, + the per-lane objective."""
    margins = jax.vmap(
        lambda c: score_entities(X, c, dense_ids, exact=True))(C)
    loss, _, _ = loss_fns(task)
    objective = jnp.sum(w * loss(offs + margins, y), axis=-1)
    return margins, objective


@partial(jax.jit, static_argnames=("shape",))
def _lane_models_tables(solutions: tuple, maps: tuple, shape: tuple):
    """G (E, d) coefficient tables from a coordinate's buckets' (E_b,
    width_b, G) lane solutions, each written through its bucket's index
    map (`maps`: (entity ids, columns or None) a bucket) into zeros."""
    G = solutions[0].shape[-1]
    tables = []
    with telemetry.device_scope("game_re.scatter"):
        for g in range(G):
            table = jnp.zeros(shape, jnp.float32)
            for w, (ents, cols) in zip(solutions, maps):
                table = table.at[_table_index(ents, cols)].set(
                    w[..., g], mode="drop")
            tables.append(table)
    return tuple(tables)


@dataclasses.dataclass
class GridFitOutcome:
    """Per-lane results of a vectorized GAME grid fit. ``stacked`` holds
    the lanes' state for batched scoring: a fixed effect's (G, d)
    coefficients, a random effect's buckets' (E_b, width_b, G) solutions
    in its dataset's block order (`RandomEffectDataset.lane_positions`
    says where a coefficient lies)."""

    lane_models: list  # [GameModel] in lane order
    objective_histories: list  # [[float]] per lane, one entry per update
    coordinate_stats: list  # [{name: [OptResult | RETrainStats]}] per lane
    stacked: dict


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

    On one device this is `coordinate_descent` with ``lanes``: every
    random-effect update is the coordinate's one-dispatch update with a
    lane axis (`RandomEffectCoordinate.fused_update_program(lanes=G)`),
    every fixed-effect update a vmapped solve over the lanes (`coordinate_
    descent._fixed_lane_update`): unprojected or INDEX_MAP buckets, each
    bucket's (E_b, width_b, G) solutions carried to its next update and
    read by the passive rows' scoring — the descent holds no (E, d) table;
    the lanes' tables are written once, from the last solutions, for the
    lanes' models. No variances. Under a mesh the grid keeps its
    block loop over (G, E, d) state, unprojected buckets only.
    """
    if mesh is not None:
        return _fit_game_grid_mesh(coordinates, lane_weights, y, weights,
                                   base_offsets, task, update_sequence,
                                   n_sweeps, mesh)
    G = len(next(iter(lane_weights.values())))
    telemetry.count("game_grid.lanes", G)
    seq = list(update_sequence) if update_sequence else list(coordinates)
    trained = list(dict.fromkeys(seq))
    result = coordinate_descent(
        coordinates, y, weights, base_offsets, task, seq, n_sweeps,
        lanes={name: lane_weight_arrays(coordinates[name].config,
                                        lane_weights[name])
               for name in trained})
    state = result.lane_state
    models: list = [{} for _ in range(G)]
    for name in trained:
        coord, lanes = coordinates[name], state[name]
        ds = coord.dataset
        if isinstance(coord, FixedEffectCoordinate):
            for g in range(G):
                models[g][name] = FixedEffectModel(
                    GeneralizedLinearModel(Coefficients(lanes[g], None),
                                           task), ds.shard_name)
            continue
        for g, table in enumerate(_lane_models_tables(
                lanes, ds.table_maps, (ds.n_entities, ds.dim))):
            models[g][name] = RandomEffectModel(
                entity_name=ds.entity_name, feature_shard=ds.shard_name,
                task=task, coefficients=table, entity_keys=ds.entity_keys,
                key_to_index=ds.key_to_index)
    return GridFitOutcome(
        lane_models=[GameModel(m, task) for m in models],
        objective_histories=result.objective_history,
        coordinate_stats=result.coordinate_stats, stacked=state)


def _fit_game_grid_mesh(coordinates, lane_weights, y, weights, base_offsets,
                        task, update_sequence, n_sweeps,
                        mesh: Mesh) -> GridFitOutcome:
    """The grid under a mesh: lane-leading (G, E, d) state, every bucket's
    (entity × lane) solves sharded over the mesh's entity axis, a host-
    driven loop of gathers, solves and scatters a bucket; unprojected
    random effects only."""
    seq = list(update_sequence) if update_sequence else list(coordinates)
    trained = list(dict.fromkeys(seq))
    G = len(next(iter(lane_weights.values())))
    y = jnp.asarray(y, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    base = jnp.asarray(base_offsets, jnp.float32)
    n = int(y.shape[0])
    telemetry.count("game_grid.lanes", G)

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
            n_pad = pad_to_multiple(n, mesh.devices.size)
            batch = pad_batch(GLMBatch(ds.X, ds.y, ds.weights,
                                       jnp.zeros((n,), jnp.float32)), n_pad)
            batch = jax.device_put(batch, data_sharding(mesh))
            obj = make_objective(task, coord.config, d)
            prep[name] = ("fixed", batch, obj, l2s, l1s, static_cfg, n_pad)
            state[name] = jnp.zeros((G, d), jnp.float32)
        else:
            if ds.projection is not None:
                raise ValueError(
                    "fit_game_grid on a mesh does not support projected "
                    "random-effect coordinates (the estimator routes them "
                    "sequentially)")
            d = ds.dim
            obj = coord._block_objective(d)
            solver = _re_grid_solver(l1s is not None, static_cfg,
                                     coord.variance)
            # Per-block batches (X/y/weights are sweep- and lane-invariant)
            # built ONCE; only the per-lane offsets are replaced per update.
            blocks = [(block, jnp.asarray(block.entity_index),
                       ds.block_batch(block, np.zeros((n,), np.float32)))
                      for block in ds.blocks]
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
                offs_in = jax.device_put(offs_in, lane_sharding)
                w0s = jax.device_put(state[name], replicated(mesh))
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
                acc = None
                for block, ents, batch_base in blocks:
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
                margins, objective = _mesh_re_scores(
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

    # the single-device form of a random effect's lane state: its buckets'
    # (E_b, d, G) solutions, in block order
    stacked = {name: (jnp.asarray(s) if prep[name][0] == "fixed" else tuple(
        jnp.asarray(np.moveaxis(s[:, block.entity_index], 0, -1))
        for block in coordinates[name].dataset.blocks))
        for name, s in state_h.items()}
    return GridFitOutcome(lane_models=lane_models,
                          objective_histories=histories,
                          coordinate_stats=lane_stats, stacked=stacked)
