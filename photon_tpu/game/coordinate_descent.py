"""Coordinate descent over GAME coordinates.

Reference parity: com.linkedin.photon.ml.algorithm.CoordinateDescent —
optimize(updateSequence, descentIterations): per sweep, per coordinate, train
that coordinate with every OTHER coordinate's scores folded into the offsets,
then refresh its scores. Locked coordinates
(reference: partialRetrainLockedCoordinates) keep their pretrained model and
only contribute scores.

The host drives this outer loop (it is O(sweeps × coordinates) Python steps);
every per-coordinate solve and every scoring pass underneath is a jitted XLA
program, so the loop body never leaves the device except for the scalar
objective tracking.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from photon_tpu import checkpoint as _ckpt
from photon_tpu import telemetry
from photon_tpu.game.fixed_effect import FixedEffectCoordinate
from photon_tpu.game.model import GameModel
from photon_tpu.game.random_effect import RandomEffectCoordinate
from photon_tpu.ops.losses import TaskType, loss_fns

Coordinate = FixedEffectCoordinate | RandomEffectCoordinate


@dataclasses.dataclass
class CoordinateDescentResult:
    """A descent's model, objective history and per-update stats. A descent
    of ``lanes`` (a regularization grid's) gives them lane by lane — a list
    of histories and a list of stats dicts, one a lane — and no model:
    ``lane_state`` holds a fixed effect's (G, d) coefficients and a random
    effect's buckets' (E_b, width_b, G) solutions (`game.grid` makes the
    lanes' models from them)."""
    model: Optional[GameModel]
    objective_history: list  # total weighted loss after each coordinate update
    coordinate_stats: dict  # name -> list of per-update OptResult / RETrainStats
    lane_state: Optional[dict] = None


# The descent loop's glue is jitted so each coordinate update costs a fixed
# handful of device dispatches (train, score, offsets, objective) instead
# of one launch per eager primitive. The offsets sum is game.scoring._sum_scores (one shared jit cache).
# On the COMMON path (single device, no normalization, unprojected or
# INDEX_MAP, with or without an incremental prior) the whole update —
# offsets, solve, variances, score, objective — fuses into ONE program per
# coordinate (see _fused_fixed_update / RandomEffectCoordinate.
# fused_update_program), ≤1 dispatch per update. Inside a descent a fused
# random-effect coordinate's (E, d) table is WRITTEN in place and never
# read back by an update: each bucket's solution, in the bucket's own
# projected space, is carried to that bucket's next update as its warm
# start (`carried` below). A caller's table (initial_models, a grid's
# previous point, a checkpoint restore) is adopted ONCE, where it enters
# the descent (random_effect.initial_table + adopt_table: each entity's row
# found by key, warm starts read through the index maps, columns outside
# them cleared); an incremental coordinate's prior is gathered ONCE into
# the buckets' own spaces (random_effect.bucket_priors) and handed to
# every update of the descent. Every OTHER random-effect update (mesh,
# RANDOM projection, normalization, straggler_budget — the last returns
# None from fused_update_program because the compacted re-solve needs a
# host repack between passes) goes through the PIPELINED
# RandomEffectCoordinate.train(): bucket k+1's upload/solve dispatched
# before bucket k's readback, so the per-coordinate wall is
# max(device solve, host scatter) per bucket instead of their sum.
from photon_tpu.game.scoring import _sum_scores  # noqa: E402


@partial(jax.jit, static_argnames=("task",))
def _objective_at(task, y, weights, offsets, score):
    loss, _, _ = loss_fns(task)
    return jnp.sum(weights * loss(offsets + score, y))


# ------------------------------------------------- streamed (out-of-HBM) face
# When any fixed-effect coordinate's shard is a host ChunkedMatrix (the
# pod-scale regime), the inter-coordinate margin exchange goes HOST-side:
# every coordinate's score lives as a host (n,) f32 cache, offsets are a
# numpy sum over those caches (never a full-dataset device vector), and
# the tracking objective accumulates chunk-wise — each slice pays one
# small device partial, totals sum in f64 on host. The device only ever
# holds O(chunk) of the scalar columns, matching the streamed solvers'
# footprint story.


def _to_host_score(score) -> "np.ndarray":
    import numpy as np

    return score if isinstance(score, np.ndarray) else \
        np.asarray(jax.device_get(score), np.float32)


def _sum_scores_host(base, score_tuple):
    import numpy as np

    out = np.array(base, np.float32, copy=True)
    for s in score_tuple:
        out += np.asarray(s)
    telemetry.count("game_e2e.host_offset_sums")
    return out


def _objective_streamed(task, y, weights, offsets, score,
                        chunk_rows: int) -> float:
    """The tracking objective over host-resident columns, chunk-wise:
    per-slice jitted partial sums (one compile per slice shape), totals
    accumulated f64 on host — nothing dataset-sized crosses to device."""
    import numpy as np

    n = int(y.shape[0])
    parts = []
    for lo in range(0, n, chunk_rows):
        sl = slice(lo, min(lo + chunk_rows, n))
        parts.append(_objective_at(task, y[sl], weights[sl], offsets[sl],
                                   score[sl]))
        telemetry.count("game_e2e.objective_chunks")
    return float(np.sum(np.asarray(jax.device_get(parts), np.float64)))


# ------------------------------------------------- checkpoint (de)hydration
# The descent loop's crash-consistency cut is "coordinate updates 0..k
# complete": the progress payload carries every updated coordinate's model
# arrays + its SCORES (stored, not recomputed, so a resumed run's
# downstream low bits match the uninterrupted run's exactly), the
# objective history, and compact per-update stats. A live random-effect
# update additionally checkpoints bucket-level state under its own
# ``u<k>/re`` scope (game/random_effect.py).


def _descent_fingerprint(coordinates, update_sequence, n_sweeps, locked,
                         task, n_rows) -> str:
    """Stable identity of one descent invocation: restored state is only
    accepted by a loop solving the SAME problem (grid points with
    different reg weights hash apart)."""
    parts = []
    for name in update_sequence:
        c = coordinates[name]
        cfg = c.config
        parts.append((
            name, type(c).__name__, cfg.effective_optimizer().value,
            cfg.max_iters,
            cfg.tolerance, cfg.history, cfg.cg_max_iters,
            cfg.reg.reg_type.value, cfg.reg.alpha, float(cfg.reg_weight),
            cfg.regularize_intercept,
            getattr(c, "pipeline_depth", None),
            getattr(c, "straggler_budget", None),
        ))
    ident = repr((task.name, n_sweeps, tuple(update_sequence),
                  tuple(sorted(locked)), int(n_rows), parts))
    return hashlib.sha1(ident.encode()).hexdigest()[:12]


def _model_from_progress(progress, name, kind, coord, task):
    from photon_tpu.game.model import FixedEffectModel, RandomEffectModel
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel

    var = progress.get(f"m.{name}.var")
    var = jnp.asarray(var) if var is not None else None
    if kind == "fixed":
        return FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(jnp.asarray(progress[f"m.{name}.w"]), var),
                task),
            coord.dataset.shard_name)
    ds = coord.dataset
    return RandomEffectModel(
        entity_name=ds.entity_name, feature_shard=ds.shard_name, task=task,
        coefficients=jnp.asarray(progress[f"m.{name}.coeffs"]),
        entity_keys=ds.entity_keys, key_to_index=ds.key_to_index,
        variances=var)


def _stats_from_entry(entry, models):
    """Rehydrate a per-update stats record. Resumed stats carry the
    SCALARS (value/grad-norm/iteration/convergence); per-iteration
    histories died with the original process and come back as NaN."""
    from photon_tpu.game.random_effect import RETrainStats
    from photon_tpu.optim.tracker import OptResult

    if entry["kind"] == "re":
        return RETrainStats(int(entry["E"]), int(entry["c"]),
                            int(entry["f"]), int(entry["it"]))
    nan = jnp.full((1,), jnp.nan, jnp.float32)
    return OptResult(
        w=jnp.asarray(models[entry["name"]].model.coefficients.means),
        value=jnp.asarray(jnp.float32(entry["value"])),
        grad_norm=jnp.asarray(jnp.float32(entry["grad_norm"])),
        iterations=jnp.asarray(jnp.int32(entry["iterations"])),
        converged=jnp.asarray(bool(entry["converged"])),
        failed=jnp.asarray(bool(entry["failed"])),
        loss_history=nan, grad_norm_history=nan)


def _progress_payload(updated, models, scores, objective_history,
                      stats_entries, n_done) -> dict:
    import numpy as np

    from photon_tpu.game.model import FixedEffectModel

    payload = {"kind": "descent_progress", "n_done": int(n_done),
               "objective": [float(v) for v in objective_history],
               "stats": list(stats_entries),
               "updated": dict(updated)}
    for name, kind in updated.items():
        m = models[name]
        if isinstance(m, FixedEffectModel):
            payload[f"m.{name}.w"] = np.asarray(m.model.coefficients.means)
            if m.model.coefficients.variances is not None:
                payload[f"m.{name}.var"] = np.asarray(
                    m.model.coefficients.variances)
        else:
            payload[f"m.{name}.coeffs"] = np.asarray(m.coefficients)
            if m.variances is not None:
                payload[f"m.{name}.var"] = np.asarray(m.variances)
        payload[f"s.{name}"] = np.asarray(scores[name])
    return payload


@partial(jax.jit, static_argnames=("config", "task", "variance",
                                   "l2_positive"))
def _fused_fixed_update(batch, base, scores, w0, obj, l1, y, weights,
                        config, task, variance, l2_positive=False):
    """One program per fixed-effect update: offsets sum + solve + margins +
    objective (the grid path's _fixed_grid_update, lane-less). The
    objective uses the CALLER's y/weights (coordinate_descent's arguments,
    like _objective_at on the unfused path), which may differ from the
    dataset's. ``l2_positive``: `models.training.l2_positive` of the
    objective's configuration (FULL variances may then take the rows
    form where the shard has fewer rows than columns)."""
    from photon_tpu.data.matrix import matvec
    from photon_tpu.game.scoring import _sum_scores
    from photon_tpu.models.training import solve
    from photon_tpu.models.variance import compute_variances

    loss, _, _ = loss_fns(task)
    with telemetry.device_scope("game.objective"):
        offs = _sum_scores(base, scores)
    b = batch._replace(offsets=offs)
    with telemetry.device_scope("game_fixed.solve"):
        res = solve(obj, b, w0, config, l1_weight=l1)
    with telemetry.device_scope("game_fixed.variance"):
        var = compute_variances(obj, res.w, b, variance, l2_positive)
    with telemetry.device_scope("game_fixed.solve"):
        margin = matvec(batch.X, res.w)
    with telemetry.device_scope("game.objective"):
        objective = jnp.sum(weights * loss(offs + margin, y))
    return res, var, margin, objective


@partial(jax.jit, static_argnames=("config", "task"))
def _fixed_lane_update(batch, base, scores, w0s, obj, l2s, l1s, config,
                       task):
    """`_fused_fixed_update` for a regularization grid's G lanes: the
    vmapped solve of `game.grid._fixed_grid_update` on lane-minor offsets
    and margins, (n, G), as a random effect's lane update takes and gives
    them."""
    from photon_tpu.game.grid import _fixed_grid_update
    from photon_tpu.models.variance import VarianceComputationType

    with telemetry.device_scope("game.objective"):
        offs = _sum_scores(base[:, None], scores)
        offs = jnp.broadcast_to(offs, base.shape + (w0s.shape[0],))
    with telemetry.device_scope("game_fixed.solve"):
        res, _, margin, objective = _fixed_grid_update(
            batch, offs.T, w0s, obj, l2s, l1s, config,
            VarianceComputationType.NONE, task)
    return res, margin.T, objective


def _fixed_prior_objective(obj, coord: FixedEffectCoordinate, prior):
    """``obj`` regularized toward the coordinate's prior, as
    `FixedEffectCoordinate.train` regularizes it; ``obj`` where there is
    none."""
    dist = coord.prior_distribution(prior)
    if dist is None:
        return obj
    return dataclasses.replace(
        obj, prior_mean=jnp.asarray(dist.mean),
        prior_precision=jnp.asarray(dist.precision_diag))


def _fixed_fusable(coord: FixedEffectCoordinate) -> bool:
    from photon_tpu.data.dataset import ChunkedMatrix
    from photon_tpu.data.matrix import (BlockedEllRows, PermutedHybridRows,
                                        ShardedHybridRows)
    from photon_tpu.optim.config import OptimizerType

    # PermutedHybridRows keeps the train_glm route: that boundary owns the
    # permuted↔original coefficient-space translation — this fused program
    # calling solve() directly would store PERMUTED coefficients in the
    # model and scoring would re-permute them (silently wrong margins).
    # ChunkedMatrix keeps it too: the streamed solve is a host loop, not a
    # jittable solve() call.
    return (coord.mesh is None
            and not isinstance(coord.dataset.X,
                               (ShardedHybridRows, PermutedHybridRows,
                                BlockedEllRows, ChunkedMatrix))
            and (coord.normalization is None
                 or coord.normalization.is_identity)
            # OWL-QN keeps the train_glm route: its single-device dense
            # solves use the pallas fused value+grad kernel (one X pass per
            # evaluation), which this fused program does not wire up
            and coord.config.effective_optimizer()
            is not OptimizerType.OWLQN)


def coordinate_descent(
    coordinates: dict,
    y,
    weights,
    base_offsets,
    task: TaskType,
    update_sequence: Optional[list] = None,
    n_sweeps: int = 1,
    locked: frozenset = frozenset(),
    initial_models: Optional[dict] = None,
    incremental: frozenset = frozenset(),
    priors: Optional[dict] = None,
    lanes: Optional[dict] = None,
) -> CoordinateDescentResult:
    """Run `n_sweeps` passes of the update sequence and return the GameModel.

    `coordinates`: name -> FixedEffectCoordinate | RandomEffectCoordinate.
    `locked` coordinates must appear in `initial_models`; they are scored but
    never retrained. Unlocked coordinates warm-start from `initial_models`
    when given (the estimator's warm start across regularization weights).
    `incremental` coordinates additionally use their initial model as an
    informative Gaussian prior for every retrain (reference: incremental
    training via PriorDistribution) — the prior stays the ORIGINAL initial
    model across sweeps, not the previous sweep's update.

    ``lanes``: a regularization grid run as G lanes of this one descent
    (`game.grid.fit_game_grid`), name -> the coordinate's lanes' (l2s, l1s,
    weight-normalized configuration) (`models.training.
    lane_weight_arrays`). Every update is then the one-dispatch update
    with a lane axis — scores (n, G), objectives (G,) — every descent
    counter is summed over the lanes, and the result comes lane by lane
    (`CoordinateDescentResult`). One device, from zeros: no locked,
    initial or prior model, no host stream, no checkpoint.
    """
    update_sequence = update_sequence or list(coordinates)
    G = 1 if lanes is None else len(next(iter(lanes.values()))[0])
    models = dict(initial_models or {})
    if priors is None:
        priors = {name: models[name] for name in incremental if name in models}
    for name in incremental:
        if name not in priors:
            raise ValueError(
                f"incremental coordinate {name!r} needs an initial model")
    for name in locked:
        if name not in models:
            raise ValueError(f"locked coordinate {name!r} needs an initial model")

    import numpy as np

    from photon_tpu.data.dataset import ChunkedMatrix

    # STREAMED regime: any coordinate whose shard is a host ChunkedMatrix
    # flips the whole descent's margin exchange host-side — scores live as
    # host (n,) caches, offsets are numpy sums, objectives accumulate
    # chunk-wise, and the dataset-sized scalar columns never device-put
    # whole (the pod-scale GAME composition; module comment above).
    chunked_coords = {
        name for name, c in coordinates.items()
        if isinstance(getattr(c.dataset, "X", None), ChunkedMatrix)
    }
    streamed = bool(chunked_coords)
    if lanes is not None and (streamed or locked or models or priors):
        raise ValueError("a descent of lanes starts every lane from zeros "
                         "on one device: no locked, initial or prior "
                         "model, no host stream")
    if streamed:
        y = np.asarray(y, np.float32)
        weights = np.asarray(weights, np.float32)
        base = np.asarray(base_offsets, np.float32)
        obj_chunk_rows = min(coordinates[n].dataset.X.chunk_rows
                             for n in chunked_coords)
    else:
        y = jnp.asarray(y, jnp.float32)
        weights = jnp.asarray(weights, jnp.float32)
        base = jnp.asarray(base_offsets, jnp.float32)
        no_score = jnp.zeros(base.shape + ((G,) if lanes else ()),
                             jnp.float32)

    # Scores of any pre-existing models participate as offsets from the start
    # (reference: CoordinateDescent seeds offsets from the initial GameModel).
    # This covers ALL coordinates with models — including ones left out of a
    # caller-supplied update_sequence (e.g. locked, score-only coordinates).
    scores = {
        name: coordinates[name].score(models[name])
        for name in coordinates
        if name in models
    }
    if streamed:
        scores = {name: _to_host_score(s) for name, s in scores.items()}

    objective_history: list = []
    coordinate_stats: dict = {name: [] for name in update_sequence}

    from photon_tpu.game.dataset import GLMBatch
    from photon_tpu.game.model import (
        FixedEffectModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.models.variance import VarianceComputationType
    from photon_tpu.models.training import (
        _l1_lam,
        _static_config,
        l2_positive,
        make_objective,
    )

    from photon_tpu.game.random_effect import (
        RETrainStats,
        adopt_table,
        bucket_priors,
        cold_warm_starts,
        initial_table,
    )

    ck = _ckpt.current() if lanes is None else None
    cd_scope = contextlib.nullcontext()
    if ck is not None:
        fp = _descent_fingerprint(coordinates, update_sequence, n_sweeps,
                                  locked, task, int(y.shape[0]))
        cd_scope = ck.scope(f"game-{fp}-{ck.invocation(fp)}")

    deferred_re: list = []  # (stats-list index slot fillers for fused REs)
    # coordinate name -> the buckets' solutions its last fused update left,
    # each in its bucket's own space: the next update's warm starts. A name
    # in here also says that this call made the coordinate's (E, d) table
    # (lanes make none). A lane fixed effect's (G, d) coefficients beside.
    carried: dict = {}
    # coordinate name -> its prior in the form its fused update takes (the
    # fixed effect's Objective, the buckets' (mean, precision) pairs),
    # made once a descent: the prior stays the caller's across sweeps
    fused_priors: dict = {}
    update_log: list = []  # (sweep, coordinate) per objective_history entry
    done_updates = 0
    stats_entries: list = []
    updated: dict = {}  # coordinate name -> "fixed" | "re", updated so far
    with cd_scope:
        progress = ck.restore("progress") if ck is not None else None
        if progress is not None:
            done_updates = int(progress["n_done"])
            objective_history = [float(v) for v in progress["objective"]]
            stats_entries = list(progress["stats"])
            updated = dict(progress["updated"])
            for name, kind in updated.items():
                models[name] = _model_from_progress(progress, name, kind,
                                                    coordinates[name], task)
                # streamed regime: restored margin caches stay HOST
                s_np = np.asarray(progress[f"s.{name}"], np.float32)
                scores[name] = s_np if streamed else jnp.asarray(s_np)
            for e in stats_entries:
                coordinate_stats[e["name"]].append(
                    _stats_from_entry(e, models))
            telemetry.count("checkpoint.descent_restores")

        upd = -1
        for sweep in range(n_sweeps):
            telemetry.count("game.sweeps", G)
            for name in update_sequence:
                if name in locked:
                    continue
                upd += 1
                update_log.append((sweep, name))
                if upd < done_updates:
                    continue  # restored from the checkpoint image above
                telemetry.count("game.coordinate_updates", G)
                coord = coordinates[name]
                lane = None if lanes is None else lanes[name]
                warm = models.get(name)
                prior = priors.get(name)
                others = tuple(s for o, s in scores.items() if o != name)
                if not streamed:
                    # always as many as the other coordinates: a fused
                    # update's program is then the same in the first sweep,
                    # when some have no score yet, as in every later one
                    others += (no_score,) * (len(coordinates) - 1
                                             - len(others))
                # per-update sub-scope: a live random-effect update's
                # bucket-level state lands under u<k>/re and is dropped
                # the moment the update completes
                u_scope = (ck.scope(f"u{upd}") if ck is not None
                           else contextlib.nullcontext())
                stat_entry: Optional[dict] = None
                with u_scope:
                    # The streamed regime keeps EVERY update on the
                    # host-cache exchange (fused device updates would pull
                    # the (n,) margin vectors back on device): each update
                    # is still one train dispatch + one scoring stream.
                    if (isinstance(coord, FixedEffectCoordinate)
                            and not streamed
                            and (lane or _fixed_fusable(coord))):
                        ds = coord.dataset
                        w0 = jnp.zeros((ds.dim,), jnp.float32)
                        if warm is not None and \
                                warm.model.weights.shape[0] == ds.dim:
                            w0 = jnp.asarray(warm.model.weights)
                        batch = GLMBatch(ds.X, ds.y, ds.weights, base)
                        obj = fused_priors.get(name)
                        if obj is None:
                            obj = fused_priors[name] = _fixed_prior_objective(
                                make_objective(task, coord.config, ds.dim),
                                coord, prior)
                        if lane is not None:
                            res, margin, objective = _fixed_lane_update(
                                batch, base, others,
                                carried.get(name, jnp.zeros(
                                    (G, ds.dim), jnp.float32)),
                                obj, *lane, task)
                            carried[name] = res.w
                        else:
                            res, var, margin, objective = \
                                _fused_fixed_update(
                                    batch, base, others, w0, obj,
                                    _l1_lam(coord.config), y, weights,
                                    _static_config(coord.config), task,
                                    coord.variance,
                                    l2_positive(coord.config))
                            models[name] = FixedEffectModel(
                                GeneralizedLinearModel(
                                    Coefficients(res.w, var), task),
                                ds.shard_name)
                        scores[name] = margin
                        coordinate_stats[name].append(res)
                        objective_history.append(objective)
                        if telemetry.enabled():  # two tiny dispatches
                            telemetry.count_device(
                                "game_fixed.row_iterations",
                                res.iterations.astype(jnp.float32) * ds.n)
                        if ck is not None:
                            stat_entry = {
                                "name": name, "kind": "fixed",
                                "value": float(res.value),
                                "grad_norm": float(res.grad_norm),
                                "iterations": int(res.iterations),
                                "converged": bool(res.converged),
                                "failed": bool(res.failed)}
                    else:
                        # fused_update_program gates itself: it returns
                        # None for mesh / RANDOM projection /
                        # normalization / straggler-budget coordinates
                        # (the budget gate logs once at INFO and counts on
                        # game_re.fused_gate_offs), which then train on
                        # the pipelined block loop below.
                        fused = (coord.fused_update_program(
                            *(() if lane is None else (G, lane[2])))
                                 if isinstance(coord, RandomEffectCoordinate)
                                 and not streamed else None)
                        if fused is None and lane is not None:
                            raise ValueError(
                                f"coordinate {name!r} has no one-dispatch "
                                "update (variances, a RANDOM projection, "
                                "normalization or a straggler budget): its "
                                "grid is fitted a point at a time")
                        if fused is not None:
                            fn, blocks_args, plan, objs, lam = fused
                            ds = coord.dataset
                            E, d = ds.n_entities, ds.dim
                            if lane is not None:  # the lanes' (l2s, l1s)
                                lam = lane[:2]
                            # the update writes the table in place and
                            # takes the buckets' warm starts beside it
                            # (the program donates both): a table this
                            # descent made is handed over as it is, with
                            # the solutions its last update left; a
                            # caller's model is copied (each entity's row
                            # by key) and adopted first
                            if name in carried:
                                coeffs0 = (None if lane is not None
                                           else warm.coefficients)
                                warm_b = carried.pop(name)
                                telemetry.count("game_re.warm_carried", G)
                            elif warm is not None and warm.dim == d:
                                coeffs0, warm_b = adopt_table(
                                    initial_table(warm, ds.entity_keys),
                                    blocks_args)
                                telemetry.count("game_re.warm_adopted")
                            else:
                                coeffs0 = (None if lane is not None else
                                           jnp.zeros((E, d), jnp.float32))
                                warm_b = cold_warm_starts(blocks_args, d, G)
                                telemetry.count("game_re.warm_cold", G)
                            bucket_prior = None
                            if prior is not None and prior.dim == d:
                                if name not in fused_priors:
                                    fused_priors[name] = bucket_priors(
                                        prior, ds.entity_keys, blocks_args)
                                bucket_prior = fused_priors[name]
                                telemetry.count(
                                    "game_re.fused_prior_updates")
                            if coord.variance is not \
                                    VarianceComputationType.NONE:
                                telemetry.count(
                                    "game_re.variance_lanes",
                                    sum(int(ents.shape[0])
                                        for _, ents, _, _ in blocks_args))
                                telemetry.count(
                                    "game_re.variance_rows_lanes",
                                    coord.variance_rows_lanes())
                            (coeffs, variances, margin, objective, st,
                             values, carried[name]) = fn(
                                coeffs0, warm_b, base, others, objs, lam,
                                blocks_args, plan, y, weights, bucket_prior)
                            # the ONE dispatch solved every block of the
                            # coordinate (the pipelined loop in
                            # RandomEffectCoordinate.train counts its own);
                            # a block's G lanes step together, so each
                            # counts the block's lock-step iterations
                            telemetry.count("game_re.blocks",
                                            G * len(blocks_args))
                            # where the update read its margins from
                            telemetry.count("game_re.block_scored_rows",
                                            G * plan.n_block_rows)
                            telemetry.count("game_re.table_scored_rows",
                                            G * plan.n_table_rows)
                            telemetry.count_device(
                                "game_re.row_iterations", st[3])
                            telemetry.count_device(
                                "game_re.block_steps", st[4], scale=G)
                            telemetry.count_device(
                                "game_re.moved_row_iterations", st[5])
                            telemetry.count_device(
                                "game_re.iterations", st[2])
                            telemetry.count_device(
                                "game_re.linesearch_trials", st[6])
                            if lane is None:
                                models[name] = RandomEffectModel(
                                    entity_name=ds.entity_name,
                                    feature_shard=ds.shard_name,
                                    task=task,
                                    coefficients=coeffs,
                                    entity_keys=ds.entity_keys,
                                    key_to_index=ds.key_to_index,
                                    variances=variances,
                                )
                            scores[name] = margin
                            if ck is None:
                                # device scalars; finalized into
                                # RETrainStats below
                                slot = len(coordinate_stats[name])
                                coordinate_stats[name].append(None)
                                deferred_re.append(
                                    (name, slot, E, values, st))
                            else:
                                # checkpointing forces the stats now —
                                # the progress payload needs host values
                                c_, f_, it_, ri_ = jax.device_get(st[:4])
                                c_, f_, it_ = int(c_), int(f_), int(it_)
                                coordinate_stats[name].append(
                                    RETrainStats(E, c_, f_, it_,
                                                 row_iterations=float(ri_),
                                                 entity_values=values))
                                stat_entry = {"name": name, "kind": "re",
                                              "E": E, "c": c_, "f": f_,
                                              "it": it_}
                            objective_history.append(objective)
                        else:
                            if streamed:
                                # host margin caches: numpy offsets sum,
                                # chunk-accumulated objective, score back
                                # into a host cache (4 B/row; no (n,)
                                # device vector anywhere in the exchange)
                                if name in chunked_coords:
                                    telemetry.count(
                                        "game_e2e.streamed_fixed_updates")
                                offsets_full = _sum_scores_host(base,
                                                                others)
                            else:
                                offsets_full = _sum_scores(base, others)
                            model, stats = coord.train(offsets_full,
                                                       warm_start=warm,
                                                       prior=prior)
                            models[name] = model
                            scores[name] = coord.score(model)
                            coordinate_stats[name].append(stats)
                            if streamed:
                                scores[name] = _to_host_score(scores[name])
                                objective_history.append(
                                    _objective_streamed(
                                        task, y, weights, offsets_full,
                                        scores[name], obj_chunk_rows))
                            else:
                                # device scalar now; host conversion is
                                # deferred below so the descent loop never
                                # blocks on a readback mid-sweep
                                objective_history.append(
                                    _objective_at(task, y, weights,
                                                  offsets_full,
                                                  scores[name]))
                            if ck is not None:
                                if isinstance(stats, RETrainStats):
                                    stat_entry = {
                                        "name": name, "kind": "re",
                                        "E": stats.n_entities,
                                        "c": stats.n_converged,
                                        "f": stats.n_failed,
                                        "it": stats.total_iterations}
                                else:
                                    stat_entry = {
                                        "name": name, "kind": "fixed",
                                        "value": float(stats.value),
                                        "grad_norm": float(stats.grad_norm),
                                        "iterations": int(stats.iterations),
                                        "converged": bool(stats.converged),
                                        "failed": bool(stats.failed)}
                if ck is not None:
                    # the update is complete: drop its sub-scope state,
                    # force its objective to host, and publish the
                    # progress cut (updates 0..upd done)
                    ck.clear(f"u{upd}", prefix=True)
                    objective_history[-1] = float(
                        jax.device_get(objective_history[-1]))
                    stats_entries.append(stat_entry)
                    from photon_tpu.game.model import (
                        FixedEffectModel as _FEM,
                    )

                    updated[name] = ("fixed" if isinstance(models[name],
                                                           _FEM) else "re")
                    ck.update("progress", _progress_payload(
                        updated, models, scores, objective_history,
                        stats_entries, upd + 1))
                    ck.note_evaluations()
                    ck.maybe_snapshot()

    # one concurrent device_get for every deferred scalar (a float() per
    # entry would block on one device→host readback each)
    objective_history, re_stats = jax.device_get(
        (objective_history, [st for *_, st in deferred_re]))
    if lanes is not None:
        return _by_lane(G, objective_history, coordinate_stats, deferred_re,
                        re_stats, carried)
    objective_history = [float(v) for v in objective_history]
    if telemetry.enabled():
        # the GAME iteration stream: one event per coordinate update, in
        # update order (objectives are deferred device scalars, so events
        # emit here — after the one batched readback — not mid-sweep)
        for i, ((sweep, name), obj_v) in enumerate(
                zip(update_log, objective_history)):
            telemetry.iteration("game_descent", i, obj_v,
                                coordinate=name, sweep=sweep)
    from photon_tpu.game.random_effect import RETrainStats

    for (name, slot, E, values, _), (c, f, it, ri, *_) in zip(
            deferred_re, re_stats):
        coordinate_stats[name][slot] = RETrainStats(
            E, int(c), int(f), int(it), row_iterations=float(ri),
            entity_values=values)
    ordered = {name: models[name] for name in update_sequence}
    for name in coordinates:  # score-only coordinates outside the sequence
        if name in models and name not in ordered:
            ordered[name] = models[name]
    return CoordinateDescentResult(
        GameModel(ordered, task), objective_history, coordinate_stats
    )


def _by_lane(G: int, history, stats: dict, deferred_re: list, re_stats,
             state: dict) -> CoordinateDescentResult:
    """A descent of G lanes' result, lane by lane: each lane's objective
    history and per-update stats as a descent of its own gives them (one
    readback for the fixed effects' stats and the entities' values), and
    the lanes' last state."""
    from photon_tpu.game.random_effect import RETrainStats

    stats, values = jax.device_get(
        (stats, [v for _, _, _, v, _ in deferred_re]))
    for (name, slot, E, _, _), (c, f, it, ri, *_), v in zip(
            deferred_re, re_stats, values):
        stats[name][slot] = [
            RETrainStats(E, int(c[g]), int(f[g]), int(it[g]),
                         row_iterations=float(ri[g]),
                         entity_values=v[:, g]) for g in range(G)]
    return CoordinateDescentResult(
        None, [[float(h[g]) for h in history] for g in range(G)],
        [{name: [s[g] if isinstance(s, list) else
                 jax.tree_util.tree_map(lambda x, g=g: x[g], s)
                 for s in per] for name, per in stats.items()}
         for g in range(G)],
        lane_state=state)


# ----------------------------------------------------------------- contracts
# The GAME descent loop's ≤1-dispatch-per-update claim rests on
# _fused_fixed_update being one clean device program: no collectives, no
# host exits, f32 accumulation, nothing baked into the trace
# (photon_tpu/analysis enforces it statically on every PR).
from photon_tpu.analysis.contracts import register_contract  # noqa: E402


@register_contract(
    name="game_fixed_update",
    description="the fused fixed-effect coordinate update: offsets sum + "
                "full L-BFGS solve + margins + objective as ONE device "
                "program with zero communication and zero host exits",
    collectives={}, tags=("game",))
def _contract_game_fixed_update():
    import numpy as np

    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.models.training import (_static_config, make_objective)
    from photon_tpu.models.variance import VarianceComputationType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    n, d = 32, 6
    rng = np.random.default_rng(0)
    task = TaskType.LOGISTIC_REGRESSION
    cfg = OptimizerConfig(max_iters=5, tolerance=1e-7, reg=l2(),
                          reg_weight=0.4, history=3)
    obj = make_objective(task, cfg, d)
    batch = GLMBatch(
        X=jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
        y=jnp.asarray((rng.uniform(size=n) < 0.5).astype(np.float32)),
        weights=jnp.ones((n,), jnp.float32),
        offsets=jnp.zeros((n,), jnp.float32))
    base = jnp.zeros((n,), jnp.float32)
    scores = (jnp.zeros((n,), jnp.float32),)  # one other coordinate
    w0 = jnp.zeros((d,), jnp.float32)
    fn = lambda b, bs, sc, w, o, y, wt: _fused_fixed_update(  # noqa: E731
        b, bs, sc, w, o, None, y, wt, _static_config(cfg), task,
        VarianceComputationType.NONE)
    return fn, (batch, base, scores, w0, obj, batch.y, batch.weights)


@register_contract(
    name="game_streamed_fixed_evaluation",
    description="the pod-scale GAME fixed-effect coordinate's per-sweep "
                "collective budget: one streamed-mesh objective "
                "evaluation — chunk partials accumulated collective-FREE "
                "across chunks, closed by exactly ONE hierarchical psum "
                "(the whole evaluation's communication)",
    collectives={"psum": 1}, tags=("game", "mesh-streamed"))
def _contract_game_streamed_fixed_evaluation():
    from photon_tpu.optim.streamed import _contract_problem, _mesh_ops
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    ops = _mesh_ops(mesh)
    obj, w, batch = _contract_problem(mesh)

    def fn(o, wv, b):
        # two chunks' partials accumulate elementwise (no collective),
        # then the evaluation closes with finish's single psum — the
        # exact shape of one fixed-effect evaluation in a GAME sweep
        _, p1 = ops.chunk_init(o, wv, b)
        _, p2 = ops.chunk_init(o, wv, b)
        acc = jax.tree_util.tree_map(jnp.add, p1, p2)
        return ops.finish(o, wv, acc)

    return fn, (obj, w, batch)
