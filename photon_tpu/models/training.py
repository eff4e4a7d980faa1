"""Distributed GLM optimization problems.

Reference parity: com.linkedin.photon.ml.optimization.game.
{DistributedOptimizationProblem, SingleNodeOptimizationProblem}.

Where the reference broadcasts coefficients to executors and treeAggregates
per-partition (value, gradient) pairs, here the *entire solver loop* is one
jit-compiled XLA program over a `Mesh`: the batch is sharded across the
``data`` axis, coefficients are replicated, and XLA's SPMD partitioner turns
the X·w / Xᵀr contractions into per-device matmuls + a single all-reduce over
the ICI — no host round-trips between iterations, no per-iteration dispatch.

The manual-collective path (Objective(axis_name=...) under shard_map) computes
the same thing and is exercised by tests/dryrun to pin the communication
pattern explicitly.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu.parallel.mesh import shard_map

from photon_tpu.data.dataset import (ChunkedBatch, ChunkedMatrix, GLMBatch,
                                     pad_batch)
from photon_tpu.data.matrix import (BlockedEllRows, HybridRows,
                                    PermutedHybridRows,
                                    ShardedBlockedEllRows,
                                    ShardedHybridRows,
                                    ShardedPermutedHybridRows, SparseRows)

# The permuted-coordinate layouts (solver works in permuted space;
# translation at this module's public boundary) and their mesh-sharded
# forms — the blocked-ELL pair joins the round-5 permuted pair.
_PERMUTED_TYPES = (PermutedHybridRows, ShardedPermutedHybridRows,
                   BlockedEllRows, ShardedBlockedEllRows)
_SINGLE_DEVICE_PERMUTED = (PermutedHybridRows, BlockedEllRows)
_SHARDED_TYPES = (ShardedHybridRows, ShardedPermutedHybridRows,
                  ShardedBlockedEllRows)
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.models.variance import VarianceComputationType, compute_variances
from photon_tpu.ops.fused import (
    lowering_available as fused_lowering_available,
)
from photon_tpu.ops.losses import TaskType
from photon_tpu.ops.objective import Objective
from photon_tpu.optim.config import OptimizerConfig, OptimizerType
from photon_tpu.ops.lane_objective import supports_lanes
from photon_tpu.optim.lane_lbfgs import minimize_lbfgs_margin_lanes
from photon_tpu.optim.lane_owlqn import minimize_owlqn_lanes
from photon_tpu.optim.lane_tron import minimize_tron_margin_lanes
from photon_tpu.optim.lbfgs import minimize_lbfgs_margin
from photon_tpu.optim.owlqn import minimize_owlqn
from photon_tpu.optim.tron import minimize_tron_margin
from photon_tpu.optim.tracker import OptResult
from photon_tpu.parallel.mesh import data_sharding, pad_to_multiple, replicated

# Run telemetry (no-op without an attached Run): the solve dispatches
# record their jit-cache argument signatures, so the run report counts
# retraces (`retrace.new_signatures`) and flags weak-type drift — the
# dynamic face of the analysis retrace-hazard rule. The attribution
# ledger (photon_tpu/profiling, same off-state contract) additionally
# measures each dispatch's wall time: a NEW-signature dispatch pays
# trace+lower+compile inline, so the ledger's compile accounting rides
# the same signature log.
from photon_tpu import profiling, telemetry


def make_objective(
    task: TaskType,
    config: OptimizerConfig,
    n_features: int,
    axis_name: Optional[str] = None,
    prior_mean=None,
    prior_precision=None,
    intercept_index: Optional[int] = -1,
    normalization=None,
    prior_full_precision=None,
    fused: bool = False,
) -> Objective:
    """Build the smooth objective for one coordinate's solve.

    intercept_index: which column to exclude from regularization when
    ``config.regularize_intercept`` is False. Defaults to -1 because
    photon_tpu's design-matrix builders (``data.feature_bags``) append the
    intercept as the LAST column; callers building their own X with a
    different layout must pass the actual index (or None for no intercept).

    normalization: optional data.normalization.NormalizationContext; its
    factors/shifts are folded into the objective's margin so the solve runs
    in normalized coefficient space without materializing normalized data.
    """
    reg_mask = None
    if not config.regularize_intercept and intercept_index is not None:
        reg_mask = jnp.ones((n_features,), jnp.float32).at[intercept_index].set(0.0)
    norm_factors = norm_shifts = None
    if normalization is not None and not normalization.is_identity:
        if normalization.factors is not None:
            norm_factors = jnp.asarray(normalization.factors, jnp.float32)
        if normalization.shifts is not None:
            norm_shifts = jnp.asarray(normalization.shifts, jnp.float32)
    return Objective(
        task=task,
        # np.float32, NOT the raw Python float: a weak-typed scalar leaf
        # would make jit's cache key differ between scalar and array
        # callers (the analysis retrace-hazard rule pins this canon).
        l2=np.float32(config.reg.l2_weight(config.reg_weight)),
        axis_name=axis_name,
        fused=fused,
        reg_mask=reg_mask,
        prior_mean=prior_mean,
        prior_precision=prior_precision,
        prior_full_precision=(None if prior_full_precision is None
                              else jnp.asarray(prior_full_precision, jnp.float32)),
        norm_factors=norm_factors,
        norm_shifts=norm_shifts,
    )


def solve(
    obj: Objective,
    batch: GLMBatch,
    w0: jax.Array,
    config: OptimizerConfig,
    l1_weight: Optional[float] = None,
) -> OptResult:
    """Run the configured solver on one (possibly sharded) batch.

    jit/vmap-safe: called inside jit for the fixed effect, inside vmap for
    per-entity random effects.
    """
    vg = lambda w: obj.value_and_grad(w, batch)
    opt = config.effective_optimizer()
    if opt is OptimizerType.OWLQN:
        lam = config.reg.l1_weight(config.reg_weight) if l1_weight is None else l1_weight
        return minimize_owlqn(
            vg, w0, lam,
            max_iters=config.max_iters, tolerance=config.tolerance,
            history=config.history, reg_mask=obj.reg_mask,
        )
    if opt is OptimizerType.TRON:
        return minimize_tron_margin(
            obj, batch, w0,
            max_iters=config.max_iters, tolerance=config.tolerance,
            cg_max_iters=config.cg_max_iters,
        )
    # Smooth solves use the margin-cached L-BFGS: the GLM margin is linear
    # in w, so line-search evaluations run elementwise on cached (z, dz) —
    # two X passes per iteration total instead of two per evaluation.
    return minimize_lbfgs_margin(
        obj, batch, w0,
        max_iters=config.max_iters, tolerance=config.tolerance,
        history=config.history,
    )


@partial(jax.jit, static_argnames=("config", "variance"))
def _train_run(batch, w0, obj, l1_lam, config, variance):
    """Module-level jitted solve+variance runner. Objective is a pytree
    argument (ops/objective.py registration) and BOTH regularization
    weights are dynamic (obj.l2 leaf, l1_lam argument), so repeated
    train_glm calls on same-shaped data — including every point of a
    reg-weight grid or GP-tuner sweep — hit the jit cache instead of
    retracing (a retrace of the solver loop costs ~2s on TPU). ``config``
    is normalized by the caller so its cache key is weight-independent."""
    res = solve(obj, batch, w0, config, l1_weight=l1_lam)
    var = compute_variances(obj, res.w, batch, variance)
    return res, var


def _hybrid_specs(X, axes: tuple, wrap=lambda s: s):
    """(batch_spec_tree) for a sharded hybrid batch: every per-shard data
    leaf's axis 0 over all mesh axes, global vectors replicated. ``wrap``
    lifts each PartitionSpec (e.g. into a NamedSharding for device_put)."""
    dat, rep = wrap(P(axes)), wrap(P())
    if isinstance(X, ShardedBlockedEllRows):
        x = ShardedBlockedEllRows(
            dense=dat,
            ell_pcols=tuple(dat for _ in X.ell_pcols),
            ell_vals=tuple(dat for _ in X.ell_vals),
            row_pos=dat,
            bucket_rows=tuple(dat for _ in X.bucket_rows),
            bucket_vals=tuple(dat for _ in X.bucket_vals),
            perm_cols=rep, inv_perm=rep,
            n_features=X.n_features, n_prefix=X.n_prefix,
            last_col_pos=X.last_col_pos, tail_nnz=X.tail_nnz)
    elif isinstance(X, ShardedPermutedHybridRows):
        x = ShardedPermutedHybridRows(
            dense=dat, tail_pcols=dat, tail_vals=dat, row_bounds=dat,
            bucket_rows=tuple(dat for _ in X.bucket_rows),
            bucket_vals=tuple(dat for _ in X.bucket_vals),
            perm_cols=rep, inv_perm=rep,
            n_features=X.n_features, n_prefix=X.n_prefix,
            last_col_pos=X.last_col_pos)
    else:
        x = ShardedHybridRows(dense=dat, dense_cols=rep, tail_rows=dat,
                              tail_cols=dat, tail_vals=dat,
                              n_features=X.n_features)
    return GLMBatch(X=x, y=dat, weights=dat, offsets=dat)


@partial(jax.jit, static_argnames=("config", "variance", "mesh"))
def _train_run_sharded(batch, w0, obj, l1_lam, config, variance, mesh):
    """The ShardedHybridRows solve: whole solver under shard_map, so the
    flat-COO tail gather/scatter is provably LOCAL to each device — the only
    cross-device traffic is the Objective's fused (value, grad) psum. XLA's
    SPMD partitioner cannot make that locality guarantee for a global
    segment_sum whose indices it can't reason about; shard_map states it.
    """
    axes = tuple(mesh.axis_names)
    batch_spec = _hybrid_specs(batch.X, axes)
    obj_spec = jax.tree_util.tree_map(lambda _: P(), obj)

    def body(b, w0, obj, l1):
        bl = b._replace(X=b.X.local())
        res = solve(obj, bl, w0, config, l1_weight=l1)
        var = compute_variances(obj, res.w, bl, variance)
        return res, var

    return shard_map(
        body, mesh=mesh,
        in_specs=(batch_spec, P(), obj_spec, P()),
        out_specs=P(),
    )(batch, w0, obj, l1_lam)


@partial(jax.jit, static_argnames=("config", "variance", "mesh"))
def _train_run_sharded_grid(batch, w0, obj, l2s, l1s, config, variance,
                            mesh):
    """Reg-weight grid over a ShardedHybridRows batch: the vmapped lanes of
    _train_run_grid inside the shard_map of _train_run_sharded — per-device
    tails stay local, each lane's (value, grad) psums batch into one
    collective per evaluation across the whole sweep."""
    import dataclasses as _dc

    axes = tuple(mesh.axis_names)
    batch_spec = _hybrid_specs(batch.X, axes)
    obj_spec = jax.tree_util.tree_map(lambda _: P(), obj)

    def body(b, w0, obj, l2s, l1s):
        bl = b._replace(X=b.X.local())

        def one(l2v, l1v):
            o = _dc.replace(obj, l2=l2v)
            res = solve(o, bl, w0, config, l1_weight=l1v)
            var = compute_variances(o, res.w, bl, variance)
            return res, var

        if l1s is None:
            return jax.vmap(lambda l2v: one(l2v, None))(l2s)
        return jax.vmap(one)(l2s, l1s)

    return shard_map(
        body, mesh=mesh,
        in_specs=(batch_spec, P(), obj_spec, P(), P()),
        out_specs=P(),
    )(batch, w0, obj, l2s, l1s)


def _matrix_dim(X) -> int:
    return (X.n_features
            if isinstance(X, (SparseRows, HybridRows, ShardedHybridRows,
                              PermutedHybridRows,
                              ShardedPermutedHybridRows, BlockedEllRows,
                              ShardedBlockedEllRows, ChunkedMatrix))
            else X.shape[1])


@partial(jax.jit, static_argnames=("scope",))
def _reorder_columns(v, order, scope):
    """``v[..., order]``: the move between model space and a permuted
    layout's column order, (d,) or lane-major (G, d). The gather the eager
    indexing ran, as a program of its own so that it carries a device
    scope (a 10M-element gather is tens of milliseconds of every solve)."""
    with telemetry.device_scope(scope):
        return v[..., order]


def _permuted_prep(X: PermutedHybridRows, w0, prior_mean, prior_precision,
                   norm):
    """Translate original-space side inputs into the permuted feature space
    a PermutedHybridRows solve runs in (see the class docstring): (d,)
    vectors gather through perm_cols; the normalization context used by the
    OBJECTIVE carries permuted factors/shifts (elementwise transforms
    commute with the permutation, so post-solve conversions run in
    original space after `to_model_space`)."""
    import dataclasses as _dc

    w0 = _reorder_columns(jnp.asarray(w0), jnp.asarray(X.perm_cols),
                          "solve.prologue")
    if prior_mean is not None:
        prior_mean = X.from_model_space(prior_mean)
    if prior_precision is not None:
        prior_precision = X.from_model_space(prior_precision)
    norm_obj = norm
    if norm is not None:
        # Host-side gather: these (d,) vectors are host numpy and
        # make_objective re-uploads them anyway — a device from_model_space
        # would pay gather + (d,) downlink + re-uplink per training call.
        perm = np.asarray(X.perm_cols)
        norm_obj = _dc.replace(
            norm,
            factors=(None if norm.factors is None
                     else np.asarray(norm.factors)[perm]),
            shifts=(None if norm.shifts is None
                    else np.asarray(norm.shifts)[perm]))
    return w0, prior_mean, prior_precision, norm_obj


def _active_norm(normalization):
    """The NormalizationContext if it actually does anything, else None."""
    if normalization is not None and not normalization.is_identity:
        return normalization
    return None


def _init_w0(d, w0, norm, allow_lanes=False):
    if w0 is None:
        return jnp.zeros((d,), jnp.float32)
    if np.ndim(w0) == 2:
        # Lane-MAJOR (G, d) per-lane warm starts: the grid paths' survivor
        # re-solve (tuning/lane_tuner.py compacts a capped screen's winning
        # lanes and re-solves them full-depth from where they stopped).
        if not allow_lanes:
            raise ValueError(
                "per-lane (G, d) w0 is a grid-path feature; single solves "
                "take a (d,) start")
        if norm is not None:
            raise ValueError(
                "per-lane w0 with normalization is not supported; pass "
                "normalized-space starts and normalization=None")
        return jnp.asarray(w0)
    if norm is not None:
        return jnp.asarray(norm.to_normalized_space(np.asarray(w0)))
    return jnp.asarray(w0)


def place_sharded_batch(batch: GLMBatch, mesh: Mesh) -> GLMBatch:
    """A sharded-layout batch (`data.dataset.shard_*_batch`) placed on
    ``mesh`` as the sharded solves read it: every per-shard leaf's axis 0
    over the mesh, the column permutation replicated. A caller that
    solves the same batch again and again places it once; the solves'
    own placement then finds every leaf where it belongs and moves
    nothing — as it finds a hot block that `shard_blocked_ell` built on
    the mesh's devices."""
    if batch.X.n_shards != mesh.devices.size:
        raise ValueError(
            f"ShardedHybridRows has {batch.X.n_shards} shards but the mesh "
            f"has {mesh.devices.size} devices; rebuild with "
            "data.dataset.shard_hybrid_batch(batch, mesh.devices.size)")
    return jax.device_put(
        batch, _hybrid_specs(batch.X, tuple(mesh.axis_names),
                             wrap=lambda s: NamedSharding(mesh, s)))


def _sharded_prep(batch: GLMBatch, w0, mesh: Mesh):
    """Shard-count check + device placement + psum axis name for a
    ShardedHybridRows solve (shared by train_glm and train_glm_grid)."""
    batch = place_sharded_batch(batch, mesh)
    w0 = jax.device_put(w0, replicated(mesh))
    axes = tuple(mesh.axis_names)
    return batch, w0, (axes[0] if len(axes) == 1 else axes)


def _mesh_prep(batch: GLMBatch, w0, mesh: Mesh):
    """Pad rows to the mesh, shard the batch, replicate w0 (shared by
    train_glm and train_glm_grid)."""
    if isinstance(batch.X, HybridRows):
        raise ValueError(
            "HybridRows is a single-device representation: its flat COO "
            "tail cannot be row-sharded over a mesh (global row ids, "
            "arbitrary nnz length). Re-lay it with "
            "data.dataset.shard_hybrid_batch(batch, mesh.devices.size) "
            "— the per-shard-tail form train_glm runs under shard_map — "
            "or use SparseRows under a mesh.")
    batch = pad_batch(batch, pad_to_multiple(batch.n, mesh.devices.size))
    batch = jax.device_put(batch, data_sharding(mesh))
    return batch, jax.device_put(w0, replicated(mesh))


def _lane_result(res) -> OptResult:
    """Transpose a lane-minor solver result (w (d, G), histories (T+1, G))
    to the public lane-MAJOR convention shared with the vmap path."""
    with telemetry.device_scope("solve.epilogue"):
        return res._replace(w=res.w.T, loss_history=res.loss_history.T,
                            grad_norm_history=res.grad_norm_history.T)


def _count_solve(res: OptResult) -> None:
    """The resident solves' `solver.*` counters, with the meanings the
    streamed loops give them (optim/streamed.py): iterations in lock step
    (the largest over lanes) and line-search trials. The values stay on
    the device, and no op is dispatched to reduce them — `count_device`
    reads them back and reduces on the host only when the run's report is
    asked for, so a solve stays one asynchronous dispatch."""
    telemetry.count_device("solver.iterations", res.iterations, reduce="max")
    if res.evaluations is not None:
        telemetry.count_device("solver.linesearch_trials", res.evaluations,
                               reduce="max")


def _count_mesh_psum(res: OptResult, d: int) -> None:
    """`mesh.psum_bytes` of one sharded L-BFGS solve: the f32 gradient's
    bytes (static from its shape) × its gradient all-reduces, one at the
    start and one an iteration. The iteration count stays on the device,
    as `_count_solve`'s do, and is multiplied on the host when the report
    is read (``scale``): a product made on the device would be one more
    dispatch a solve, and a program built inside a traced window."""
    gradient = 4.0 * d
    telemetry.count("mesh.psum_bytes", gradient)
    telemetry.count_device("mesh.psum_bytes", res.iterations, reduce="max",
                           scale=gradient)


def _lane_solve(obj, batch, w0, l2s, l1s, config):
    """The one place a lane-minor solve is dispatched: smooth L2 sweeps on
    the margin-cached L-BFGS or TRON lanes (optim/lane_lbfgs.py,
    optim/lane_tron.py), L1/elastic-net sweeps on the OWL-QN lanes
    (optim/lane_owlqn.py — the orthant projection breaks margin linearity,
    so its trials pay one SHARED X pass instead of riding cached margins).
    ``l1s is None`` + the static optimizer are the route switch; jit
    traces each case separately.

    ``w0`` is either a shared (d,) start broadcast to every lane, or a
    lane-MAJOR (G, d) per-lane warm start (the tuner's compacted survivor
    re-solve) transposed into the solvers' lane-minor (d, G) layout."""
    if w0.ndim == 2:
        W0 = w0.T
    else:
        W0 = jnp.broadcast_to(w0[:, None], (w0.shape[0], l2s.shape[0]))
    if l1s is not None:
        return minimize_owlqn_lanes(
            obj, l2s, l1s, batch, W0, max_iters=config.max_iters,
            tolerance=config.tolerance, history=config.history,
            reg_mask=obj.reg_mask, history_dtype=config.lane_history_dtype)
    if config.optimizer is OptimizerType.TRON:
        return minimize_tron_margin_lanes(
            obj, l2s, batch, W0, max_iters=config.max_iters,
            tolerance=config.tolerance, cg_max_iters=config.cg_max_iters)
    return minimize_lbfgs_margin_lanes(
        obj, l2s, batch, W0, max_iters=config.max_iters,
        tolerance=config.tolerance, history=config.history,
        history_dtype=config.lane_history_dtype)


@partial(jax.jit, static_argnames=("config",))
def _train_run_grid_lanes(batch, w0, obj, l2s, l1s, config):
    """The LANE-MINOR grid runner: one lock-step solver whose state
    carries a minor lane axis, so the hot matvec is a true
    (n, d_sel) × (d_sel, G) MXU matmul and the tail gather/scatter costs
    the same index count as a single lane. The vmapped runner below
    (_train_run_grid) is the general fallback (variances, priors); for
    reg sweeps this path is the fast road (the vmapped one measured ~5× a
    single lane PER LANE at d=10M)."""
    return _lane_result(_lane_solve(obj, batch, w0, l2s, l1s, config)), None


@partial(jax.jit, static_argnames=("config", "mesh"))
def _train_run_sharded_grid_lanes(batch, w0, obj, l2s, l1s, config, mesh):
    """Lane-minor grid runner under shard_map for ShardedHybridRows: each
    device runs the lock-step lane solver on its local (dense rows + tail)
    piece; the per-lane (value, grad) psums batch into one collective per
    evaluation across the sweep, as in _train_run_sharded_grid."""
    axes = tuple(mesh.axis_names)
    batch_spec = _hybrid_specs(batch.X, axes)
    obj_spec = jax.tree_util.tree_map(lambda _: P(), obj)

    def body(b, w0, obj, l2s, l1s):
        bl = b._replace(X=b.X.local())
        return _lane_result(_lane_solve(obj, bl, w0, l2s, l1s, config))

    in_specs = (batch_spec, P(), obj_spec, P(),
                *(() if l1s is None else (P(),)))
    args = (batch, w0, obj, l2s) + (() if l1s is None else (l1s,))
    if l1s is None:
        fn = lambda b, w0, obj, l2s: body(b, w0, obj, l2s, None)
    else:
        fn = body
    return shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
    )(*args), None


@partial(jax.jit, static_argnames=("config", "variance"))
def _train_run_grid(batch, w0, obj, l2s, l1s, config, variance):
    """One compiled program for a whole regularization-weight grid: the
    solver is vmapped over the weight lanes, so every lane shares each pass
    over X — the (n, d) matvec becomes one (n, d)×(d, G) matmul (a far
    better MXU shape) and the per-dispatch fixed cost is paid once for the
    sweep instead of once per grid point. The reference's grid mode trains
    each weight as a separate Spark job."""
    import dataclasses as _dc

    def one(l2v, l1v, w0v):
        o = _dc.replace(obj, l2=l2v)
        res = solve(o, batch, w0v, config, l1_weight=l1v)
        var = compute_variances(o, res.w, batch, variance)
        return res, var

    if w0.ndim == 2:  # per-lane (G, d) warm starts ride the lane axis
        if l1s is None:
            return jax.vmap(lambda l2v, w0v: one(l2v, None, w0v))(l2s, w0)
        return jax.vmap(one)(l2s, l1s, w0)
    if l1s is None:
        return jax.vmap(lambda l2v: one(l2v, None, w0))(l2s)
    return jax.vmap(lambda l2v, l1v: one(l2v, l1v, w0))(l2s, l1s)


def lane_weight_arrays(config: OptimizerConfig, reg_weights):
    """(l2s, l1s, static_config) for a grid's per-lane regularization
    weights — THE one place the lane routing lives (shared by
    train_glm_grid and game.grid): an L1/elastic-net sweep runs OWL-QN
    lanes even though the base config's own weight carries no L1 term (the
    reference's forced-OWLQN-on-L1 rule, applied per sweep), and the
    static config is weight-normalized so every sweep shares one compiled
    program."""
    import dataclasses as _dc

    weights = [float(wt) for wt in reg_weights]
    l2s = jnp.asarray([config.reg.l2_weight(wt) for wt in weights],
                      jnp.float32)
    use_owlqn = (config.effective_optimizer() is OptimizerType.OWLQN
                 or any(config.reg.l1_weight(wt) > 0.0 for wt in weights))
    l1s = None
    if use_owlqn:
        l1s = jnp.asarray([config.reg.l1_weight(wt) for wt in weights],
                          jnp.float32)
    static_cfg = _dc.replace(
        config, reg_weight=0.0,
        optimizer=(OptimizerType.OWLQN if use_owlqn
                   else config.effective_optimizer()))
    return l2s, l1s, static_cfg


def train_glm_grid(
    batch: GLMBatch,
    task: TaskType,
    config: OptimizerConfig,
    reg_weights,
    mesh: Optional[Mesh] = None,
    w0: Optional[jax.Array] = None,
    variance: VarianceComputationType = VarianceComputationType.NONE,
    normalization=None,
    device_results: bool = False,
    prior_mean=None,
    prior_precision=None,
    prior=None,
) -> list[tuple[GeneralizedLinearModel, OptResult]]:
    """Train one GLM per regularization weight — as ONE device program.

    The TPU-native form of the reference's grid search over regularization
    weights (GameEstimator.fit over a λ grid, one Spark run per λ): all
    lanes run in lock-step sharing each X pass, so a G-point sweep costs
    barely more than a single solve. Returns [(model, result)] in
    ``reg_weights`` order.

    Unlike the sequential path, lanes cannot warm-start from each other
    (they run concurrently); every lane starts from ``w0``. Convergence is
    tracked per lane. ``w0`` may also be a lane-MAJOR (G, d) block — a
    PER-LANE warm start (one row per reg weight), the handoff the batched
    tuner's successive-halving re-solve uses to resume its compacted
    survivor lanes from where the capped screen left them. Per-lane
    starts are supported on the single-device lane and vmapped runners
    and the sharded lane runner; not with normalization or permuted
    layouts.

    ``device_results=True`` returns the raw lane-stacked ``(OptResult,
    variances)`` pytree still resident on device — no host transfer, no
    per-lane model assembly, normalization NOT unfolded. For large-d
    sweeps (the 10M-feature regime) the (G, d) coefficient block is
    G×40 MB; callers selecting one winning lane (or reducing to metrics)
    should fetch only what they need.

    ``prior`` / ``prior_mean``+``prior_precision``: an informative
    Gaussian prior SHARED by every lane (incremental training — the
    continual flywheel re-tuning its reg weight on a refresh). Priors are
    rejected by the lane-minor lock-step solver
    (`ops.lane_objective.supports_lanes`), so a prior sweep runs on the
    general vmapped runner — one single-lane solver program per lane,
    lock-step but without the shared-X-pass lane-minor layout — and says
    so at INFO.
    """
    if isinstance(batch, ChunkedBatch):
        raise ValueError(
            "streamed mode has no lane-minor grid (every lane would "
            "multiply the per-pass host→device stream); run the sweep "
            "sequentially — each point is a train_glm(ChunkedBatch) solve")
    d = _matrix_dim(batch.X)
    sharded_hybrid = mesh is not None and isinstance(batch.X,
                                                     _SHARDED_TYPES)
    permuted = isinstance(batch.X, _PERMUTED_TYPES)
    if isinstance(batch.X, _SINGLE_DEVICE_PERMUTED) and mesh is not None:
        raise ValueError(
            f"{type(batch.X).__name__} is a single-device representation "
            "(its bucketed tail cannot be row-sharded); use the sharded "
            "form (data.dataset.shard_permuted_batch / "
            "shard_blocked_ell_batch) or ShardedHybridRows under a mesh")
    norm = _active_norm(normalization)
    reg_weights = list(reg_weights)
    if np.ndim(w0) == 2:
        if permuted:
            raise ValueError(
                "per-lane (G, d) w0 is not supported with permuted "
                "layouts (the column-space translation is per-vector); "
                "pass a shared (d,) start or a non-permuted batch")
        if np.shape(w0) != (len(reg_weights), d):
            raise ValueError(
                f"per-lane w0 must be (G={len(reg_weights)}, d={d}), "
                f"got {np.shape(w0)}")
    w0 = _init_w0(d, w0, norm, allow_lanes=True)
    if prior is not None:
        if prior_mean is not None or prior_precision is not None:
            raise ValueError("pass prior OR prior_mean/prior_precision")
        if prior.precision_full is not None:
            raise ValueError(
                "full-covariance priors are not supported on the grid "
                "path; use a diagonal prior (from_variances) or run the "
                "sweep sequentially via train_glm")
        prior_mean = prior.mean
        prior_precision = prior.precision_diag
    if norm is not None and prior_mean is not None:
        prior_mean = norm.to_normalized_space(np.asarray(prior_mean))
        if prior_precision is not None and norm.factors is not None:
            f = np.asarray(norm.factors)
            prior_precision = np.asarray(prior_precision,
                                         np.float32) * f * f
    norm_obj, intercept_index = norm, -1
    if permuted:
        w0, prior_mean, prior_precision, norm_obj = _permuted_prep(
            batch.X, w0, prior_mean, prior_precision, norm)
        intercept_index = batch.X.last_col_pos
    if prior_mean is not None:
        prior_mean = jnp.asarray(prior_mean, jnp.float32)
    if prior_precision is not None:
        prior_precision = jnp.asarray(prior_precision, jnp.float32)
    weights = [float(wt) for wt in reg_weights]
    l2s, l1s, static_cfg = lane_weight_arrays(config, weights)
    axis_name = None
    if sharded_hybrid:
        batch, w0, axis_name = _sharded_prep(batch, w0, mesh)
    obj = make_objective(task, config, d, axis_name=axis_name,
                         normalization=norm_obj,
                         intercept_index=intercept_index,
                         prior_mean=prior_mean,
                         prior_precision=prior_precision)
    telemetry.record_signature("training._train_run_grid",
                               (batch, w0, obj, l2s, l1s))
    # Reg sweeps without variances ride a lane-minor solver (one lock-step
    # program sharing every X pass): smooth sweeps on the margin-cached
    # L-BFGS or TRON lanes, L1/elastic-net sweeps on the OWL-QN lanes.
    # Variance requests fall back to the general vmapped runner; so do
    # informative priors (supports_lanes), SAYING so — a silently slower
    # sweep is the kind of routing surprise the flywheel cannot afford.
    if not supports_lanes(obj):
        from photon_tpu.utils.logging import photon_logger

        photon_logger("photon_tpu.models", propagate=True).info(
            "train_glm_grid: informative prior present — the lane-minor "
            "lock-step grid does not support priors "
            "(ops.lane_objective.supports_lanes); routing the %d-lane "
            "sweep to the general vmapped single-lane-per-lane runner. "
            "Drop the prior (or solve points sequentially with "
            "train_glm(prior=...)) to get the lane-minor path back.",
            len(weights))
    use_lanes = (variance is VarianceComputationType.NONE
                 and supports_lanes(obj)
                 # lane_weight_arrays pins OWLQN <=> l1s is not None;
                 # all three optimizers have a lane-minor solver
                 and (l1s is not None) == (static_cfg.optimizer
                                           is OptimizerType.OWLQN))
    if w0.ndim == 2 and sharded_hybrid and not use_lanes:
        raise ValueError(
            "per-lane w0 on the sharded grid requires the lane-minor "
            "path (no variances/priors); this sweep routes to the "
            "sharded vmapped runner")
    with profiling.dispatch("training._train_run_grid",
                            (batch, w0, obj, l2s, l1s)):
        if sharded_hybrid:
            if use_lanes:
                res, var = _train_run_sharded_grid_lanes(
                    batch, w0, obj, l2s, l1s, static_cfg, mesh)
            else:
                res, var = _train_run_sharded_grid(batch, w0, obj, l2s, l1s,
                                                   static_cfg, variance,
                                                   mesh)
        else:
            if mesh is not None:
                batch, w0 = _mesh_prep(batch, w0, mesh)
            if use_lanes:
                res, var = _train_run_grid_lanes(batch, w0, obj, l2s, l1s,
                                                 static_cfg)
            else:
                res, var = _train_run_grid(batch, w0, obj, l2s, l1s,
                                           static_cfg, variance)
    _count_solve(res)
    if permuted:
        # Back to original column order (one (G, d) device gather for the
        # whole sweep) before normalization unfolds / models assemble;
        # device_results callers get original-order coefficients too.
        inv = jnp.asarray(batch.X.inv_perm)
        res = res._replace(w=_reorder_columns(res.w, inv, "solve.epilogue"))
        if var is not None:
            var = var[:, inv]
    if device_results:
        return res, var
    # ONE host transfer for the whole sweep, then pure-numpy lane assembly:
    # per-lane device slicing would pay a dispatch + readback per lane per
    # field. The returned leaves are numpy; they re-device on first use
    # like any host constant.
    res, var = jax.device_get((res, var))
    out = []
    W = res.w
    V = var
    if norm is not None:
        W = norm.rows_to_original_space(W)
        if V is not None:
            V = norm.variances_to_original_space(V)
    for i in range(len(weights)):
        # the lane-minor solvers' lock-step `evaluations` is one scalar
        # for the sweep: every lane's result carries it whole
        lane = jax.tree_util.tree_map(
            lambda x, i=i: x[i] if x.ndim else x, res)
        model = GeneralizedLinearModel(
            Coefficients(W[i], None if V is None else V[i]), task)
        out.append((model, lane))
    return out


def evaluate_glm_grid(grid, batch: GLMBatch, evaluator=None):
    """Validation model selection over a `train_glm_grid` result
    (reference: GameEstimator's best-model pick via Evaluator.betterThan,
    one Spark evaluation job per grid point). The expensive part — scoring,
    the only pass over X — runs for all lanes in one device program
    (`models.glm.score_models`); the (n,)-sized metric reductions then run
    per lane. Returns ``(best_index, [score per lane])``.
    """
    from photon_tpu.evaluation.evaluator import default_evaluator
    from photon_tpu.models.glm import score_models_on_batch

    task = grid[0][0].task
    evaluator = evaluator if evaluator is not None else default_evaluator(task)
    margins = np.asarray(score_models_on_batch([m for m, _ in grid], batch))
    scores = [float(evaluator.evaluate(margins[i], batch.y, batch.weights))
              for i in range(len(grid))]
    best = 0
    for i in range(1, len(scores)):
        if evaluator.better_than(scores[i], scores[best]):
            best = i
    return best, scores


def _l1_lam(config: OptimizerConfig):
    """The dynamic L1 weight for a solve (None on smooth routes) — the one
    place the OWLQN lam is derived, shared by fixed- and random-effect
    paths."""
    if config.effective_optimizer() is OptimizerType.OWLQN:
        return config.reg.l1_weight(config.reg_weight)
    return None


def _static_config(config: OptimizerConfig) -> OptimizerConfig:
    """The jit-cache key for a solve: the config with its (dynamic) weight
    zeroed and the L1-vs-smooth routing pinned, so every reg weight maps to
    the same compiled program."""
    import dataclasses as _dc

    return _dc.replace(config, reg_weight=0.0,
                       optimizer=config.effective_optimizer())


def train_glm_streamed(
    data: ChunkedBatch,
    task: TaskType,
    config: OptimizerConfig,
    w0: Optional[jax.Array] = None,
    prior_mean=None,
    prior_precision=None,
    normalization=None,
    mesh: Optional[Mesh] = None,
) -> tuple[GeneralizedLinearModel, OptResult]:
    """The out-of-HBM solve: the dataset is a host-resident ChunkedBatch and
    every objective evaluation accumulates over streamed device chunks
    (optim/streamed.py — the treeAggregate regime). Same objective, same
    convergence criteria, same returned shapes as the resident `train_glm`;
    `train_glm` dispatches here automatically when handed a ChunkedBatch.

    With a ``mesh``, every streamed chunk row-shards across ALL mesh
    devices (each device streams 1/D of every feature chunk, the chunk
    partials run under shard_map, and ONE hierarchical psum per evaluation
    combines the (value, gradient) partials — the pod-scale treeAggregate),
    so an out-of-HBM dataset trains against the mesh's POOLED HBM-bandwidth
    and compute. Smooth/L1 solves only either way: TRON's CG inner loop
    would pay one full dataset stream PER CG step, so it is rejected rather
    than silently shipped into the wrong cost regime.
    """
    from photon_tpu.optim.streamed import (minimize_lbfgs_streamed,
                                           minimize_owlqn_streamed)

    if config.effective_optimizer() is OptimizerType.TRON:
        raise ValueError(
            "TRON is not available in streamed mode (each CG step would "
            "stream the full dataset once — cg_max_iters streams per "
            "iteration vs L-BFGS's two); use LBFGS or OWLQN for "
            "out-of-HBM solves")
    d = data.X.n_features
    norm = _active_norm(normalization)
    w0 = _init_w0(d, w0, norm)
    if norm is not None and prior_mean is not None:
        prior_mean = jnp.asarray(norm.to_normalized_space(
            np.asarray(prior_mean)))
    if norm is not None and prior_precision is not None:
        f = np.asarray(norm.factors) if norm.factors is not None else 1.0
        prior_precision = jnp.asarray(
            np.asarray(prior_precision, np.float32) * f * f)
    # Blocked-ELL chunk ladders (data.dataset.chunk_blocked_ell) carry ONE
    # global column permutation for the whole stream: translate the
    # original-space side inputs in, exactly as _permuted_prep does for
    # the resident permuted layouts, and translate the solution back out
    # below. Under a mesh the ladder must be the MESH form
    # (chunk_blocked_ell(n_shards=mesh size) — ShardedBlockedEllRows
    # chunks whose per-device ELL buckets row-shard with the stream);
    # optim.streamed._backend rejects the single-device form with the
    # rebuild recipe.
    permuted = data.X.permuted
    norm_obj, intercept_index = norm, -1
    if permuted:
        perm = np.asarray(data.X.perm_cols)
        w0 = jnp.asarray(w0)[jnp.asarray(perm)]
        if prior_mean is not None:
            prior_mean = jnp.asarray(prior_mean)[jnp.asarray(perm)]
        if prior_precision is not None:
            prior_precision = jnp.asarray(prior_precision)[jnp.asarray(perm)]
        if norm is not None:
            import dataclasses as _dc

            norm_obj = _dc.replace(
                norm,
                factors=(None if norm.factors is None
                         else np.asarray(norm.factors)[perm]),
                shifts=(None if norm.shifts is None
                        else np.asarray(norm.shifts)[perm]))
        intercept_index = data.X.last_col_pos
    obj = make_objective(task, config, d, prior_mean=prior_mean,
                         prior_precision=prior_precision,
                         normalization=norm_obj,
                         intercept_index=intercept_index)
    if config.effective_optimizer() is OptimizerType.OWLQN:
        res = minimize_owlqn_streamed(
            obj, data, w0, config.reg.l1_weight(config.reg_weight),
            max_iters=config.max_iters, tolerance=config.tolerance,
            history=config.history, reg_mask=obj.reg_mask, mesh=mesh)
    else:
        res = minimize_lbfgs_streamed(
            obj, data, w0, max_iters=config.max_iters,
            tolerance=config.tolerance, history=config.history, mesh=mesh)
    if permuted:
        # Back to original column order (one gather) BEFORE the
        # normalization unfold, as at every permuted boundary.
        res = res._replace(w=jnp.asarray(res.w)[jnp.asarray(
            np.asarray(data.X.inv_perm))])
    w_out = res.w
    if norm is not None:
        w_out = jnp.asarray(norm.to_original_space(np.asarray(res.w)))
    model = GeneralizedLinearModel(Coefficients(w_out, None), task)
    return model, res


def train_glm(
    batch: GLMBatch,
    task: TaskType,
    config: OptimizerConfig,
    mesh: Optional[Mesh] = None,
    w0: Optional[jax.Array] = None,
    variance: VarianceComputationType = VarianceComputationType.NONE,
    prior_mean=None,
    prior_precision=None,
    prior=None,
    normalization=None,
) -> tuple[GeneralizedLinearModel, OptResult]:
    """Full-batch distributed GLM training (DistributedOptimizationProblem.run).

    With a mesh, examples are sharded across the ``data`` axis and the whole
    solve compiles to one SPMD program; without one it runs single-device.

    With a NormalizationContext, the solve runs in normalized coefficient
    space (factors/shifts fused into the objective; X untouched) and the
    returned model's coefficients/variances are converted BACK to original
    space, so scoring raw features works directly. ``w0`` and priors, when
    given, are interpreted in original space too.

    ``prior``: an optim.prior.PriorDistribution (incremental training —
    reference: PriorDistribution / initial-model priors); shorthand for the
    prior_mean/prior_precision pair, and the only way to pass a
    full-covariance precision.

    A ChunkedBatch (host-resident chunked dataset) dispatches to the
    streamed out-of-HBM solve — single-chip, or with ``mesh`` row-sharded
    across every mesh device with one psum per evaluation; see
    `train_glm_streamed`.
    """
    if isinstance(batch, ChunkedBatch):
        if variance is not VarianceComputationType.NONE:
            raise ValueError(
                "coefficient variances are not available in streamed mode "
                "(the Hessian-diagonal pass is not chunk-accumulated yet); "
                "use variance_type=none")
        if prior is not None:
            if prior_mean is not None or prior_precision is not None:
                raise ValueError("pass prior OR prior_mean/prior_precision")
            if prior.precision_full is not None:
                raise ValueError(
                    "full-covariance priors are not supported in streamed "
                    "mode; use a diagonal prior")
            prior_mean = jnp.asarray(prior.mean, jnp.float32)
            prior_precision = (
                None if prior.precision_diag is None
                else jnp.asarray(prior.precision_diag, jnp.float32))
        return train_glm_streamed(
            batch, task, config, w0=w0, prior_mean=prior_mean,
            prior_precision=prior_precision, normalization=normalization,
            mesh=mesh)
    d = _matrix_dim(batch.X)
    norm = _active_norm(normalization)
    permuted = isinstance(batch.X, _PERMUTED_TYPES)
    if isinstance(batch.X, _SINGLE_DEVICE_PERMUTED) and mesh is not None:
        raise ValueError(
            f"{type(batch.X).__name__} is a single-device representation "
            "(its bucketed tail cannot be row-sharded); use the sharded "
            "form (data.dataset.shard_permuted_batch / "
            "shard_blocked_ell_batch) or ShardedHybridRows under a mesh")
    prior_full_precision = None
    if prior is not None:
        if prior_mean is not None or prior_precision is not None:
            raise ValueError("pass prior OR prior_mean/prior_precision")
        prior_mean = jnp.asarray(prior.mean, jnp.float32)
        if prior.precision_diag is not None:
            prior_precision = jnp.asarray(prior.precision_diag, jnp.float32)
        prior_full_precision = prior.precision_full
        if prior_full_precision is not None and norm is not None:
            raise ValueError(
                "full-covariance priors are not supported together with "
                "normalization (no exact diagonal-space transform exists); "
                "pre-transform the precision or use a diagonal prior"
            )
    w0 = _init_w0(d, w0, norm)
    if norm is not None and prior_mean is not None:
        prior_mean = jnp.asarray(norm.to_normalized_space(np.asarray(prior_mean)))
    if norm is not None and prior_precision is not None:
        # Diagonal prior in original space ↦ normalized space: the penalty
        # τ_j(w_orig − μ_orig)_j² with w_orig_j = f_j·w_norm_j becomes
        # (τ_j f_j²)(w_norm − μ_norm)_j² (intercept/shift coupling dropped —
        # same diagonal approximation as variances_to_original_space).
        f = np.asarray(norm.factors) if norm.factors is not None else 1.0
        prior_precision = jnp.asarray(
            np.asarray(prior_precision, np.float32) * f * f)
    # Single-device dense OWL-QN solves use the pallas fused value+grad
    # kernel (one X pass per evaluation; ops/fused.py). L-BFGS and TRON go
    # through the margin-cached solvers, which never call value_and_grad —
    # their per-pass matvec/rmatvec are already single X passes. Mesh solves
    # keep the jnp path — XLA's SPMD partitioner cannot shard a pallas
    # custom call; under a mesh the fused kernel is only reachable through
    # the explicit shard_map/axis_name route (Objective(axis_name=...,
    # fused=True)).
    use_fused = (mesh is None
                 and config.effective_optimizer() is OptimizerType.OWLQN)
    norm_obj, intercept_index = norm, -1
    if permuted:
        if prior_full_precision is not None:
            raise ValueError(
                "full-covariance priors are not supported with "
                f"{type(batch.X).__name__} (a (d, d) precision at "
                "permuted-hybrid scale is impractical; use a diagonal "
                "prior)")
        w0, prior_mean, prior_precision, norm_obj = _permuted_prep(
            batch.X, w0, prior_mean, prior_precision, norm)
        intercept_index = batch.X.last_col_pos
        use_fused = False
    sharded_hybrid = mesh is not None and isinstance(batch.X,
                                                     _SHARDED_TYPES)
    axis_name = None
    if sharded_hybrid:
        batch, w0, axis_name = _sharded_prep(batch, w0, mesh)
    obj = make_objective(task, config, d, axis_name=axis_name,
                         prior_mean=prior_mean, prior_precision=prior_precision,
                         normalization=norm_obj,
                         prior_full_precision=prior_full_precision,
                         fused=use_fused, intercept_index=intercept_index)

    if sharded_hybrid:
        telemetry.record_signature("training._train_run_sharded",
                                   (batch, w0, obj, _l1_lam(config)))
        with profiling.dispatch("training._train_run_sharded",
                                (batch, w0, obj, _l1_lam(config))):
            res, var = _train_run_sharded(batch, w0, obj, _l1_lam(config),
                                          _static_config(config), variance,
                                          mesh)
        if config.effective_optimizer() is OptimizerType.LBFGS:
            _count_mesh_psum(res, d)
    elif mesh is not None:
        batch, w0 = _mesh_prep(batch, w0, mesh)
    elif (obj.fused
          and not isinstance(batch.X,
                             (SparseRows, HybridRows, ShardedHybridRows))
          and batch.n >= 128
          and fused_lowering_available(d)):
        # Zero-weight padding up to a 4096 multiple so the fused kernel's
        # power-of-two row chunks always divide n (padding rows contribute
        # nothing to loss or gradient). Skipped when can_fuse would reject
        # the batch anyway (no fused lowering for this backend/width).
        batch = pad_batch(batch, pad_to_multiple(batch.n, 4096))

    if not sharded_hybrid:
        telemetry.record_signature("training._train_run",
                                   (batch, w0, obj, _l1_lam(config)))
        if profiling.needs_note("training._train_run"):
            # static cost of the WHOLE jitted solve, its while loops
            # bounded by the config's iteration budget (trace-only)
            lam, static_cfg = _l1_lam(config), _static_config(config)
            profiling.note_program(
                "training._train_run",
                lambda b, w, o: _train_run(b, w, o, lam, static_cfg,
                                           variance),
                (batch, w0, obj), while_trips=config.max_iters)
        with profiling.dispatch("training._train_run",
                                (batch, w0, obj, _l1_lam(config))):
            res, var = _train_run(batch, w0, obj, _l1_lam(config),
                                  _static_config(config), variance)
    _count_solve(res)
    if permuted:
        # Back to original column order (one device gather) BEFORE the
        # normalization unfold — elementwise transforms commute with the
        # permutation, so the original-space context applies unchanged.
        res = res._replace(w=_reorder_columns(
            res.w, jnp.asarray(batch.X.inv_perm), "solve.epilogue"))
        if var is not None:
            var = batch.X.to_model_space(var)
    w_out = res.w
    if norm is not None:
        w_out = jnp.asarray(norm.to_original_space(np.asarray(res.w)))
        if var is not None:
            var = jnp.asarray(norm.variances_to_original_space(np.asarray(var)))
    model = GeneralizedLinearModel(Coefficients(w_out, var), task)
    return model, res


# ----------------------------------------------------------------- contracts
# Static-analysis contracts for this module's solver programs (see
# photon_tpu/analysis): the full resident L-BFGS program and the lane-minor
# grid are communication-free on one device; the sharded hybrid/permuted
# solves close each evaluation with ONE psum; the permuted layout is
# additionally scatter-free BY CONSTRUCTION (the round-5 measured wall —
# ~12 ns/element TPU scatter-adds — cannot regress silently).
from photon_tpu.analysis.contracts import register_contract  # noqa: E402
from photon_tpu.analysis.walker import (  # noqa: E402
    SCATTER_ADD_PRIMITIVES,
    SCATTER_PRIMITIVES,
)


def _contract_cfg(**kw):
    from photon_tpu.optim.regularization import l2

    kw.setdefault("max_iters", 6)
    kw.setdefault("tolerance", 1e-7)
    kw.setdefault("reg", l2())
    kw.setdefault("history", 4)
    return OptimizerConfig(**kw)


def _contract_dense_batch(n=64, d=8):
    rng = np.random.default_rng(0)
    return (rng.normal(size=(n, d)).astype(np.float32),
            (rng.uniform(size=n) < 0.5).astype(np.float32))


def _contract_sparse_batch(n, d, k=4):
    from photon_tpu.data.dataset import make_batch

    rng = np.random.default_rng(0)
    ind = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    return make_batch(SparseRows(ind, val, d), y)


@register_contract(
    name="resident_lbfgs_solve",
    description="the whole jitted margin-cached L-BFGS solve+variance "
                "program (_train_run): single device, zero communication, "
                "no host exits anywhere in the solver loop",
    collectives={}, tags=("resident",))
def _contract_resident_lbfgs_solve():
    from photon_tpu.data.dataset import make_batch

    X, y = _contract_dense_batch()
    cfg = _contract_cfg(reg_weight=0.5)
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, X.shape[1])
    fn = lambda b, w, o: _train_run(  # noqa: E731
        b, w, o, None, _static_config(cfg), VarianceComputationType.NONE)
    return fn, (make_batch(X, y), jnp.zeros((X.shape[1],), jnp.float32),
                obj)


@register_contract(
    name="resident_grid_lanes",
    description="the lane-minor reg-weight grid (_train_run_grid_lanes): "
                "G lock-step lanes, one program, zero communication",
    collectives={}, tags=("resident", "lane"))
def _contract_resident_grid_lanes():
    from photon_tpu.data.dataset import make_batch

    X, y = _contract_dense_batch()
    cfg = _contract_cfg(reg_weight=0.0)
    l2s, l1s, static_cfg = lane_weight_arrays(cfg, [0.1, 1.0])
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, X.shape[1])
    fn = lambda b, w, o, l2v: _train_run_grid_lanes(  # noqa: E731
        b, w, o, l2v, None, static_cfg)
    return fn, (make_batch(X, y), jnp.zeros((X.shape[1],), jnp.float32),
                obj, l2s)


def _contract_sharded_vg(batch, mesh):
    axes = tuple(mesh.axis_names)
    batch_spec = _hybrid_specs(batch.X, axes)

    def vg(obj, b, w):
        def body(obj, b, w):
            return obj.value_and_grad(w, b._replace(X=b.X.local()))

        obj_spec = jax.tree_util.tree_map(lambda _: P(), obj)
        return shard_map(body, mesh=mesh,
                         in_specs=(obj_spec, batch_spec, P()),
                         out_specs=(P(), P()))(obj, b, w)

    return vg


@register_contract(
    name="sharded_hybrid_value_and_grad",
    description="ShardedHybridRows shard_map evaluation: ONE psum, and the "
                "per-shard tail provably never crosses devices (no gather/"
                "scatter collectives)",
    collectives={"psum": 1}, tags=("resident", "mesh"))
def _contract_sharded_hybrid_value_and_grad():
    from photon_tpu.data.dataset import shard_hybrid_batch
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    n_sh = int(mesh.devices.size)
    d = 64
    batch = shard_hybrid_batch(_contract_sparse_batch(16 * n_sh, d), n_sh,
                               d_dense=16)
    cfg = _contract_cfg(reg_weight=0.5)
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d,
                         axis_name=mesh.axis_names[0])
    return _contract_sharded_vg(batch, mesh), \
        (obj, batch, jnp.zeros((d,), jnp.float32))


@register_contract(
    name="sharded_permuted_value_and_grad",
    description="ShardedPermutedHybridRows shard_map evaluation: ONE psum "
                "and ZERO scatter ops — the scatter-free layout holds on "
                "the mesh path",
    collectives={"psum": 1}, forbid=SCATTER_PRIMITIVES,
    tags=("resident", "mesh"))
def _contract_sharded_permuted_value_and_grad():
    from photon_tpu.data.dataset import shard_permuted_batch
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    n_sh = int(mesh.devices.size)
    d = 96
    batch = shard_permuted_batch(_contract_sparse_batch(16 * n_sh, d),
                                 n_sh, d_dense=16)
    cfg = _contract_cfg(reg_weight=0.5)
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d,
                         axis_name=mesh.axis_names[0],
                         intercept_index=batch.X.last_col_pos)
    return _contract_sharded_vg(batch, mesh), \
        (obj, batch, jnp.zeros((d,), jnp.float32))


@register_contract(
    name="sharded_permuted_grid_lanes",
    description="the FULL sharded lane-grid solver program "
                "(_train_run_sharded_grid_lanes on ShardedPermutedHybrid"
                "Rows): no combining scatters anywhere (history writes "
                "are .at[i].set -> dynamic-update-slice), and exactly 3 "
                "psum eqns — the init value+grad, the line-search trial's "
                "phi (inner while), the accepted step's grad (outer while)",
    collectives={"psum": 3}, forbid=SCATTER_ADD_PRIMITIVES,
    tags=("resident", "mesh", "lane"))
def _contract_sharded_permuted_grid_lanes():
    from photon_tpu.data.dataset import shard_permuted_batch
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    n_sh = int(mesh.devices.size)
    d = 96
    batch = shard_permuted_batch(_contract_sparse_batch(16 * n_sh, d),
                                 n_sh, d_dense=16)
    cfg = _contract_cfg(reg_weight=0.0)
    l2s, l1s, static_cfg = lane_weight_arrays(cfg, [0.1, 1.0])
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d,
                         axis_name=mesh.axis_names[0],
                         intercept_index=batch.X.last_col_pos)
    fn = lambda b, w, o, l2v: _train_run_sharded_grid_lanes(  # noqa: E731
        b, w, o, l2v, None, static_cfg, mesh)
    return fn, (batch, jnp.zeros((d,), jnp.float32), obj, l2s)


@register_contract(
    name="sharded_blocked_ell_value_and_grad",
    description="ShardedBlockedEllRows shard_map evaluation (bf16 "
                "storage): ONE psum, ZERO scatter ops of any kind, every "
                "sparse dot/einsum accumulating f32 — the blocked-ELL law "
                "holds on the mesh path",
    collectives={"psum": 1}, forbid=SCATTER_PRIMITIVES,
    require_f32_accum=True, tags=("resident", "mesh", "sparse"))
def _contract_sharded_blocked_ell_value_and_grad():
    from photon_tpu.data.dataset import (cast_features,
                                         shard_blocked_ell_batch)
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    n_sh = int(mesh.devices.size)
    d = 96
    batch = cast_features(
        shard_blocked_ell_batch(_contract_sparse_batch(16 * n_sh, d),
                                n_sh, d_dense=16))
    cfg = _contract_cfg(reg_weight=0.5)
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d,
                         axis_name=mesh.axis_names[0],
                         intercept_index=batch.X.last_col_pos)
    return _contract_sharded_vg(batch, mesh), \
        (obj, batch, jnp.zeros((d,), jnp.float32))
