"""Lane-minor grid solver parity (optim/lane_lbfgs.py, ops/lane_objective.py).

Mirrors the reference's grid-search contract (GameEstimator over a λ grid:
each grid point must train AS IF it were its own job): every lane of the
lock-step lane-minor solver must match an independent single-lane
`train_glm` solve on the same data to f32 reduction noise, across matrix
representations, tasks, weights/offsets, normalization, and skewed grids.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import GLMBatch, make_batch
from photon_tpu.data.matrix import (SparseRows, matvec, matvec_lanes,
                                    rmatvec, rmatvec_lanes, to_hybrid)
from photon_tpu.models.training import train_glm, train_glm_grid
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig, OptimizerType
from photon_tpu.optim.regularization import elastic_net, l2


def _sparse_problem(rng, n=600, d=120, k=8, task=TaskType.LOGISTIC_REGRESSION):
    ind = rng.integers(0, d - 1, size=(n, k)).astype(np.int32)
    ind[:, -1] = d - 1  # intercept column
    val = rng.normal(size=(n, k)).astype(np.float32)
    val[:, -1] = 1.0
    wt = rng.normal(size=d).astype(np.float32) * 0.5
    z = np.einsum("nk,nk->n", val, wt[ind])
    if task is TaskType.LINEAR_REGRESSION:
        y = (z + 0.1 * rng.normal(size=n)).astype(np.float32)
    elif task is TaskType.POISSON_REGRESSION:
        y = rng.poisson(np.exp(np.clip(z * 0.3, None, 3.0))).astype(np.float32)
    else:
        y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    return SparseRows(jnp.asarray(ind), jnp.asarray(val), d), jnp.asarray(y)


def _grid_vs_sequential(batch, task, cfg, weights, atol=2e-2):
    """Each lane must train AS IF it were its own job. Near a tolerance-
    converged optimum the two f32 solver paths (lock-step lanes vs solo)
    take different line-search trial sequences, so coefficients agree to
    the optimum's conditioning (loose atol) while the achieved OBJECTIVE
    values — the quantity convergence actually pins — must match tightly."""
    grid = train_glm_grid(batch, task, cfg, weights)
    assert len(grid) == len(weights)
    for wt, (model, res) in zip(weights, grid):
        m_seq, r_seq = train_glm(
            batch, task, dataclasses.replace(cfg, reg_weight=wt))
        np.testing.assert_allclose(
            float(res.value), float(r_seq.value), rtol=1e-5,
            err_msg=f"objective mismatch at weight {wt}")
        np.testing.assert_allclose(
            np.asarray(model.coefficients.means),
            np.asarray(m_seq.coefficients.means), atol=atol,
            err_msg=f"lane mismatch at weight {wt}")
        assert bool(res.converged) == bool(r_seq.converged)


@pytest.mark.parametrize("task", [TaskType.LOGISTIC_REGRESSION,
                                  TaskType.LINEAR_REGRESSION,
                                  TaskType.POISSON_REGRESSION])
def test_lane_grid_matches_sequential_sparse(rng, task):
    X, y = _sparse_problem(rng, task=task)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    _grid_vs_sequential(batch, task, cfg, [1e-2, 1e-1, 1.0, 10.0])


def test_lane_grid_matches_sequential_hybrid(rng):
    X, y = _sparse_problem(rng, n=600, d=500, k=10)
    H = to_hybrid(X, 64)
    batch = make_batch(H, y)
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    _grid_vs_sequential(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                        [1e-2, 1.0, 30.0])


def test_lane_grid_matches_sequential_dense_weights_offsets(rng):
    n, d = 300, 20
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    wt = rng.normal(size=d).astype(np.float32)
    y = jnp.asarray((rng.random(n) < 1 / (1 + np.exp(-X @ wt))).astype(
        np.float32))
    weights = jnp.asarray(rng.uniform(0.5, 2.0, size=n).astype(np.float32))
    offsets = jnp.asarray(rng.normal(size=n).astype(np.float32) * 0.3)
    batch = GLMBatch(X=X, y=y, weights=weights, offsets=offsets)
    cfg = OptimizerConfig(max_iters=100, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    _grid_vs_sequential(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                        [1e-2, 1.0, 100.0])


def test_lane_grid_normalization(rng):
    from photon_tpu.data.normalization import NormalizationContext, NormalizationType

    n, d = 300, 12
    X = np.asarray(rng.normal(size=(n, d)) * rng.uniform(0.1, 8.0, size=d),
                   dtype=np.float32)
    X[:, -1] = 1.0
    wt = rng.normal(size=d).astype(np.float32)
    y = (rng.random(n) < 1 / (1 + np.exp(-X @ wt))).astype(np.float32)
    norm = NormalizationContext.build(jnp.asarray(X),
                                      NormalizationType.STANDARDIZATION,
                                      intercept_index=d - 1)
    batch = make_batch(jnp.asarray(X), jnp.asarray(y))
    cfg = OptimizerConfig(max_iters=100, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    weights = [1e-2, 1.0]
    grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg, weights,
                          normalization=norm)
    for wt_, (model, _) in zip(weights, grid):
        m_seq, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION,
                             dataclasses.replace(cfg, reg_weight=wt_),
                             normalization=norm)
        np.testing.assert_allclose(np.asarray(model.coefficients.means),
                                   np.asarray(m_seq.coefficients.means),
                                   atol=3e-3)


def test_lane_grid_skewed_weights_converge_independently(rng):
    """Wildly skewed grids: the heavy-reg lane converges in a handful of
    iterations, the light lane needs many; per-lane freezing must keep
    both correct and report per-lane iteration counts."""
    X, y = _sparse_problem(rng)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(max_iters=120, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    weights = [1e-4, 1e4]
    grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg, weights)
    iters = [int(r.iterations) for _, r in grid]
    assert iters[1] < iters[0], iters  # heavy reg stops far earlier
    _grid_vs_sequential(batch, TaskType.LOGISTIC_REGRESSION, cfg, weights)


def test_lane_grid_owlqn_matches_sequential(rng):
    """Elastic-net sweeps ride the lane-minor OWL-QN solver
    (optim/lane_owlqn.py): each lane must match its own sequential OWL-QN
    solve — coefficients, achieved objective, AND the L1 sparsity the
    orthant projection is there to produce."""
    X, y = _sparse_problem(rng)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(max_iters=120, tolerance=1e-6,
                          reg=elastic_net(0.5), reg_weight=0.0, history=5)
    weights = [1e-2, 1e-1, 3.0]
    grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg, weights)
    for wt, (model, res) in zip(weights, grid):
        m_seq, r_seq = train_glm(
            batch, TaskType.LOGISTIC_REGRESSION,
            dataclasses.replace(cfg, reg_weight=wt,
                                optimizer=OptimizerType.OWLQN))
        np.testing.assert_allclose(float(res.value), float(r_seq.value),
                                   rtol=1e-5,
                                   err_msg=f"objective mismatch at {wt}")
        np.testing.assert_allclose(np.asarray(model.coefficients.means),
                                   np.asarray(m_seq.coefficients.means),
                                   atol=2e-3)
    # The heavy-L1 lane must be genuinely sparse — exact zeros, not small
    # (the sequential OWL-QN zeroes the same ~40% at this weight).
    w_heavy = np.asarray(grid[-1][0].coefficients.means)
    assert (w_heavy == 0.0).sum() > w_heavy.size // 3


@pytest.mark.parametrize("task", [TaskType.LOGISTIC_REGRESSION,
                                  TaskType.LINEAR_REGRESSION])
def test_lane_grid_tron_matches_sequential(rng, task):
    """TRON sweeps ride the lane-minor margin-cached TRON
    (optim/lane_tron.py): each lane must match its own sequential TRON
    solve — same trust-region constants, same Steihaug subproblem, same
    stop rules, per lane."""
    X, y = _sparse_problem(rng, task=task)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(optimizer=OptimizerType.TRON, max_iters=80,
                          tolerance=1e-6, reg=l2(), reg_weight=0.0,
                          cg_max_iters=20)
    _grid_vs_sequential(batch, task, cfg, [1e-2, 1.0, 10.0])


def test_lane_grid_tron_sharded_hybrid(rng, mesh8):
    from photon_tpu.data.dataset import shard_hybrid_batch

    X, y = _sparse_problem(rng, n=640, d=400, k=10)
    H = to_hybrid(X, 64)
    batch = shard_hybrid_batch(make_batch(H, y), mesh8.devices.size)
    cfg = OptimizerConfig(optimizer=OptimizerType.TRON, max_iters=80,
                          tolerance=1e-6, reg=l2(), reg_weight=0.0)
    weights = [1e-1, 1.0, 30.0]
    grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg, weights,
                          mesh=mesh8)
    single = make_batch(to_hybrid(X, 64), y)
    for wt, (model, res) in zip(weights, grid):
        m_seq, r_seq = train_glm(single, TaskType.LOGISTIC_REGRESSION,
                                 dataclasses.replace(cfg, reg_weight=wt))
        np.testing.assert_allclose(float(res.value), float(r_seq.value),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(model.coefficients.means),
                                   np.asarray(m_seq.coefficients.means),
                                   atol=2e-2)


def test_lane_grid_owlqn_variance_fallback_vmap_path(rng):
    """L1 grids that request variances cannot ride the lane road (the
    lane runners skip variance computation) — they must fall back to the
    vmapped runner and still match sequential solves, variances included."""
    from photon_tpu.models.variance import VarianceComputationType

    X, y = _sparse_problem(rng)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(max_iters=120, tolerance=1e-6,
                          reg=elastic_net(0.5), reg_weight=0.0, history=5)
    weights = [1e-2, 1e-1]
    grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg, weights,
                          variance=VarianceComputationType.SIMPLE)
    for wt, (model, res) in zip(weights, grid):
        m_seq, _ = train_glm(
            batch, TaskType.LOGISTIC_REGRESSION,
            dataclasses.replace(cfg, reg_weight=wt,
                                optimizer=OptimizerType.OWLQN),
            variance=VarianceComputationType.SIMPLE)
        np.testing.assert_allclose(np.asarray(model.coefficients.means),
                                   np.asarray(m_seq.coefficients.means),
                                   atol=2e-3)
        assert model.coefficients.variances is not None
        np.testing.assert_allclose(np.asarray(model.coefficients.variances),
                                   np.asarray(m_seq.coefficients.variances),
                                   rtol=2e-2, atol=1e-4)


def test_lane_grid_owlqn_sharded_hybrid(rng, mesh8):
    from photon_tpu.data.dataset import shard_hybrid_batch

    X, y = _sparse_problem(rng, n=640, d=400, k=10)
    H = to_hybrid(X, 64)
    batch = shard_hybrid_batch(make_batch(H, y), mesh8.devices.size)
    cfg = OptimizerConfig(max_iters=120, tolerance=1e-6,
                          reg=elastic_net(0.5), reg_weight=0.0, history=5)
    weights = [1e-1, 1.0]
    grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg, weights,
                          mesh=mesh8)
    single = make_batch(to_hybrid(X, 64), y)
    for wt, (model, res) in zip(weights, grid):
        m_seq, r_seq = train_glm(
            single, TaskType.LOGISTIC_REGRESSION,
            dataclasses.replace(cfg, reg_weight=wt,
                                optimizer=OptimizerType.OWLQN))
        np.testing.assert_allclose(float(res.value), float(r_seq.value),
                                   rtol=1e-4)
        # 6e-2, not 2e-2: the stop at 1e-6 pins F to 1.4e-4 and leaves
        # coefficients of size 1-3 free by a few hundredths in this flat
        # valley (L2 part 0.05). The PARENT's two-loop with its dots
        # summed in any other order reads 0.022-0.037 here, the
        # carried-products form 0.030 (PERF.md §6, PR 28)
        np.testing.assert_allclose(np.asarray(model.coefficients.means),
                                   np.asarray(m_seq.coefficients.means),
                                   atol=6e-2)


def test_lane_grid_sharded_hybrid(rng, mesh8):
    from photon_tpu.data.dataset import shard_hybrid_batch

    X, y = _sparse_problem(rng, n=640, d=400, k=10)
    H = to_hybrid(X, 64)
    batch = shard_hybrid_batch(make_batch(H, y), mesh8.devices.size)
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    # d≈n: the near-unregularized lane's optimum has flat directions
    # where f32 paths wander ~0.04; keep the lightest weight conditioned.
    weights = [1e-1, 1.0, 30.0]
    grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg, weights,
                          mesh=mesh8)
    single = make_batch(to_hybrid(X, 64), y)
    for wt, (model, res) in zip(weights, grid):
        m_seq, r_seq = train_glm(single, TaskType.LOGISTIC_REGRESSION,
                                 dataclasses.replace(cfg, reg_weight=wt))
        # Two divergence sources vs the single-device sequential run: lane
        # lock-step AND the shard psum's reduction order — same contract as
        # _grid_vs_sequential (tight objective, conditioning-loose coeffs).
        np.testing.assert_allclose(float(res.value), float(r_seq.value),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(model.coefficients.means),
                                   np.asarray(m_seq.coefficients.means),
                                   atol=2e-2)


def test_lane_grid_bf16_history_quality(rng):
    """lane_history_dtype="bfloat16" stores the (m, d, G) S/Y pairs
    half-width while every steering inner product (rho, gamma, curvature
    acceptance) stays f32 from the unrounded pair. The rounded two-loop
    direction is still vetted by the Wolfe search, so achieved objectives
    must match the f32-history run tightly and coefficients to the
    optimum's conditioning."""
    X, y = _sparse_problem(rng)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    weights = [1e-2, 1.0, 10.0]
    grid32 = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                            weights)
    grid16 = train_glm_grid(
        batch, TaskType.LOGISTIC_REGRESSION,
        dataclasses.replace(cfg, lane_history_dtype="bfloat16"), weights)
    for (m32, r32), (m16, r16) in zip(grid32, grid16):
        np.testing.assert_allclose(float(r16.value), float(r32.value),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(m16.coefficients.means),
                                   np.asarray(m32.coefficients.means),
                                   atol=2e-2)
        assert bool(r16.converged)


def test_matvec_lanes_match_single(rng):
    n, d, k, G = 64, 120, 6, 5
    ind = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    X = SparseRows(jnp.asarray(ind), jnp.asarray(val), d)
    H = to_hybrid(X, 16)
    D = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(d, G)).astype(np.float32))
    R = jnp.asarray(rng.normal(size=(n, G)).astype(np.float32))
    for M in (X, H, D):
        mv = np.asarray(matvec_lanes(M, W))
        rv = np.asarray(rmatvec_lanes(M, R))
        for g in range(G):
            np.testing.assert_allclose(
                mv[:, g], np.asarray(matvec(M, W[:, g])), rtol=2e-5,
                atol=1e-5)
            np.testing.assert_allclose(
                rv[:, g], np.asarray(rmatvec(M, R[:, g])), rtol=2e-5,
                atol=1e-5)


def test_lane_grid_device_results_layout(rng):
    X, y = _sparse_problem(rng, n=200, d=100, k=6)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(max_iters=40, tolerance=1e-7, reg=l2(),
                          reg_weight=0.0, history=5)
    res, var = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                              [1e-2, 1.0, 30.0], device_results=True)
    assert res.w.shape == (3, 100)
    assert res.value.shape == (3,)
    assert var is None


@pytest.mark.parametrize("optimizer,l1", [(OptimizerType.LBFGS, False),
                                          (OptimizerType.LBFGS, True),
                                          (OptimizerType.TRON, False)],
                         ids=["lbfgs", "owlqn", "tron"])
def test_lane_grid_tolerance_zero_is_a_fixed_depth(rng, optimizer, l1):
    """The lane solvers follow `optim.config.stop_state` as the scalar ones
    do: at ``tolerance`` 0 every lane takes ``max_iters`` iterations, ends
    converged and not failed, at the objective the early-stopping solve
    reaches."""
    X, y = _sparse_problem(rng, n=200, d=12, k=4)
    batch = make_batch(X, y)
    weights = [2.0, 8.0]
    reg_ctx = elastic_net(0.5) if l1 else l2()
    cfg = OptimizerConfig(optimizer=optimizer, max_iters=40, tolerance=0.0,
                          reg=reg_ctx)
    fixed = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg, weights)
    early = train_glm_grid(
        batch, TaskType.LOGISTIC_REGRESSION,
        dataclasses.replace(cfg, tolerance=1e-7), weights)
    for (_, res), (_, res_early) in zip(fixed, early):
        assert int(res.iterations) == 40 > int(res_early.iterations)
        assert bool(res.converged) and not bool(res.failed)
        np.testing.assert_allclose(float(res.value), float(res_early.value),
                                   rtol=1e-5)
