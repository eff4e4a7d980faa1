"""End-to-end GLM training: mesh == single device, parity with sklearn /
closed forms, variances.

Mirrors the reference's DistributedOptimizationProblemTest and the
supervised-model integration tests.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import make_batch
from photon_tpu.models.training import train_glm
from photon_tpu.models.variance import VarianceComputationType
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim import regularization as reg
from photon_tpu.optim.config import OptimizerConfig, OptimizerType


def _logistic_data(rng, n=2000, d=12):
    X = rng.normal(size=(n, d)).astype(np.float32)
    wt = (rng.normal(size=d) * 0.5).astype(np.float32)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-X @ wt))).astype(np.float32)
    return X, y


def test_mesh_matches_single_device(rng, mesh8):
    X, y = _logistic_data(rng)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(max_iters=150, reg=reg.l2(), reg_weight=1.0)
    m_mesh, r_mesh = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg, mesh=mesh8)
    m_one, r_one = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
    # sharded reductions reorder f32 sums; the line search then stops at a
    # slightly different iterate — ~1e-4 coefficient drift is expected
    np.testing.assert_allclose(m_mesh.weights, m_one.weights, atol=1e-4)
    np.testing.assert_allclose(r_mesh.value, r_one.value, rtol=1e-5)


def test_mesh_with_padding(rng, mesh8):
    """n not divisible by 8: zero-weight padding must not change the result."""
    X, y = _logistic_data(rng, n=1001)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(max_iters=150, reg=reg.l2(), reg_weight=1.0)
    m_mesh, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg, mesh=mesh8)
    m_one, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
    # f32 reduction order differs once padding reshapes the shards, so the
    # iterates drift by ~1 ulp per step; equality holds to optimizer tolerance.
    np.testing.assert_allclose(m_mesh.weights, m_one.weights, atol=5e-4)


def test_linear_regression_closed_form(rng):
    n, d = 500, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) + 0.1 * rng.normal(size=n)).astype(np.float32)
    lam = 2.0
    cfg = OptimizerConfig(max_iters=300, reg=reg.l2(), reg_weight=lam, tolerance=1e-9)
    model, _ = train_glm(make_batch(X, y), TaskType.LINEAR_REGRESSION, cfg)
    exact = np.linalg.solve(X.T @ X + lam * np.eye(d), X.T @ y)
    np.testing.assert_allclose(model.weights, exact, atol=2e-3)


def test_poisson_regression_recovers_truth(rng):
    n, d = 4000, 5
    X = (rng.normal(size=(n, d)) * 0.3).astype(np.float32)
    wt = np.array([0.5, -0.3, 0.2, 0.0, 0.4], np.float32)
    y = rng.poisson(np.exp(X @ wt)).astype(np.float32)
    cfg = OptimizerConfig(max_iters=200, reg=reg.l2(), reg_weight=1e-3)
    model, res = train_glm(make_batch(X, y), TaskType.POISSON_REGRESSION, cfg)
    assert bool(res.converged)
    np.testing.assert_allclose(model.weights, wt, atol=0.1)


def test_tron_optimizer_path(rng, mesh8):
    X, y = _logistic_data(rng, n=800)
    cfg_t = OptimizerConfig(optimizer=OptimizerType.TRON, max_iters=80,
                            reg=reg.l2(), reg_weight=1.0)
    cfg_l = OptimizerConfig(max_iters=200, reg=reg.l2(), reg_weight=1.0)
    mt, _ = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION, cfg_t, mesh=mesh8)
    ml, _ = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION, cfg_l)
    np.testing.assert_allclose(mt.weights, ml.weights, atol=3e-3)


def test_l1_auto_selects_owlqn(rng):
    X, y = _logistic_data(rng, n=400, d=20)
    cfg = OptimizerConfig(max_iters=200, reg=reg.l1(), reg_weight=8.0)
    assert cfg.effective_optimizer() is OptimizerType.OWLQN
    model, res = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION, cfg)
    assert int((np.asarray(model.weights) != 0).sum()) < 20


def test_elastic_net(rng):
    X, y = _logistic_data(rng, n=400, d=15)
    cfg = OptimizerConfig(max_iters=200, reg=reg.elastic_net(alpha=0.5),
                          reg_weight=4.0)
    model, res = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION, cfg)
    assert bool(res.converged)
    # elastic net at alpha=0.5 still induces some sparsity
    assert int((np.asarray(model.weights) == 0).sum()) > 0


def test_simple_variances_match_inverse_hessian_diag(rng):
    """For linear regression with lam=0, SIMPLE variance = 1/diag(X^T X)."""
    n, d = 300, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ np.ones(d)).astype(np.float32)
    cfg = OptimizerConfig(max_iters=100)
    model, _ = train_glm(make_batch(X, y), TaskType.LINEAR_REGRESSION, cfg,
                         variance=VarianceComputationType.SIMPLE)
    expected = 1.0 / np.diag(X.T @ X)
    np.testing.assert_allclose(model.coefficients.variances, expected, rtol=1e-3)


def test_full_variances(rng):
    n, d = 300, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ np.ones(d)).astype(np.float32)
    cfg = OptimizerConfig(max_iters=100)
    model, _ = train_glm(make_batch(X, y), TaskType.LINEAR_REGRESSION, cfg,
                         variance=VarianceComputationType.FULL)
    expected = np.diag(np.linalg.inv(X.T @ X))
    np.testing.assert_allclose(model.coefficients.variances, expected, rtol=2e-3)


def test_weights_and_offsets(rng):
    """Duplicating a row == weighting it 2x; offsets shift the margin."""
    X, y = _logistic_data(rng, n=200, d=6)
    cfg = OptimizerConfig(max_iters=200, reg=reg.l2(), reg_weight=0.5)

    Xdup = np.concatenate([X, X[:50]])
    ydup = np.concatenate([y, y[:50]])
    w = np.ones(200, np.float32)
    w[:50] = 2.0
    m_dup, _ = train_glm(make_batch(Xdup, ydup), TaskType.LOGISTIC_REGRESSION, cfg)
    m_wt, _ = train_glm(make_batch(X, y, weights=w), TaskType.LOGISTIC_REGRESSION, cfg)
    np.testing.assert_allclose(m_dup.weights, m_wt.weights, atol=2e-3)


def test_prior_incremental_training(rng):
    """Strong prior pins coefficients at the prior mean; weak prior doesn't."""
    X, y = _logistic_data(rng, n=300, d=5)
    batch = make_batch(X, y)
    cfg = OptimizerConfig(max_iters=200)
    mu = jnp.asarray(np.full(5, 0.37, np.float32))
    strong, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                          prior_mean=mu, prior_precision=jnp.full((5,), 1e6))
    np.testing.assert_allclose(strong.weights, mu, atol=1e-2)


class TestTrainGlmGrid:
    """train_glm_grid: one compiled program per reg-weight sweep."""

    def _problem(self, rng, n=512, d=12):
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d).astype(np.float32) / np.sqrt(d)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w)))).astype(
            np.float32)
        return make_batch(X, y)

    def test_matches_sequential_l2(self, rng):
        from photon_tpu.models.training import train_glm_grid

        batch = self._problem(rng)
        cfg = OptimizerConfig(max_iters=60, reg=reg.l2(), reg_weight=0.0,
                              regularize_intercept=True)
        weights = [0.1, 1.0, 10.0]
        grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                              weights)
        assert len(grid) == 3
        for wt, (m_g, r_g) in zip(weights, grid):
            import dataclasses

            m_s, r_s = train_glm(
                batch, TaskType.LOGISTIC_REGRESSION,
                dataclasses.replace(cfg, reg_weight=wt))
            assert bool(r_g.converged)
            np.testing.assert_allclose(
                np.asarray(m_g.coefficients.means),
                np.asarray(m_s.coefficients.means), atol=2e-4)

    def test_matches_sequential_owlqn(self, rng):
        """Grid lanes must equal the same-route single solve bit-for-bit-ish
        (train_glm's single-device OWLQN takes the pallas fused route, whose
        f32 rounding diverges the iterate path — so compare against the jnp
        objective the grid itself uses)."""
        from photon_tpu.models.training import (
            make_objective, solve, train_glm_grid)
        from photon_tpu.optim.config import OptimizerType

        batch = self._problem(rng)
        cfg = OptimizerConfig(optimizer=OptimizerType.OWLQN, max_iters=60,
                              reg=reg.l1(), reg_weight=0.0,
                              regularize_intercept=True)
        weights = [0.5, 5.0]
        grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                              weights)
        d = batch.X.shape[1]
        w0 = np.zeros(d, np.float32)
        for wt, (m_g, r_g) in zip(weights, grid):
            import dataclasses

            c = dataclasses.replace(cfg, reg_weight=wt)
            obj = make_objective(TaskType.LOGISTIC_REGRESSION, c, d)
            r_s = solve(obj, batch, w0, c)
            # 4e-5, not 1e-5: the lane and scalar histories sum their
            # inner products in different orders; the PARENT's two-loop
            # with its dots summed in another order reads 1.5e-5 here,
            # the carried-products form 1.9e-5 (PERF.md §6, PR 28)
            np.testing.assert_allclose(np.asarray(m_g.coefficients.means),
                                       np.asarray(r_s.w), atol=4e-5)
        # stronger L1 → sparser lane
        nnz = [int((np.abs(np.asarray(m.coefficients.means)) > 1e-6).sum())
               for m, _ in grid]
        assert nnz[1] <= nnz[0]

    def test_l1_grid_routes_owlqn_without_config_weight(self, rng):
        """An L1 grid whose config carries reg_weight=0.0 (the natural grid
        idiom) must still run OWL-QN lanes with the grid's weights —
        regression: effective_optimizer() saw l1_weight(0.0)==0 and silently
        dropped ALL regularization, every lane returning the same
        unpenalized solution."""
        from photon_tpu.models.training import train_glm_grid

        batch = self._problem(rng)
        cfg = OptimizerConfig(max_iters=60, reg=reg.l1(), reg_weight=0.0,
                              regularize_intercept=True)
        grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                              [0.5, 20.0])
        w_weak = np.asarray(grid[0][0].coefficients.means)
        w_strong = np.asarray(grid[1][0].coefficients.means)
        assert not np.allclose(w_weak, w_strong)  # weights actually applied
        nnz_weak = int((np.abs(w_weak) > 1e-6).sum())
        nnz_strong = int((np.abs(w_strong) > 1e-6).sum())
        assert nnz_strong < nnz_weak  # strong L1 produces genuine sparsity

    def test_grid_on_mesh(self, rng, mesh8):
        from photon_tpu.models.training import train_glm_grid

        batch = self._problem(rng, n=1024)
        cfg = OptimizerConfig(max_iters=40, reg=reg.l2(), reg_weight=0.0,
                              regularize_intercept=True)
        grid_m = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                                [0.5, 5.0], mesh=mesh8)
        grid_s = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                                [0.5, 5.0])
        for (m_m, _), (m_s, _) in zip(grid_m, grid_s):
            np.testing.assert_allclose(
                np.asarray(m_m.coefficients.means),
                np.asarray(m_s.coefficients.means), atol=2e-3)

    def test_grid_with_variances_and_normalization(self, rng):
        from photon_tpu.data.normalization import (
            NormalizationContext, NormalizationType)
        from photon_tpu.models.training import train_glm_grid
        from photon_tpu.models.variance import VarianceComputationType

        rng2 = np.random.default_rng(3)
        n, d = 400, 6
        X = np.concatenate([rng2.normal(2.0, 5.0, size=(n, d - 1)),
                            np.ones((n, 1))], 1).astype(np.float32)
        y = (rng2.uniform(size=n) < 0.4).astype(np.float32)
        batch = make_batch(X, y)
        norm = NormalizationContext.build(X, NormalizationType.STANDARDIZATION)
        cfg = OptimizerConfig(max_iters=50, reg=reg.l2(), reg_weight=0.0,
                              regularize_intercept=True)
        grid = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                              [1.0, 3.0], normalization=norm,
                              variance=VarianceComputationType.SIMPLE)
        for wt, (m_g, _) in zip([1.0, 3.0], grid):
            import dataclasses

            m_s, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION,
                               dataclasses.replace(cfg, reg_weight=wt),
                               normalization=norm,
                               variance=VarianceComputationType.SIMPLE)
            np.testing.assert_allclose(
                np.asarray(m_g.coefficients.means),
                np.asarray(m_s.coefficients.means), atol=2e-3)
            np.testing.assert_allclose(
                np.asarray(m_g.coefficients.variances),
                np.asarray(m_s.coefficients.variances), rtol=2e-2)

    def test_score_models_and_grid_selection(self, rng):
        from photon_tpu.models.glm import score_models
        from photon_tpu.models.training import (
            evaluate_glm_grid, train_glm_grid)

        batch = self._problem(rng, n=800)
        Xv = np.asarray(batch.X)[600:]
        val = make_batch(Xv, np.asarray(batch.y)[600:])
        tr = make_batch(np.asarray(batch.X)[:600], np.asarray(batch.y)[:600])
        cfg = OptimizerConfig(max_iters=50, reg=reg.l2(), reg_weight=0.0,
                              regularize_intercept=True)
        weights = [0.1, 1.0, 1000.0]
        grid = train_glm_grid(tr, TaskType.LOGISTIC_REGRESSION, cfg, weights)
        # batched margins == per-model margins
        M = np.asarray(score_models([m for m, _ in grid], val.X))
        for i, (m, _) in enumerate(grid):
            np.testing.assert_allclose(M[i], np.asarray(m.score(val.X)),
                                       rtol=1e-5, atol=1e-5)
        best, scores = evaluate_glm_grid(grid, val)
        assert len(scores) == 3
        # default logistic evaluator is AUC; the absurdly over-regularized
        # lane must not win
        assert best != 2
