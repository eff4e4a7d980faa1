"""Random-effect feature-space projectors (reference: projector.*)."""
import numpy as np
import pytest

from photon_tpu.game.dataset import GameData, RandomEffectDataset
from photon_tpu.game.projector import (
    BlockProjection,
    ProjectionConfig,
    ProjectorType,
    RandomProjector,
    build_index_map_projection,
    gather_rows,
    scatter_rows_into,
)
from photon_tpu.game.random_effect import RandomEffectCoordinate
from photon_tpu.models.variance import VarianceComputationType
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.regularization import l2


def _mixed_effect_data(seed=0, n=400, E=7, d=24, sparse_per_entity=3,
                       intercept=True, vary_support=False):
    """Each entity only ever touches its own small feature subset (plus the
    intercept), the regime INDEX_MAP projection exists for."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, E, size=n)
    # entity e is active on features [e*s, (e+1)*s)
    s = sparse_per_entity
    assert E * s <= d - int(intercept)
    X = np.zeros((n, d), np.float32)
    for i in range(n):
        e = ids[i]
        # vary_support: entity e uses only (e % s) + 1 of its features, so one
        # bucket mixes entities with different active-set sizes
        se = (e % s) + 1 if vary_support else s
        X[i, e * s:e * s + se] = rng.normal(size=se)
    if intercept:
        X[:, -1] = 1.0
    u = rng.normal(size=(E, d)).astype(np.float32) * 0.8
    margin = np.einsum("nd,nd->n", X, u[ids])
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    return X, y, ids


def _train_re(X, y, ids, projection=None, variance=VarianceComputationType.NONE):
    data = GameData.build(y, shards={"s": X}, entity_ids={"e": ids})
    ds = RandomEffectDataset.build(data, "e", "s", projection=projection)
    coord = RandomEffectCoordinate(
        ds, TaskType.LOGISTIC_REGRESSION,
        OptimizerConfig(max_iters=60, reg=l2(), reg_weight=0.5),
        variance=variance,
    )
    model, stats = coord.train(np.zeros_like(y))
    return ds, coord, model, stats


class TestBlockProjection:
    def test_gather_scatter_roundtrip(self):
        rng = np.random.default_rng(3)
        E, d = 5, 12
        sets = [np.sort(rng.choice(d - 1, size=rng.integers(1, 5), replace=False))
                for _ in range(E)]
        bp = build_index_map_projection(sets, intercept_index=d - 1)
        full = rng.normal(size=(E, d)).astype(np.float32)
        rows = gather_rows(full, bp)
        # round-trip: scatter the gathered rows into zeros == full restricted
        # to each entity's active set + intercept
        out = np.zeros((E, d), np.float32)
        scatter_rows_into(out, rows, np.arange(E), bp)
        for e in range(E):
            keep = np.zeros(d, bool)
            keep[sets[e]] = True
            keep[d - 1] = True
            np.testing.assert_allclose(out[e][keep], full[e][keep], rtol=1e-6)
            assert (out[e][~keep] == 0).all()

    def test_intercept_pinned_last(self):
        bp = build_index_map_projection(
            [np.array([1, 3]), np.array([0])], intercept_index=9)
        assert (bp.proj_idx[:, -1] == 9).all()
        assert (bp.proj_mask[:, -1] == 1.0).all()

    def test_dim_padded_pow2(self):
        bp = build_index_map_projection(
            [np.arange(5), np.arange(2)], intercept_index=None)
        assert bp.dim == 8

    def test_sparse_block_varying_active_sizes(self):
        """Regression: entities whose active count + 1 < padded width p must
        still route intercept values to the intercept column, not feature 0."""
        from photon_tpu.game.dataset import _project_sparse

        # entity 0: features {2, 5} + intercept 9 (p=4 -> nact+1 < p)
        ind = np.array([[[2, 5, 9, 0]], [[1, 3, 9, 0]]])  # (E=2, m=1, k=4)
        val = np.array([[[1.5, -2.0, 1.0, 0.0]], [[4.0, 5.0, 1.0, 0.0]]],
                       np.float32)
        out, bp = _project_sparse(ind, val, 9, width=4)
        assert bp.dim == 4
        np.testing.assert_array_equal(bp.proj_idx[1], [1, 3, 0, 9])
        np.testing.assert_array_equal(bp.proj_mask[1], [1, 1, 0, 1])
        np.testing.assert_allclose(out[0, 0], [1.5, -2.0, 0.0, 1.0])
        np.testing.assert_allclose(out[1, 0], [4.0, 5.0, 0.0, 1.0])


class TestIndexMapProjection:
    def test_projected_solve_matches_full_solve(self):
        """INDEX_MAP projection is exact: same coefficients as the
        unprojected per-entity solves."""
        X, y, ids = _mixed_effect_data()
        _, _, m_full, _ = _train_re(X, y, ids, projection=None)
        ds, _, m_proj, stats = _train_re(
            X, y, ids,
            projection=ProjectionConfig(ProjectorType.INDEX_MAP))
        # every bucket solved in a reduced space strictly smaller than d
        assert all(b.dim is not None and b.dim < X.shape[1] for b in ds.blocks)
        np.testing.assert_allclose(
            np.asarray(m_proj.coefficients), np.asarray(m_full.coefficients),
            atol=2e-3,
        )
        assert stats.n_converged == stats.n_entities

    def test_projected_variances_match(self):
        X, y, ids = _mixed_effect_data(seed=1)
        _, _, m_full, _ = _train_re(
            X, y, ids, variance=VarianceComputationType.SIMPLE)
        _, _, m_proj, _ = _train_re(
            X, y, ids,
            projection=ProjectionConfig(ProjectorType.INDEX_MAP),
            variance=VarianceComputationType.SIMPLE,
        )
        vf = np.asarray(m_full.variances)
        vp = np.asarray(m_proj.variances)
        # On each entity's active features the variances agree; off-support
        # projected variances are 0 while the full solve reports the bare
        # 1/(l2) prior curvature there — compare only where both are active.
        active = vp > 0
        assert active.any()
        np.testing.assert_allclose(vp[active], vf[active], rtol=0.05, atol=1e-2)

    def test_sparse_input_matches_dense(self):
        import scipy.sparse as sp

        from photon_tpu.data.matrix import from_scipy_csr

        X, y, ids = _mixed_effect_data(seed=2, vary_support=True)
        _, _, m_dense, _ = _train_re(
            X, y, ids, projection=ProjectionConfig(ProjectorType.INDEX_MAP))
        Xs = from_scipy_csr(sp.csr_matrix(X))
        data = GameData.build(y, shards={"s": Xs}, entity_ids={"e": ids})
        ds = RandomEffectDataset.build(
            data, "e", "s",
            projection=ProjectionConfig(ProjectorType.INDEX_MAP))
        coord = RandomEffectCoordinate(
            ds, TaskType.LOGISTIC_REGRESSION,
            OptimizerConfig(max_iters=60, reg=l2(), reg_weight=0.5),
        )
        m_sparse, _ = coord.train(np.zeros_like(y))
        np.testing.assert_allclose(
            np.asarray(m_sparse.coefficients), np.asarray(m_dense.coefficients),
            atol=1e-4,
        )


class TestRandomProjection:
    def test_back_projected_scoring_is_exact(self):
        """x·back_project(w) == project_rows(x)·w — the identity scoring
        correctness rests on."""
        rng = np.random.default_rng(5)
        d, p = 40, 12
        proj = RandomProjector.build(d, p, keep_intercept=True, seed=0)
        X = rng.normal(size=(50, d)).astype(np.float32)
        X[:, -1] = 1.0
        w = rng.normal(size=p).astype(np.float32)
        lhs = X @ proj.back_project(w)
        rhs = proj.project_rows(X) @ w
        np.testing.assert_allclose(lhs, rhs, rtol=1e-4, atol=1e-4)

    def test_sparse_rows_projection_matches_dense(self):
        import scipy.sparse as sp

        from photon_tpu.data.matrix import from_scipy_csr

        rng = np.random.default_rng(6)
        d, p = 60, 16
        dense = np.zeros((30, d), np.float32)
        for i in range(30):
            cols = rng.choice(d - 1, size=4, replace=False)
            dense[i, cols] = rng.normal(size=4)
        dense[:, -1] = 1.0
        proj = RandomProjector.build(d, p, keep_intercept=True, seed=1)
        Xs = from_scipy_csr(sp.csr_matrix(dense))
        out_sparse = proj.project_sparse_rows(
            np.asarray(Xs.indices), np.asarray(Xs.values))
        np.testing.assert_allclose(
            out_sparse, proj.project_rows(dense), rtol=1e-4, atol=1e-4)

    def test_random_projected_training_learns(self):
        """Training per-entity models in a random-projected space still beats
        chance, and the model lives in full space for scoring."""
        X, y, ids = _mixed_effect_data(seed=7, n=800, E=4, d=32,
                                       sparse_per_entity=6)
        ds, coord, model, _ = _train_re(
            X, y, ids,
            projection=ProjectionConfig(ProjectorType.RANDOM, projected_dim=16))
        assert np.asarray(model.coefficients).shape == (4, X.shape[1])
        scores = np.asarray(coord.score(model))
        from sklearn.metrics import roc_auc_score

        assert roc_auc_score(y, scores) > 0.6

    def test_coeff_roundtrip_is_unbiased(self):
        """Regression: project_coeffs∘back_project must be ≈ identity, not a
        (d/p)-fold blow-up — warm starts cross this round trip every sweep."""
        rng = np.random.default_rng(11)
        d, p = 512, 64
        proj = RandomProjector.build(d, p, keep_intercept=True, seed=2)
        w = rng.normal(size=p).astype(np.float32)
        w2 = proj.project_coeffs(proj.back_project(w))
        ratio = np.linalg.norm(w2) / np.linalg.norm(w)
        assert 0.5 < ratio < 2.0

    def test_variance_with_random_projection_raises(self):
        X, y, ids = _mixed_effect_data(seed=8)
        with pytest.raises(ValueError, match="RANDOM"):
            _train_re(
                X, y, ids,
                projection=ProjectionConfig(ProjectorType.RANDOM, projected_dim=8),
                variance=VarianceComputationType.SIMPLE,
            )

    def test_projected_dim_required(self):
        with pytest.raises(ValueError, match="projected_dim"):
            ProjectionConfig(ProjectorType.RANDOM)


class TestEstimatorIntegration:
    def test_game_fit_with_projection(self):
        from photon_tpu.game.estimator import (
            FixedEffectConfig,
            GameEstimator,
            RandomEffectConfig,
        )
        from photon_tpu.game.scoring import score_game

        X, y, ids = _mixed_effect_data(seed=9, n=600, E=6, d=20)
        rng = np.random.default_rng(10)
        Xf = rng.normal(size=(len(y), 5)).astype(np.float32)
        data = GameData.build(
            y, shards={"fixed": Xf, "per": X}, entity_ids={"e": ids})
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={
                "fixed": FixedEffectConfig(
                    "fixed", OptimizerConfig(max_iters=20, reg=l2(), reg_weight=0.1)),
                "per_e": RandomEffectConfig(
                    "e", "per",
                    OptimizerConfig(max_iters=30, reg=l2(), reg_weight=0.5),
                    projection=ProjectionConfig(ProjectorType.INDEX_MAP)),
            },
            n_sweeps=2,
        )
        results = est.fit(data)
        scores = np.asarray(score_game(results[0].model, data))
        from sklearn.metrics import roc_auc_score

        assert roc_auc_score(y, scores) > 0.75
