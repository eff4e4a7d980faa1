"""GAME end-to-end tests (SURVEY.md §4 integration strategy): synthetic
mixed-effect data must recover planted coefficients, GAME must beat a
fixed-effect-only model, and everything must run on the 8-device mesh."""
import dataclasses
import numpy as np
import pytest
import jax.numpy as jnp
from sklearn.metrics import roc_auc_score

from photon_tpu.data.matrix import SparseRows, from_scipy_csr
from photon_tpu.game import (
    FixedEffectConfig,
    FixedEffectCoordinate,
    FixedEffectDataset,
    GameData,
    GameEstimator,
    RandomEffectConfig,
    RandomEffectCoordinate,
    RandomEffectDataset,
    coordinate_descent,
    predict_mean,
    score_game,
)
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim import regularization as reg
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.models.training import train_glm
from photon_tpu.data.dataset import make_batch


def _mixed_effect_logistic(rng, n_entities=30, d_fixed=8, d_re=3, rows_lo=5,
                           rows_hi=60, noise=1.0):
    """Rows: y ~ Bernoulli(sigmoid(x_f·w_fixed + x_r·w_entity))."""
    w_fixed = rng.normal(size=d_fixed)
    w_re = rng.normal(size=(n_entities, d_re)) * 1.5
    rows = rng.integers(rows_lo, rows_hi, size=n_entities)
    ent = np.repeat(np.arange(n_entities), rows)
    n = ent.shape[0]
    perm = rng.permutation(n)
    ent = ent[perm]
    Xf = rng.normal(size=(n, d_fixed)).astype(np.float32)
    Xr = rng.normal(size=(n, d_re)).astype(np.float32)
    logit = Xf @ w_fixed + np.einsum("nd,nd->n", Xr, w_re[ent]) + noise * 0
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    data = GameData.build(
        y,
        shards={"fixed": Xf, "per_entity": Xr},
        entity_ids={"entity": ent.astype(np.int64)},
    )
    return data, w_fixed, w_re, ent


@pytest.mark.tier2
def test_movielens_style_two_random_effects(rng):
    """BASELINE config 3 shape: fixed effect + per-USER + per-ITEM random
    effects (MovieLens-style), coordinate descent alternating over three
    coordinates with residual offsets. Each additional coordinate must add
    held-out AUC, and the full model must recover the planted structure."""
    n_users, n_items, d_f = 60, 40, 6
    n = 6000
    users = rng.integers(0, n_users, size=n)
    items = rng.integers(0, n_items, size=n)
    w_f = rng.normal(size=d_f)
    u_eff = rng.normal(size=n_users) * 1.3   # per-user intercepts
    i_eff = rng.normal(size=n_items) * 1.3   # per-item intercepts
    Xf = rng.normal(size=(n, d_f)).astype(np.float32)
    ones = np.ones((n, 1), np.float32)       # RE shard: intercept feature
    logit = Xf @ w_f + u_eff[users] + i_eff[items]
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)

    tr = np.arange(n) < n - 1500
    te = ~tr

    def build(idx):
        return GameData.build(
            y[idx], shards={"fixed": Xf[idx], "bias": ones[idx]},
            entity_ids={"user": users[idx], "item": items[idx]})

    data, test = build(tr), build(te)
    cfg = OptimizerConfig(max_iters=40, reg=reg.l2(), reg_weight=1.0)
    configs_full = {
        "fixed": FixedEffectConfig("fixed", cfg),
        "per_user": RandomEffectConfig("user", "bias", cfg),
        "per_item": RandomEffectConfig("item", "bias", cfg),
    }
    aucs = {}
    for name, keys in [("fixed", ("fixed",)),
                       ("user", ("fixed", "per_user")),
                       ("full", ("fixed", "per_user", "per_item"))]:
        est = GameEstimator(TaskType.LOGISTIC_REGRESSION,
                            {k: configs_full[k] for k in keys}, n_sweeps=2)
        model = est.fit(data)[0].model
        aucs[name] = roc_auc_score(y[te], np.asarray(score_game(model, test)))
    assert aucs["user"] > aucs["fixed"] + 0.01
    assert aucs["full"] > aucs["user"] + 0.01
    assert aucs["full"] > 0.8
    # Planted per-user effects recovered (up to shared-intercept shift);
    # align by the model's own entity keys — robust to users unseen in
    # training (dense_ids would return the out-of-range sentinel there).
    u_hat = np.asarray(model["per_user"].coefficients)[:, 0]
    keys = np.asarray(model["per_user"].entity_keys).astype(int)
    corr = np.corrcoef(u_hat, u_eff[keys])[0, 1]
    assert corr > 0.8


def test_re_dataset_bucketing(rng):
    n_entities = 17
    rows = rng.integers(1, 40, size=n_entities)
    ent = np.repeat(np.arange(n_entities), rows)
    rng.shuffle(ent)
    n = ent.shape[0]
    X = rng.normal(size=(n, 2)).astype(np.float32)
    data = GameData.build(np.zeros(n), {"s": X}, {"e": ent})
    ds = RandomEffectDataset.build(data, "e", "s")
    assert ds.n_entities == n_entities
    assert ds.n_active == n and ds.n_passive == 0
    # every real row appears exactly once across blocks, padding is weight-0
    seen = np.zeros(n, np.int32)
    total_entities = 0
    for b in ds.blocks:
        assert b.m & (b.m - 1) == 0  # power of two
        total_entities += b.n_entities
        w = np.asarray(b.weights)
        ri = np.asarray(b.row_index)
        for i in range(b.n_entities):
            real = w[i] > 0
            np.testing.assert_array_equal(
                np.sort(ent[ri[i][real]]), np.full(real.sum(), ent[ri[i][real]][0])
            )
            seen[ri[i][real]] += 1
    assert total_entities == n_entities
    np.testing.assert_array_equal(seen, 1)


def test_random_effect_recovers_per_entity_coefficients(rng):
    n_entities, d = 12, 3
    w_true = rng.normal(size=(n_entities, d)).astype(np.float32)
    rows = rng.integers(30, 80, size=n_entities)
    ent = np.repeat(np.arange(n_entities), rows)
    n = ent.shape[0]
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.einsum("nd,nd->n", X, w_true[ent]) + 0.01 * rng.normal(size=n)
    data = GameData.build(y, {"s": X}, {"e": ent})
    ds = RandomEffectDataset.build(data, "e", "s")
    coord = RandomEffectCoordinate(
        ds, TaskType.LINEAR_REGRESSION,
        OptimizerConfig(max_iters=50, reg=reg.l2(), reg_weight=1e-4),
    )
    model, stats = coord.train(np.zeros(n, np.float32))
    assert stats.n_converged == n_entities
    got = np.asarray(model.coefficients)[
        np.asarray([model.key_to_index[k] for k in range(n_entities)])
    ]
    np.testing.assert_allclose(got, w_true, atol=0.05)


def test_game_beats_fixed_only_and_recovers_coefficients(rng):
    data, w_fixed, w_re, ent = _mixed_effect_logistic(rng)
    n = data.n
    tr = np.arange(n) % 5 != 0
    te = ~tr

    def subset(mask):
        return GameData.build(
            data.y[mask],
            {k: np.asarray(v)[mask] for k, v in data.shards.items()},
            {k: v[mask] for k, v in data.entity_ids.items()},
        )

    train, test = subset(tr), subset(te)
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectConfig(
                "fixed", OptimizerConfig(max_iters=60, reg=reg.l2(), reg_weight=0.1)
            ),
            "per_entity": RandomEffectConfig(
                "entity", "per_entity",
                OptimizerConfig(max_iters=40, reg=reg.l2(), reg_weight=1.0),
            ),
        },
        n_sweeps=2,
    )
    results = est.fit(train, validation=test)
    model = results[0].model
    # objective decreases monotonically-ish across coordinate updates
    hist = results[0].descent.objective_history
    assert hist[-1] < hist[0]

    # fixed coefficients recovered up to noise
    got_fixed = np.asarray(model["fixed"].model.weights)
    corr = np.corrcoef(got_fixed, w_fixed)[0, 1]
    assert corr > 0.95

    # GAME beats fixed-effect-only on held-out AUC
    game_scores = np.asarray(score_game(model, test))
    game_auc = roc_auc_score(test.y, game_scores)
    fe_only, _ = train_glm(
        make_batch(train.shards["fixed"], train.y),
        TaskType.LOGISTIC_REGRESSION,
        OptimizerConfig(max_iters=60, reg=reg.l2(), reg_weight=0.1),
    )
    fe_auc = roc_auc_score(
        test.y, np.asarray(fe_only.predict_mean(jnp.asarray(test.shards["fixed"])))
    )
    assert game_auc > fe_auc + 0.02
    assert results[0].validation_score == pytest.approx(game_auc, abs=1e-5)


def test_game_mesh_matches_single_device(rng, mesh8):
    data, *_ = _mixed_effect_logistic(rng, n_entities=10, rows_lo=8, rows_hi=24)
    configs = {
        "fixed": FixedEffectConfig(
            "fixed", OptimizerConfig(max_iters=30, reg=reg.l2(), reg_weight=0.5)
        ),
        "per_entity": RandomEffectConfig(
            "entity", "per_entity",
            OptimizerConfig(max_iters=20, reg=reg.l2(), reg_weight=1.0),
        ),
    }
    single = GameEstimator(TaskType.LOGISTIC_REGRESSION, configs, n_sweeps=1)
    meshy = GameEstimator(TaskType.LOGISTIC_REGRESSION, configs, n_sweeps=1, mesh=mesh8)
    m1 = single.fit(data)[0].model
    m2 = meshy.fit(data)[0].model
    # Single-device fixed-effect solves run the fused pallas objective while
    # mesh solves use the jnp path: different f32 reduction orders, drift
    # amplified across coordinate-descent iterations. ~1e-3 is the expected
    # noise floor, not a semantic difference.
    np.testing.assert_allclose(
        np.asarray(m1["fixed"].model.weights),
        np.asarray(m2["fixed"].model.weights),
        atol=2e-3,
    )
    np.testing.assert_allclose(
        np.asarray(m1["per_entity"].coefficients),
        np.asarray(m2["per_entity"].coefficients),
        atol=2e-3,
    )


def test_locked_coordinate_not_retrained(rng):
    data, *_ = _mixed_effect_logistic(rng, n_entities=8, rows_lo=8, rows_hi=20)
    fe_ds = FixedEffectDataset.build(data, "fixed")
    cfg = OptimizerConfig(max_iters=30, reg=reg.l2(), reg_weight=0.5)
    fe_coord = FixedEffectCoordinate(fe_ds, TaskType.LOGISTIC_REGRESSION, cfg)
    pretrained, _ = fe_coord.train(np.zeros(data.n, np.float32))

    re_ds = RandomEffectDataset.build(data, "entity", "per_entity")
    re_coord = RandomEffectCoordinate(
        re_ds, TaskType.LOGISTIC_REGRESSION,
        OptimizerConfig(max_iters=20, reg=reg.l2(), reg_weight=1.0),
    )
    result = coordinate_descent(
        {"fixed": fe_coord, "per_entity": re_coord},
        data.y, data.weights, data.offsets,
        TaskType.LOGISTIC_REGRESSION,
        n_sweeps=2,
        locked=frozenset({"fixed"}),
        initial_models={"fixed": pretrained},
    )
    np.testing.assert_array_equal(
        np.asarray(result.model["fixed"].model.weights),
        np.asarray(pretrained.model.weights),
    )
    # the random effect actually trained
    assert np.abs(np.asarray(result.model["per_entity"].coefficients)).max() > 0


def test_config_grid_warm_start_and_selection(rng):
    data, *_ = _mixed_effect_logistic(rng, n_entities=10, rows_lo=10, rows_hi=30)
    base = {
        "fixed": FixedEffectConfig(
            "fixed", OptimizerConfig(max_iters=30, reg=reg.l2(), reg_weight=1.0)
        ),
    }
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, base, n_sweeps=1)
    grid = [
        {"fixed": FixedEffectConfig(
            "fixed", OptimizerConfig(max_iters=30, reg=reg.l2(), reg_weight=w))}
        for w in (10.0, 0.1)
    ]
    results = est.fit(data, validation=data, config_grid=grid)
    assert len(results) == 2
    assert all(r.validation_score is not None for r in results)
    best = est.best_model(results)
    assert best is results[int(np.argmax([r.validation_score for r in results]))]


def test_scoring_unseen_entity_contributes_zero(rng):
    data, *_ = _mixed_effect_logistic(rng, n_entities=6, rows_lo=10, rows_hi=20)
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "fixed": FixedEffectConfig(
                "fixed", OptimizerConfig(max_iters=20, reg=reg.l2(), reg_weight=1.0)
            ),
            "per_entity": RandomEffectConfig(
                "entity", "per_entity",
                OptimizerConfig(max_iters=15, reg=reg.l2(), reg_weight=1.0),
            ),
        },
        n_sweeps=1,
    )
    model = est.fit(data)[0].model
    new = GameData.build(
        data.y[:3],
        {k: np.asarray(v)[:3] for k, v in data.shards.items()},
        {"entity": np.array([999, 998, 997], np.int64)},  # all unseen
    )
    scores = np.asarray(score_game(model, new))
    fe_scores = np.asarray(model["fixed"].score(new.shards["fixed"]))
    np.testing.assert_allclose(scores, fe_scores, atol=1e-6)
    mean = np.asarray(predict_mean(model, new))
    assert ((mean > 0) & (mean < 1)).all()
    # Device-resident shards score identically (drivers use to_device()).
    np.testing.assert_allclose(np.asarray(score_game(model, new.to_device())),
                               scores, atol=1e-6)


def test_sparse_re_matches_dense(rng):
    import scipy.sparse as sp

    n_entities, d = 6, 5
    rows = rng.integers(10, 25, size=n_entities)
    ent = np.repeat(np.arange(n_entities), rows)
    n = ent.shape[0]
    Xd = rng.normal(size=(n, d)).astype(np.float32)
    Xd[rng.random(size=(n, d)) < 0.5] = 0.0
    y = rng.normal(size=n).astype(np.float32)
    cfg = OptimizerConfig(max_iters=30, reg=reg.l2(), reg_weight=0.1)

    def fit(X):
        data = GameData.build(y, {"s": X}, {"e": ent})
        ds = RandomEffectDataset.build(data, "e", "s")
        coord = RandomEffectCoordinate(ds, TaskType.LINEAR_REGRESSION, cfg)
        model, _ = coord.train(np.zeros(n, np.float32))
        return np.asarray(model.coefficients), np.asarray(coord.score(model))

    cd, sd = fit(Xd)
    cs, ss = fit(from_scipy_csr(sp.csr_matrix(Xd)))
    # f32 reduction-order drift between segment_sum and dense matmul paths
    # compounds over solver iterations; ~1e-4 is expected, not a bug.
    np.testing.assert_allclose(cd, cs, atol=5e-4)
    np.testing.assert_allclose(sd, ss, atol=5e-4)


def test_active_cap_passive_rows_scored(rng):
    n_entities = 5
    rows = np.full(n_entities, 40)
    ent = np.repeat(np.arange(n_entities), rows)
    n = ent.shape[0]
    X = rng.normal(size=(n, 2)).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    data = GameData.build(y, {"s": X}, {"e": ent})
    ds = RandomEffectDataset.build(data, "e", "s", active_cap=16)
    assert ds.n_active == n_entities * 16
    assert ds.n_passive == n - n_entities * 16
    coord = RandomEffectCoordinate(
        ds, TaskType.LINEAR_REGRESSION,
        OptimizerConfig(max_iters=20, reg=reg.l2(), reg_weight=0.1),
    )
    model, _ = coord.train(np.zeros(n, np.float32))
    scores = np.asarray(coord.score(model))
    assert scores.shape == (n,)
    expected = np.einsum(
        "nd,nd->n", X, np.asarray(model.coefficients)[ds.entity_dense]
    )
    np.testing.assert_allclose(scores, expected, atol=1e-5)


def test_sharded_evaluator_in_fit(rng):
    from photon_tpu.evaluation import Evaluator, EvaluatorType

    data, *_ = _mixed_effect_logistic(rng, n_entities=8, rows_lo=10, rows_hi=25)
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "fixed": FixedEffectConfig(
                "fixed", OptimizerConfig(max_iters=20, reg=reg.l2(), reg_weight=1.0)
            ),
            "per_entity": RandomEffectConfig(
                "entity", "per_entity",
                OptimizerConfig(max_iters=15, reg=reg.l2(), reg_weight=1.0),
            ),
        },
        n_sweeps=1,
        evaluator=Evaluator(EvaluatorType.SHARDED_AUC),
    )
    results = est.fit(data, validation=data)
    assert results[0].validation_score is not None
    assert 0.5 < results[0].validation_score <= 1.0


def test_config_grid_dataset_override_takes_effect(rng):
    data, *_ = _mixed_effect_logistic(rng, n_entities=5, rows_lo=30, rows_hi=40)
    base = {
        "per_entity": RandomEffectConfig(
            "entity", "per_entity",
            OptimizerConfig(max_iters=10, reg=reg.l2(), reg_weight=1.0),
        ),
    }
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, base, n_sweeps=1,
                        warm_start=False)
    grid = [
        {"per_entity": RandomEffectConfig(
            "entity", "per_entity",
            OptimizerConfig(max_iters=10, reg=reg.l2(), reg_weight=1.0),
            active_cap=8)},
        {"per_entity": base["per_entity"]},
    ]
    r_capped, r_full = est.fit(data, config_grid=grid)
    # the capped fit trained on fewer rows, so coefficients must differ
    assert not np.allclose(
        np.asarray(r_capped.model["per_entity"].coefficients),
        np.asarray(r_full.model["per_entity"].coefficients),
    )


def test_initial_models_honored_without_warm_start(rng):
    data, *_ = _mixed_effect_logistic(rng, n_entities=6, rows_lo=10, rows_hi=20)
    cfg = {
        "fixed": FixedEffectConfig(
            "fixed", OptimizerConfig(max_iters=25, reg=reg.l2(), reg_weight=0.5)
        ),
    }
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, cfg, n_sweeps=1)
    pretrained = est.fit(data)[0].model.coordinates
    est2 = GameEstimator(TaskType.LOGISTIC_REGRESSION, cfg, n_sweeps=1,
                         warm_start=False)
    r = est2.fit(data, initial_models=dict(pretrained))[0]
    # warm-started solve converges almost immediately from the optimum
    assert r.descent.coordinate_stats["fixed"][0].iterations <= 3


def test_unseen_longer_entity_id_maps_to_zero_row():
    """Unseen ids longer than every training key must NOT truncate into a
    real entity's row (fixed-width unicode cast bug)."""
    from photon_tpu.game.model import RandomEffectModel

    keys = np.asarray(["abc", "xyz"])  # dtype <U3
    m = RandomEffectModel(
        entity_name="e", feature_shard="s", task=TaskType.LOGISTIC_REGRESSION,
        coefficients=jnp.ones((2, 2)), entity_keys=keys,
        key_to_index={"abc": 0, "xyz": 1},
    )
    ids = m.dense_ids(np.asarray(["abcde", "abc", "zzz", "xyz"]))
    np.testing.assert_array_equal(ids, [2, 0, 2, 1])
    # integer raw ids against string keys still resolve by string value
    m2 = RandomEffectModel(
        entity_name="e", feature_shard="s", task=TaskType.LOGISTIC_REGRESSION,
        coefficients=jnp.ones((2, 2)), entity_keys=np.asarray(["1", "2"]),
        key_to_index={"1": 0, "2": 1},
    )
    np.testing.assert_array_equal(m2.dense_ids(np.asarray([2, 7, 1])), [1, 2, 0])


def test_estimator_normalization_detects_intercept():
    """Estimator-level normalization must not treat a real feature column as
    the intercept on shards built without one."""
    from photon_tpu.data.normalization import NormalizationType
    from photon_tpu.game.estimator import _last_column_is_intercept

    rng = np.random.default_rng(0)
    X_no = rng.normal(size=(50, 3)).astype(np.float32)  # no intercept
    X_yes = X_no.copy(); X_yes[:, -1] = 1.0
    assert not _last_column_is_intercept(X_no)
    assert _last_column_is_intercept(jnp.asarray(X_yes))

    y = (rng.uniform(size=50) < 0.5).astype(np.float32)
    data = GameData.build(y, shards={"s": X_no}, entity_ids={})
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"fixed": FixedEffectConfig("s", OptimizerConfig(max_iters=5))},
        n_sweeps=1,
        normalization={"fixed": NormalizationType.STANDARDIZATION},
    )
    with pytest.raises(ValueError, match="intercept"):
        est.fit(data)
    # scale-only mode works without an intercept, and normalizes EVERY column
    est2 = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"fixed": FixedEffectConfig("s", OptimizerConfig(max_iters=20))},
        n_sweeps=1,
        normalization={"fixed": NormalizationType.SCALE_WITH_STANDARD_DEVIATION},
    )
    r = est2.fit(data)[0]
    assert np.isfinite(np.asarray(r.model["fixed"].model.weights)).all()


class TestVectorizedFixedGrid:
    """Fixed-effect-only reg-weight grids run as one compiled program."""

    def _data(self, rng, n=600, d=10):
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d).astype(np.float32) * 0.7
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w)))).astype(
            np.float32)
        return GameData.build(y, shards={"fixed": X}, entity_ids={})

    def test_matches_sequential_path(self, rng):
        data = self._data(rng)
        val = self._data(rng, n=300)
        cfg = OptimizerConfig(max_iters=60, reg=reg.l2(), reg_weight=1.0,
                              regularize_intercept=True)
        grid = [{"fixed": FixedEffectConfig(
            "fixed", dataclasses.replace(cfg, reg_weight=wt))}
            for wt in (0.1, 1.0, 10.0)]

        def run(vectorized, warm):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs={"fixed": FixedEffectConfig("fixed", cfg)},
                n_sweeps=1, vectorized_grid=vectorized, warm_start=warm)
            return est.fit(data, validation=val, config_grid=grid)

        fast = run(True, False)
        slow = run(False, False)
        assert len(fast) == len(slow) == 3
        for rf, rs in zip(fast, slow):
            wf = np.asarray(
                rf.model.coordinates["fixed"].model.coefficients.means)
            ws = np.asarray(
                rs.model.coordinates["fixed"].model.coefficients.means)
            np.testing.assert_allclose(wf, ws, atol=2e-4)
            assert abs(rf.validation_score - rs.validation_score) < 1e-3
            np.testing.assert_allclose(rf.descent.objective_history[-1],
                                       rs.descent.objective_history[-1],
                                       rtol=1e-4)
            assert rf.configs["fixed"].optimizer.reg_weight == \
                rs.configs["fixed"].optimizer.reg_weight

    def test_matches_sequential_path_elastic_net(self, rng):
        """Fixed-only L1 grids through the estimator ride the OWL-QN lane
        road inside train_glm_grid and must still match the sequential
        estimator path point for point (incl. exact-zero sparsity)."""
        data = self._data(rng)
        cfg = OptimizerConfig(max_iters=80, reg=reg.elastic_net(0.5),
                              reg_weight=1.0, regularize_intercept=True)
        grid = [{"fixed": FixedEffectConfig(
            "fixed", dataclasses.replace(cfg, reg_weight=wt))}
            for wt in (0.05, 0.5, 5.0)]

        def run(vectorized):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs={"fixed": FixedEffectConfig("fixed", cfg)},
                n_sweeps=1, vectorized_grid=vectorized, warm_start=False)
            return est.fit(data, config_grid=grid)

        fast = run(True)
        slow = run(False)
        for rf, rs in zip(fast, slow):
            wf = np.asarray(
                rf.model.coordinates["fixed"].model.coefficients.means)
            ws = np.asarray(
                rs.model.coordinates["fixed"].model.coefficients.means)
            np.testing.assert_allclose(wf, ws, atol=2e-3)
            np.testing.assert_array_equal(wf == 0.0, ws == 0.0)

    def test_fast_path_not_taken_with_random_effects(self, rng):
        """Mixed-effect grids must keep the sequential path (probe None)."""
        data = self._data(rng)
        ids = np.arange(data.n) % 5
        data = GameData.build(np.asarray(data.y),
                              shards={"fixed": np.asarray(data.shards["fixed"]),
                                      "re": np.asarray(data.shards["fixed"])},
                              entity_ids={"e": ids})
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={
                "fixed": FixedEffectConfig("fixed"),
                "per_e": RandomEffectConfig("e", "re"),
            }, n_sweeps=1)
        assert est._fixed_only_reg_grid([est.coordinate_configs]) is None

    def test_best_model_selection_through_fast_path(self, rng):
        data = self._data(rng)
        val = self._data(rng, n=300)
        cfg = OptimizerConfig(max_iters=60, reg=reg.l2(), reg_weight=1.0,
                              regularize_intercept=True)
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={"fixed": FixedEffectConfig("fixed", cfg)},
            n_sweeps=1, vectorized_grid=True)
        grid = [{"fixed": FixedEffectConfig(
            "fixed", dataclasses.replace(cfg, reg_weight=wt))}
            for wt in (0.1, 1e5)]
        results = est.fit(data, validation=val, config_grid=grid)
        best = est.best_model(results)
        assert best.configs["fixed"].optimizer.reg_weight == 0.1

    def test_sweeps_route_to_lane_path_with_full_semantics(self, rng):
        """n_sweeps>1 no longer disengages vectorization: it routes to the
        lane-axis grid (game.grid), whose lanes run BOTH warm-started
        sweeps — the original regression (the one-solve fast path silently
        replacing the second sweep) must stay fixed, now by semantics
        rather than by falling back."""
        data = self._data(rng)
        cfg = OptimizerConfig(max_iters=15, reg=reg.l2(), reg_weight=1.0,
                              regularize_intercept=True)
        grid = [{"fixed": FixedEffectConfig(
            "fixed", dataclasses.replace(cfg, reg_weight=wt))}
            for wt in (0.5, 5.0)]

        def run(vectorized):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs={"fixed": FixedEffectConfig("fixed", cfg)},
                n_sweeps=2, warm_start=True, vectorized_grid=vectorized)
            return est.fit(data, config_grid=grid)

        fast_flag, slow = run(True), run(False)
        for rf, rs in zip(fast_flag, slow):
            # two objective entries per point: the second sweep really ran
            assert len(rf.descent.objective_history) == 2
            np.testing.assert_allclose(
                np.asarray(rf.model.coordinates["fixed"].model.coefficients.means),
                np.asarray(rs.model.coordinates["fixed"].model.coefficients.means),
                atol=5e-3)
            np.testing.assert_allclose(rf.descent.objective_history,
                                       rs.descent.objective_history,
                                       rtol=2e-3)
        # plain fit() (no config_grid) stays sequential: two sweeps
        # progress further than one solve from zeros would.
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={"fixed": FixedEffectConfig("fixed", cfg)},
            n_sweeps=2)
        (r,) = est.fit(data)
        assert len(r.descent.objective_history) == 2

    def test_default_respects_warm_start(self, rng):
        """vectorized_grid=None + warm_start=True (the defaults) must keep
        the sequential warm-started sweep — warm starts the user asked for
        are never silently dropped."""
        data = self._data(rng)
        cfg = OptimizerConfig(max_iters=30, reg=reg.l2(), reg_weight=1.0,
                              regularize_intercept=True)
        grid = [{"fixed": FixedEffectConfig(
            "fixed", dataclasses.replace(cfg, reg_weight=wt))}
            for wt in (0.5, 5.0)]

        def run(**kw):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs={"fixed": FixedEffectConfig("fixed", cfg)},
                n_sweeps=1, **kw)
            return est.fit(data, config_grid=grid)

        default = run()                                  # warm_start=True
        sequential = run(vectorized_grid=False)
        for rd, rs in zip(default, sequential):
            np.testing.assert_array_equal(
                np.asarray(rd.model.coordinates["fixed"].model.coefficients.means),
                np.asarray(rs.model.coordinates["fixed"].model.coefficients.means))
        # warm_start=False defaults into the vectorized path
        auto = run(warm_start=False)
        forced = run(warm_start=False, vectorized_grid=True)
        for ra, rf in zip(auto, forced):
            np.testing.assert_array_equal(
                np.asarray(ra.model.coordinates["fixed"].model.coefficients.means),
                np.asarray(rf.model.coordinates["fixed"].model.coefficients.means))


class TestVectorizedGameGrid:
    """Mixed (fixed + random effect) reg-weight grids run as lanes of one
    vectorized coordinate descent (game.grid.fit_game_grid)."""

    def _mixed(self, rng, n_entities=25):
        data, w_fixed, w_re, ent = _mixed_effect_logistic(
            rng, n_entities=n_entities, d_fixed=6, d_re=3, rows_lo=5,
            rows_hi=40)
        val, *_ = _mixed_effect_logistic(
            rng, n_entities=n_entities, d_fixed=6, d_re=3, rows_lo=3,
            rows_hi=20)
        return data, val

    def _configs(self, cfg_f, cfg_r):
        return {"fixed": FixedEffectConfig("fixed", cfg_f),
                "per_e": RandomEffectConfig("entity", "per_entity", cfg_r)}

    def _grid(self, cfg_f, cfg_r, pairs):
        return [{"fixed": FixedEffectConfig(
                     "fixed", dataclasses.replace(cfg_f, reg_weight=wf)),
                 "per_e": RandomEffectConfig(
                     "entity", "per_entity",
                     dataclasses.replace(cfg_r, reg_weight=wr))}
                for wf, wr in pairs]

    @pytest.mark.tier2
    def test_mixed_grid_matches_sequential(self, rng):
        """The top round-3 deliverable: lane-axis GAME grid == sequential
        per point (mirroring the fixed-only pin above), with per-lane
        sweeps, validation scores, histories, and RE stats."""
        data, val = self._mixed(rng)
        cfg_f = OptimizerConfig(max_iters=25, reg=reg.l2(), reg_weight=0.1)
        cfg_r = OptimizerConfig(max_iters=20, reg=reg.l2(), reg_weight=1.0)
        grid = self._grid(cfg_f, cfg_r,
                          [(0.05, 0.5), (0.05, 5.0), (0.5, 0.5), (0.5, 5.0)])

        def run(vectorized):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs=self._configs(cfg_f, cfg_r),
                n_sweeps=2, warm_start=False, vectorized_grid=vectorized)
            if vectorized:
                assert est.would_vectorize(grid)
            return est.fit(data, validation=val, config_grid=grid)

        fast, slow = run(True), run(False)
        assert len(fast) == len(slow) == 4
        for rf, rs in zip(fast, slow):
            np.testing.assert_allclose(
                np.asarray(rf.model["fixed"].model.coefficients.means),
                np.asarray(rs.model["fixed"].model.coefficients.means),
                atol=5e-3)
            np.testing.assert_allclose(
                np.asarray(rf.model["per_e"].coefficients),
                np.asarray(rs.model["per_e"].coefficients), atol=2e-2)
            assert abs(rf.validation_score - rs.validation_score) < 5e-3
            # 2 sweeps × 2 coordinates = 4 objective entries, same curve
            assert len(rf.descent.objective_history) == 4
            np.testing.assert_allclose(rf.descent.objective_history,
                                       rs.descent.objective_history,
                                       rtol=2e-3)
            assert (rf.configs["per_e"].optimizer.reg_weight
                    == rs.configs["per_e"].optimizer.reg_weight)
            stats = rf.descent.coordinate_stats["per_e"][0]
            assert stats.n_entities == 25
            assert stats.n_converged + stats.n_failed <= 25
        # stronger RE regularization must shrink the per-entity coefficients
        norm_small = np.linalg.norm(np.asarray(fast[0].model["per_e"].coefficients))
        norm_big = np.linalg.norm(np.asarray(fast[1].model["per_e"].coefficients))
        assert norm_big < norm_small

    @pytest.mark.tier2
    def test_l1_grid_runs_owlqn_lanes(self, rng):
        """An elastic-net sweep routes the lane solves through OWL-QN and
        matches the sequential path (sparsity included)."""
        data, val = self._mixed(rng)
        cfg_f = OptimizerConfig(max_iters=30, reg=reg.l1(), reg_weight=0.1)
        cfg_r = OptimizerConfig(max_iters=20, reg=reg.l2(), reg_weight=1.0)
        grid = self._grid(cfg_f, cfg_r, [(0.5, 1.0), (8.0, 1.0)])

        def run(vectorized):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs=self._configs(cfg_f, cfg_r),
                n_sweeps=1, warm_start=False, vectorized_grid=vectorized)
            return est.fit(data, config_grid=grid)

        fast, slow = run(True), run(False)
        for rf, rs in zip(fast, slow):
            wf = np.asarray(rf.model["fixed"].model.coefficients.means)
            ws = np.asarray(rs.model["fixed"].model.coefficients.means)
            np.testing.assert_allclose(wf, ws, atol=5e-3)
            np.testing.assert_array_equal(wf == 0.0, ws == 0.0)
        # the strong-L1 lane is genuinely sparser
        w_hi = np.asarray(fast[1].model["fixed"].model.coefficients.means)
        assert (w_hi == 0.0).sum() > 0

    @pytest.mark.tier2
    def test_runs_on_mesh(self, rng, mesh8):
        """The lane path under a mesh (entity-axis sharded RE chunks,
        row-sharded fixed batch) matches the single-device lane path."""
        data, val = self._mixed(rng)
        cfg_f = OptimizerConfig(max_iters=20, reg=reg.l2(), reg_weight=0.1)
        cfg_r = OptimizerConfig(max_iters=15, reg=reg.l2(), reg_weight=1.0)
        grid = self._grid(cfg_f, cfg_r, [(0.05, 0.5), (0.5, 5.0)])

        def run(mesh):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs=self._configs(cfg_f, cfg_r),
                n_sweeps=1, warm_start=False, vectorized_grid=True,
                mesh=mesh)
            return est.fit(data, validation=val, config_grid=grid)

        on_mesh, single = run(mesh8), run(None)
        for rm, r1 in zip(on_mesh, single):
            np.testing.assert_allclose(
                np.asarray(rm.model["fixed"].model.coefficients.means),
                np.asarray(r1.model["fixed"].model.coefficients.means),
                atol=5e-3)
            np.testing.assert_allclose(
                np.asarray(rm.model["per_e"].coefficients),
                np.asarray(r1.model["per_e"].coefficients), atol=2e-2)

    def test_gate_probes(self, rng):
        """_game_grid_probe accepts reg-only mixed grids and rejects
        anything the lane path cannot replicate."""
        from photon_tpu.game.projector import ProjectionConfig, ProjectorType

        cfg_f = OptimizerConfig(max_iters=10, reg=reg.l2(), reg_weight=0.1)
        cfg_r = OptimizerConfig(max_iters=10, reg=reg.l2(), reg_weight=1.0)
        grid = self._grid(cfg_f, cfg_r, [(0.1, 1.0), (1.0, 2.0)])

        def make(**kw):
            return GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs=self._configs(cfg_f, cfg_r),
                warm_start=False, **kw)

        est = make()
        lanes = est._game_grid_probe(grid)
        assert lanes == {"fixed": [0.1, 1.0], "per_e": [1.0, 2.0]}
        assert est.would_vectorize(grid)
        # n_sweeps > 1 is supported by the mixed path
        assert make(n_sweeps=3).would_vectorize(grid)
        # grid varying a non-reg knob → sequential
        bad = [dict(g) for g in grid]
        bad[1]["fixed"] = FixedEffectConfig(
            "fixed", dataclasses.replace(cfg_f, reg_weight=1.0, max_iters=11))
        assert est._game_grid_probe(bad) is None
        # projection on the RE coordinate → sequential
        proj = make()
        proj.coordinate_configs["per_e"] = RandomEffectConfig(
            "entity", "per_entity", cfg_r,
            projection=ProjectionConfig(ProjectorType.RANDOM, 2))
        assert proj._game_grid_probe(grid) is None
        # normalization → sequential
        from photon_tpu.data.normalization import NormalizationType

        normed = make(
            normalization={"fixed": NormalizationType.STANDARDIZATION})
        assert normed._game_grid_probe(grid) is None
        # warm_start=True default → sequential (never silently dropped)
        warm = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs=self._configs(cfg_f, cfg_r))
        assert not warm.would_vectorize(grid)

    def test_skew_aware_auto_policy(self):
        """Auto mode (vectorized_grid=None) must fall back to sequential on
        strongly skewed reg grids — docs/PERF.md's masking A/B measured the
        lane-axis path 3.7× WORSE at spread 1e5 (lock-step runs every chunk
        to its slowest lane) — while mild geomspace sweeps keep the lane
        path and the explicit tri-state always wins."""
        cfg_f = OptimizerConfig(max_iters=25, reg=reg.l2(), reg_weight=0.1)
        cfg_r = OptimizerConfig(max_iters=20, reg=reg.l2(), reg_weight=1.0)

        def make(**kw):
            return GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs=self._configs(cfg_f, cfg_r),
                warm_start=False, **kw)

        skewed = self._grid(cfg_f, cfg_r,
                            [(100.0, 1.0), (10.0, 1.0), (1.0, 1.0),
                             (1e-3, 1.0)])   # the A/B's skewed profile
        mild = self._grid(cfg_f, cfg_r,
                          [(w, 1.0) for w in np.geomspace(1e-4, 1e-2, 4)])
        auto = make()
        assert auto._grid_reg_skew(skewed) > 1e4
        assert not auto.would_vectorize(skewed)
        assert auto.would_vectorize(mild)
        # explicit tri-state overrides the heuristic in both directions
        assert make(vectorized_grid=True).would_vectorize(skewed)
        assert not make(vectorized_grid=False).would_vectorize(mild)
        # a zero-reg lane among heavy ones counts as unconditioned (slow)
        mixed_zero = self._grid(cfg_f, cfg_r,
                                [(0.0, 1.0), (500.0, 1.0), (50.0, 1.0)])
        assert not auto.would_vectorize(mixed_zero)


def test_poisson_game_end_to_end(rng):
    """GAME with a second GLM family: per-entity Poisson rates recovered
    through coordinate descent (the machinery is task-generic; this pins it
    beyond logistic/linear)."""
    n_entities, d_f = 25, 4
    rows = rng.integers(40, 80, size=n_entities)
    ent = np.repeat(np.arange(n_entities), rows)
    rng.shuffle(ent)
    n = ent.shape[0]
    Xf = (rng.normal(size=(n, d_f)) * 0.3).astype(np.float32)
    ones = np.ones((n, 1), np.float32)
    w_f = rng.normal(size=d_f) * 0.4
    u = rng.normal(size=n_entities) * 0.8  # per-entity log-rate intercepts
    lam = np.exp(np.clip(Xf @ w_f + u[ent], -4, 4))
    y = rng.poisson(lam).astype(np.float32)
    data = GameData.build(y, {"fixed": Xf, "bias": ones}, {"e": ent})
    est = GameEstimator(
        task=TaskType.POISSON_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectConfig(
                "fixed", OptimizerConfig(max_iters=60, reg=reg.l2(),
                                         reg_weight=1e-2)),
            "per_e": RandomEffectConfig(
                "e", "bias", OptimizerConfig(max_iters=40, reg=reg.l2(),
                                             reg_weight=0.5)),
        },
        n_sweeps=2,
    )
    model = est.fit(data)[0].model
    got_w = np.asarray(model["fixed"].model.weights)
    np.testing.assert_allclose(got_w, w_f, atol=0.15)
    u_hat = np.asarray(model["per_e"].coefficients)[:, 0]
    keys = np.asarray(model["per_e"].entity_keys).astype(int)
    corr = np.corrcoef(u_hat, u[keys])[0, 1]
    assert corr > 0.85
    # predicted rates correlate with true rates
    mean = np.asarray(predict_mean(model, data))
    assert np.corrcoef(mean, lam)[0, 1] > 0.9
