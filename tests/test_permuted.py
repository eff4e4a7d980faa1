"""PermutedHybridRows: the scatter-free permuted-space hybrid
(data/matrix.py). Parity contract: every op and every solve must agree
with the SparseRows representation of the same matrix, with all
user-facing vectors in ORIGINAL column order.

Mirrors the reference's representation-invariance expectation
(com.linkedin.photon.ml.data: LabeledPoint math is identical whatever the
underlying vector type).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import GLMBatch, cast_features, make_batch, pad_batch
from photon_tpu.data.matrix import (PermutedHybridRows, SparseRows, matvec,
                                    matvec_lanes, rmatvec, rmatvec_lanes,
                                    sq_rmatvec, to_permuted_hybrid,
                                    weighted_gram)
from photon_tpu.models.training import (evaluate_glm_grid, train_glm,
                                        train_glm_grid)
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.regularization import l2


def _power_law_sparse(rng, n=500, d=800, k=10, d_dense=32):
    """Zipf-ish column frequencies so hot/bucket/deep-tail paths all fill.

    Duplicate (row, col) slots get value 0 (the padding convention): real
    feature-bag rows never repeat a feature, and duplicate cells are where
    per-entry and per-cell quadratic semantics (sq_rmatvec) diverge."""
    col = (rng.zipf(1.5, size=(n, k)).astype(np.int64) - 1) % (d - 1)
    val = rng.normal(size=(n, k)).astype(np.float32)
    order = np.argsort(col, axis=1, kind="stable")
    sorted_col = np.take_along_axis(col, order, axis=1)
    dup = sorted_col[:, 1:] == sorted_col[:, :-1]
    dupmask = np.zeros_like(col, bool)
    np.put_along_axis(dupmask, order[:, 1:], dup, axis=1)
    val[dupmask] = 0.0
    ind = np.concatenate([col, np.full((n, 1), d - 1)], axis=1).astype(
        np.int32)
    va = np.concatenate([val, np.ones((n, 1), np.float32)], axis=1)
    X = SparseRows(jnp.asarray(ind), jnp.asarray(va), d)
    P = to_permuted_hybrid(X, d_dense)
    return X, P


def test_perm_roundtrip_and_layout(rng):
    X, P = _power_law_sparse(rng)
    d = X.n_features
    perm = np.asarray(P.perm_cols)
    inv = np.asarray(P.inv_perm)
    assert sorted(perm.tolist()) == list(range(d))
    np.testing.assert_array_equal(perm[inv], np.arange(d))
    v = rng.normal(size=d).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(P.to_model_space(P.from_model_space(v))), v)
    # intercept (original last column, in every row) must be hot
    assert P.last_col_pos < P.d_sel
    assert np.asarray(P.dense)[:, P.last_col_pos].min() == 1.0


def test_perm_matvec_rmatvec_parity(rng):
    X, P = _power_law_sparse(rng)
    n, d = X.shape
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    r = jnp.asarray(rng.normal(size=n).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(matvec(P, P.from_model_space(w))),
        np.asarray(matvec(X, w)), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(P.to_model_space(rmatvec(P, r))),
        np.asarray(rmatvec(X, r)), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(P.to_model_space(sq_rmatvec(P, r))),
        np.asarray(sq_rmatvec(X, r)), rtol=2e-4, atol=2e-4)


def test_perm_lane_ops_parity(rng):
    X, P = _power_law_sparse(rng)
    n, d = X.shape
    G = 5
    W = jnp.asarray(rng.normal(size=(d, G)).astype(np.float32))
    R = jnp.asarray(rng.normal(size=(n, G)).astype(np.float32))
    Wp = P.from_model_space(W)
    mv = np.asarray(matvec_lanes(P, Wp))
    rv = np.asarray(P.to_model_space(rmatvec_lanes(P, R)))
    for g in range(G):
        np.testing.assert_allclose(mv[:, g], np.asarray(matvec(X, W[:, g])),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(rv[:, g], np.asarray(rmatvec(X, R[:, g])),
                                   rtol=2e-4, atol=2e-4)


def test_perm_weighted_gram_parity(rng):
    X, P = _power_law_sparse(rng, n=200, d=60, k=6, d_dense=8)
    r = jnp.asarray(rng.uniform(0.1, 1.0, size=200).astype(np.float32))
    Gp = np.asarray(weighted_gram(P, r))          # permuted space
    Gs = np.asarray(weighted_gram(X, r))
    perm = np.asarray(P.perm_cols)
    np.testing.assert_allclose(Gp, Gs[np.ix_(perm, perm)], rtol=1e-4,
                               atol=1e-4)


def test_perm_empty_tail(rng):
    # every column hot → tail empty; ops must still be exact
    ind = rng.integers(0, 16, size=(50, 4)).astype(np.int32)
    val = rng.normal(size=(50, 4)).astype(np.float32)
    X = SparseRows(jnp.asarray(ind), jnp.asarray(val), 16)
    P = to_permuted_hybrid(X, 16)
    assert P.bucket_rows == ()
    w = jnp.asarray(rng.normal(size=16).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(matvec(P, P.from_model_space(w))),
        np.asarray(matvec(X, w)), rtol=1e-5, atol=1e-5)
    r = jnp.asarray(rng.normal(size=50).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(P.to_model_space(rmatvec(P, r))),
        np.asarray(rmatvec(X, r)), rtol=1e-5, atol=1e-5)


def test_perm_train_glm_parity(rng):
    X, P = _power_law_sparse(rng)
    wt = rng.normal(size=X.n_features).astype(np.float32) * 0.5
    z = np.asarray(matvec(X, jnp.asarray(wt)))
    y = jnp.asarray((rng.random(X.shape[0]) < 1 / (1 + np.exp(-z))).astype(
        np.float32))
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=l2(),
                          reg_weight=0.1, history=5)
    m_p, r_p = train_glm(make_batch(P, y), TaskType.LOGISTIC_REGRESSION, cfg)
    m_s, r_s = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION, cfg)
    np.testing.assert_allclose(float(r_p.value), float(r_s.value), rtol=1e-5)
    # 3e-2, not 5e-3: the two layouts reduce in different orders and a
    # relative-decrease stop at 1e-6 leaves each solve anywhere in a flat
    # neighbourhood of the optimum. The old pin held by both solves
    # sharing the two-loop's arithmetic: the PARENT's vector-space
    # recursion with its dots summed in another order reads 0.0054-0.0135
    # here, the carried-products form 0.0077-0.016 (PERF.md §6, PR 28)
    np.testing.assert_allclose(np.asarray(m_p.coefficients.means),
                               np.asarray(m_s.coefficients.means), atol=3e-2)
    # model scoring translates to permuted space internally
    np.testing.assert_allclose(np.asarray(m_p.score(P)),
                               np.asarray(m_p.score(X)), rtol=2e-4, atol=2e-4)


def test_perm_train_glm_regularize_intercept_off(rng):
    X, P = _power_law_sparse(rng)
    y = jnp.asarray((rng.random(X.shape[0]) < 0.5).astype(np.float32))
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=l2(),
                          reg_weight=10.0, history=5,
                          regularize_intercept=False)
    m_p, r_p = train_glm(make_batch(P, y), TaskType.LOGISTIC_REGRESSION, cfg)
    m_s, r_s = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION, cfg)
    np.testing.assert_allclose(float(r_p.value), float(r_s.value), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(m_p.coefficients.means),
                               np.asarray(m_s.coefficients.means), atol=5e-3)


def test_perm_train_glm_w0_and_normalization(rng):
    from photon_tpu.data.normalization import (NormalizationContext,
                                               NormalizationType)

    X, P = _power_law_sparse(rng, n=400, d=200, k=8, d_dense=16)
    d = X.n_features
    y = jnp.asarray((rng.random(400) < 0.5).astype(np.float32))
    w0 = rng.normal(size=d).astype(np.float32) * 0.1
    norm = NormalizationContext.build(X, NormalizationType.STANDARDIZATION,
                                      intercept_index=d - 1)
    # standardization of rare sparse columns gives huge factors and flat
    # optimum directions; strong L2 keeps the parity check conditioned
    # (the objective VALUE is the tight assertion either way)
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=l2(),
                          reg_weight=5.0, history=5)
    m_p, r_p = train_glm(make_batch(P, y), TaskType.LOGISTIC_REGRESSION,
                         cfg, w0=w0, normalization=norm)
    m_s, r_s = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION,
                         cfg, w0=w0, normalization=norm)
    np.testing.assert_allclose(float(r_p.value), float(r_s.value), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(m_p.coefficients.means),
                               np.asarray(m_s.coefficients.means), atol=5e-3)


def test_perm_grid_parity_and_eval(rng):
    X, P = _power_law_sparse(rng)
    wt = rng.normal(size=X.n_features).astype(np.float32) * 0.5
    z = np.asarray(matvec(X, jnp.asarray(wt)))
    y = jnp.asarray((rng.random(X.shape[0]) < 1 / (1 + np.exp(-z))).astype(
        np.float32))
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    weights = [1e-1, 1.0, 30.0]
    bp, bs = make_batch(P, y), make_batch(X, y)
    grid_p = train_glm_grid(bp, TaskType.LOGISTIC_REGRESSION, cfg, weights)
    grid_s = train_glm_grid(bs, TaskType.LOGISTIC_REGRESSION, cfg, weights)
    for (m_p, r_p), (m_s, r_s) in zip(grid_p, grid_s):
        np.testing.assert_allclose(float(r_p.value), float(r_s.value),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(m_p.coefficients.means),
                                   np.asarray(m_s.coefficients.means),
                                   atol=2e-2)
    best_p, scores_p = evaluate_glm_grid(grid_p, bp)
    best_s, scores_s = evaluate_glm_grid(grid_s, bs)
    assert best_p == best_s
    np.testing.assert_allclose(scores_p, scores_s, rtol=1e-3)


def test_perm_grid_device_results_original_order(rng):
    X, P = _power_law_sparse(rng, n=200, d=100, k=6, d_dense=8)
    y = jnp.asarray((rng.random(200) < 0.5).astype(np.float32))
    cfg = OptimizerConfig(max_iters=30, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    res_p, _ = train_glm_grid(make_batch(P, y), TaskType.LOGISTIC_REGRESSION,
                              cfg, [0.5, 2.0], device_results=True)
    grid_s = train_glm_grid(make_batch(X, y), TaskType.LOGISTIC_REGRESSION,
                            cfg, [0.5, 2.0])
    for i, (m_s, _) in enumerate(grid_s):
        np.testing.assert_allclose(np.asarray(res_p.w)[i],
                                   np.asarray(m_s.coefficients.means),
                                   atol=2e-2)


def test_perm_pad_and_cast(rng):
    X, P = _power_law_sparse(rng, n=100, d=300, k=6)
    y = jnp.asarray(rng.normal(size=100).astype(np.float32))
    b = pad_batch(make_batch(P, y), 128)
    assert b.n == 128
    w = jnp.asarray(rng.normal(size=300).astype(np.float32))
    z = np.asarray(matvec(b.X, b.X.from_model_space(w)))
    np.testing.assert_allclose(z[:100], np.asarray(matvec(X, w)), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(z[100:], 0.0, atol=1e-6)
    bc = cast_features(b)
    assert bc.X.dense.dtype == jnp.bfloat16
    assert all(v.dtype == jnp.bfloat16 for v in bc.X.bucket_vals)


def test_perm_intercept_in_tail_detected(rng):
    """Hot-selection tie-break can leave an every-row intercept column in
    the tail (other columns with duplicate entries out-count it); the
    bucket scan must still recognize it — and reject a near-intercept
    missing one row."""
    from photon_tpu.data.matrix import last_column_is_intercept

    n, d = 16, 6
    ind = np.tile(np.array([[0, 0, 1, 1, 2, 5]], np.int32), (n, 1))
    val = np.ones((n, 6), np.float32)
    P = to_permuted_hybrid(SparseRows(jnp.asarray(ind), jnp.asarray(val), d),
                           d_dense=2)
    assert P.last_col_pos >= P.d_sel  # forced into the tail
    assert last_column_is_intercept(P)
    val2 = val.copy()
    val2[3, 5] = 0.0  # intercept missing from one row
    P2 = to_permuted_hybrid(
        SparseRows(jnp.asarray(ind), jnp.asarray(val2), d), d_dense=2)
    assert not last_column_is_intercept(P2)


def test_perm_game_fixed_effect_falls_back_correctly(rng):
    """A GAME fit whose fixed shard is PermutedHybridRows must route
    through train_glm (which owns the coefficient-space translation), not
    the fused update or the lane grid — and match the SparseRows fit."""
    from photon_tpu.game.coordinate_descent import _fixed_fusable
    from photon_tpu.game.dataset import GameData
    from photon_tpu.game.estimator import FixedEffectConfig, GameEstimator

    X, P = _power_law_sparse(rng, n=300, d=150, k=6, d_dense=16)
    y = (rng.random(300) < 0.5).astype(np.float32)
    cfg = OptimizerConfig(max_iters=40, tolerance=1e-6, reg=l2(),
                          reg_weight=1.0)

    def fit(shard):
        data = GameData.build(y, {"f": shard}, {})
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={"fixed": FixedEffectConfig("f", cfg)},
            warm_start=False)
        assert not est._grid_data_supported(data) or shard is X
        return est.fit(data)[0]

    r_p, r_s = fit(P), fit(X)
    np.testing.assert_allclose(
        np.asarray(r_p.model["fixed"].model.coefficients.means),
        np.asarray(r_s.model["fixed"].model.coefficients.means), atol=5e-3)


def test_perm_mesh_rejected(rng, mesh8):
    X, P = _power_law_sparse(rng, n=64, d=100, k=4)
    y = jnp.asarray(rng.normal(size=64).astype(np.float32))
    cfg = OptimizerConfig(max_iters=5, reg=l2(), reg_weight=0.1)
    with pytest.raises(ValueError, match="single-device"):
        train_glm(make_batch(P, y), TaskType.LINEAR_REGRESSION, cfg,
                  mesh=mesh8)


class TestShardedPermuted:
    """ShardedPermutedHybridRows (the mesh form of the scatter-free
    layout): op + solve parity vs the single-device permuted build, with
    user-facing vectors in original column order."""

    def _problem(self, rng, n=640, d=500, k=9):
        col = (rng.zipf(1.5, size=(n, k - 1)).astype(np.int64) - 1) % (d - 1)
        val = rng.normal(size=(n, k - 1)).astype(np.float32)
        order = np.argsort(col, axis=1, kind="stable")
        sorted_col = np.take_along_axis(col, order, axis=1)
        dup = sorted_col[:, 1:] == sorted_col[:, :-1]
        dupmask = np.zeros_like(col, bool)
        np.put_along_axis(dupmask, order[:, 1:], dup, axis=1)
        val[dupmask] = 0.0
        ind = np.concatenate([col, np.full((n, 1), d - 1)], axis=1).astype(
            np.int32)
        va = np.concatenate([val, np.ones((n, 1), np.float32)], axis=1)
        X = SparseRows(jnp.asarray(ind), jnp.asarray(va), d)
        wt = rng.normal(size=d).astype(np.float32) * 0.5
        z = np.einsum("nk,nk->n", va, wt[ind])
        y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
        return X, y

    def test_ops_match_single_device_permuted(self, rng):
        from photon_tpu.data.matrix import shard_permuted_hybrid

        X, _ = self._problem(rng)
        n, d = X.shape
        P1 = to_permuted_hybrid(X, 64)
        SP = shard_permuted_hybrid(X, 8, 64)
        assert SP.n_shards == 8 and SP.shape == (n, d)
        w = rng.normal(size=d).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(matvec(SP, SP.from_model_space(w))),
            np.asarray(matvec(P1, P1.from_model_space(w))),
            rtol=2e-5, atol=1e-5)
        r = rng.normal(size=n).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(SP.to_model_space(rmatvec(SP, r))),
            np.asarray(P1.to_model_space(rmatvec(P1, r))),
            rtol=2e-5, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(SP.to_model_space(sq_rmatvec(SP, r))),
            np.asarray(P1.to_model_space(sq_rmatvec(P1, r))),
            rtol=2e-5, atol=1e-4)
        W = rng.normal(size=(d, 4)).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(matvec_lanes(SP, SP.from_model_space(W))),
            np.asarray(matvec_lanes(P1, P1.from_model_space(W))),
            rtol=2e-5, atol=1e-4)
        R = rng.normal(size=(n, 4)).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(SP.to_model_space(rmatvec_lanes(SP, R))),
            np.asarray(P1.to_model_space(rmatvec_lanes(P1, R))),
            rtol=2e-5, atol=1e-4)

    def test_local_view_composes_to_global(self, rng):
        """Slicing shard s's leaves + local() must equal the global op on
        that shard's row range — the shard_map contract, checked without a
        mesh."""
        from photon_tpu.data.matrix import shard_permuted_hybrid

        X, _ = self._problem(rng)
        n, d = X.shape
        SP = shard_permuted_hybrid(X, 4, 64)
        n_local = SP.n_local
        w = rng.normal(size=d).astype(np.float32)
        wp = SP.from_model_space(w)
        full = np.asarray(matvec(SP, wp))
        grads = []
        for s in range(SP.n_shards):
            sliced = dataclasses.replace(
                SP,
                dense=SP.dense[s * n_local:(s + 1) * n_local],
                tail_pcols=SP.tail_pcols[s:s + 1],
                tail_vals=SP.tail_vals[s:s + 1],
                row_bounds=SP.row_bounds[s:s + 1],
                bucket_rows=tuple(b[s:s + 1] for b in SP.bucket_rows),
                bucket_vals=tuple(b[s:s + 1] for b in SP.bucket_vals))
            loc = sliced.local()
            np.testing.assert_allclose(
                np.asarray(matvec(loc, wp)),
                full[s * n_local:(s + 1) * n_local], rtol=2e-5, atol=1e-5)
            r = rng.normal(size=n_local).astype(np.float32)
            grads.append((loc, r))
        # per-shard rmatvec partials sum to the global rmatvec
        r_full = np.concatenate([np.asarray(r) for _, r in grads])
        total = sum(np.asarray(rmatvec(loc, jnp.asarray(r)))
                    for loc, r in grads)
        np.testing.assert_allclose(
            total, np.asarray(rmatvec(SP, jnp.asarray(r_full))),
            rtol=2e-5, atol=1e-4)

    def test_train_glm_mesh_matches_single_device(self, rng, mesh8):
        from photon_tpu.data.dataset import shard_permuted_batch

        X, y = self._problem(rng)
        sb = shard_permuted_batch(make_batch(X, y), mesh8.devices.size, 64)
        cfg = OptimizerConfig(max_iters=60, tolerance=1e-7, reg=l2(),
                              reg_weight=1.0)
        m_s, r_s = train_glm(sb, TaskType.LOGISTIC_REGRESSION, cfg,
                             mesh=mesh8)
        m_1, r_1 = train_glm(make_batch(to_permuted_hybrid(X, 64), y),
                             TaskType.LOGISTIC_REGRESSION, cfg)
        np.testing.assert_allclose(float(r_s.value), float(r_1.value),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(m_s.coefficients.means),
                                   np.asarray(m_1.coefficients.means),
                                   atol=2e-3)

    def test_train_glm_grid_lanes_mesh(self, rng, mesh8):
        from photon_tpu.data.dataset import shard_permuted_batch

        X, y = self._problem(rng)
        sb = shard_permuted_batch(make_batch(X, y), mesh8.devices.size, 64)
        cfg = OptimizerConfig(max_iters=60, tolerance=1e-7, reg=l2(),
                              reg_weight=0.0, history=5)
        weights = [1e-1, 1.0, 10.0]
        grid = train_glm_grid(sb, TaskType.LOGISTIC_REGRESSION, cfg,
                              weights, mesh=mesh8)
        ref = train_glm_grid(make_batch(to_permuted_hybrid(X, 64), y),
                             TaskType.LOGISTIC_REGRESSION, cfg, weights)
        for (ms, rs), (m1, r1) in zip(grid, ref):
            np.testing.assert_allclose(float(rs.value), float(r1.value),
                                       rtol=1e-4)
            np.testing.assert_allclose(np.asarray(ms.coefficients.means),
                                       np.asarray(m1.coefficients.means),
                                       atol=2e-2)

    def test_cast_features_bf16(self, rng):
        from photon_tpu.data.matrix import shard_permuted_hybrid

        X, y = self._problem(rng)
        SP = shard_permuted_hybrid(X, 4, 64)
        b = cast_features(make_batch(SP, y))
        assert b.X.dense.dtype == jnp.bfloat16
        assert all(v.dtype == jnp.bfloat16 for v in b.X.bucket_vals)
        w = rng.normal(size=X.n_features).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(matvec(b.X, b.X.from_model_space(w))),
            np.asarray(matvec(SP, SP.from_model_space(w))),
            rtol=2e-2, atol=2e-2)
