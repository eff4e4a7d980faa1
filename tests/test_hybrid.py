"""HybridRows (hot-dense / cold-sparse split) vs plain SparseRows parity."""
import numpy as np
import jax.numpy as jnp
import pytest
import scipy.sparse as sp

from photon_tpu.data.dataset import cast_features, make_batch, pad_batch
from photon_tpu.data.matrix import (
    HybridRows,
    SparseRows,
    from_scipy_csr,
    matvec,
    rmatvec,
    sq_rmatvec,
    to_hybrid,
    weighted_gram,
)
from photon_tpu.models.training import train_glm
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim import regularization as reg
from photon_tpu.optim.config import OptimizerConfig


@pytest.fixture
def power_law(rng):
    """Power-law sparse matrix: a few hot columns, long cold tail."""
    n, d, k = 400, 500, 12
    cols = np.minimum((rng.pareto(1.0, size=(n, k)) * 20).astype(np.int64),
                      d - 1)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    rows = np.repeat(np.arange(n), k)
    M = sp.csr_matrix((vals.ravel(), (rows, cols.ravel())), shape=(n, d))
    M.sum_duplicates()
    return from_scipy_csr(M)


class TestHybridParity:
    def test_ops_match_sparse(self, power_law, rng):
        X = power_law
        H = to_hybrid(X, d_dense=32)
        assert H.shape == X.shape
        w = jnp.asarray(rng.normal(size=X.n_features), jnp.float32)
        r = jnp.asarray(rng.normal(size=X.shape[0]), jnp.float32)
        np.testing.assert_allclose(np.asarray(matvec(H, w)),
                                   np.asarray(matvec(X, w)),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(rmatvec(H, r)),
                                   np.asarray(rmatvec(X, r)),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(sq_rmatvec(H, r)),
                                   np.asarray(sq_rmatvec(X, r)),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(weighted_gram(H, r)),
                                   np.asarray(weighted_gram(X, r)),
                                   rtol=1e-3, atol=1e-3)

    def test_hot_columns_really_dense(self, power_law):
        H = to_hybrid(power_law, d_dense=32)
        # The selected columns carry no tail nnz.
        tail_cols = set(np.asarray(H.tail_cols)[
            np.asarray(H.tail_vals) != 0].ravel())
        assert tail_cols.isdisjoint(set(np.asarray(H.dense_cols)))
        # Power-law data: 32 of 500 columns should cover most nnz.
        nnz_dense = int((np.asarray(H.dense) != 0).sum())
        nnz_tail = int((np.asarray(H.tail_vals) != 0).sum())
        assert nnz_dense > nnz_tail
        # Flat tail is exact-size (no per-row padding) and row-sorted.
        rows = np.asarray(H.tail_rows)
        assert (np.diff(rows) >= 0).all()

    def test_train_glm_hybrid(self, power_law, rng):
        X = power_law
        n = X.shape[0]
        w_true = rng.normal(size=X.n_features).astype(np.float32)
        z = np.asarray(matvec(X, jnp.asarray(w_true)))
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float32)
        cfg = OptimizerConfig(max_iters=60, reg=reg.l2(), reg_weight=1.0,
                              regularize_intercept=True)
        m_s, r_s = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION,
                             cfg)
        m_h, r_h = train_glm(make_batch(to_hybrid(X, 32), y),
                             TaskType.LOGISTIC_REGRESSION, cfg)
        assert bool(r_h.converged)
        np.testing.assert_allclose(np.asarray(m_h.coefficients.means),
                                   np.asarray(m_s.coefficients.means),
                                   atol=2e-3)

    def test_pad_and_cast(self, power_law, rng):
        n = power_law.shape[0]
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        b = make_batch(to_hybrid(power_law, 16), y)
        padded = pad_batch(b, n + 24)
        assert padded.X.dense.shape[0] == n + 24
        w = jnp.asarray(rng.normal(size=power_law.n_features), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(matvec(padded.X, w))[:n],
            np.asarray(matvec(b.X, w)), rtol=1e-5, atol=1e-5)
        b16 = cast_features(b)
        assert b16.X.dense.dtype == jnp.bfloat16
        assert b16.X.tail_vals.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(matvec(b16.X, w)),
                                   np.asarray(matvec(b.X, w)),
                                   rtol=0.05, atol=0.1)


class TestShardedHybrid:
    """ShardedHybridRows: the mesh-ready per-shard-tail layout."""

    def test_global_ops_match_sparse(self, power_law, rng):
        from photon_tpu.data.matrix import shard_hybrid

        X = power_law
        S = shard_hybrid(X, n_shards=8, d_dense=32)
        assert S.n_shards == 8 and S.shape == X.shape
        w = jnp.asarray(rng.normal(size=X.n_features), jnp.float32)
        r = jnp.asarray(rng.normal(size=X.shape[0]), jnp.float32)
        np.testing.assert_allclose(np.asarray(matvec(S, w)),
                                   np.asarray(matvec(X, w)),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(rmatvec(S, r)),
                                   np.asarray(rmatvec(X, r)),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(sq_rmatvec(S, r)),
                                   np.asarray(sq_rmatvec(X, r)),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(weighted_gram(S, r)),
                                   np.asarray(weighted_gram(X, r)),
                                   rtol=1e-3, atol=1e-3)

    def test_local_views_tile_the_matrix(self, power_law, rng):
        """Concatenating each shard's local() matvec == the global matvec."""
        import dataclasses

        from photon_tpu.data.matrix import shard_hybrid

        X = shard_hybrid(power_law, n_shards=8, d_dense=32)
        w = jnp.asarray(rng.normal(size=X.n_features), jnp.float32)
        n_local = X.n_local
        pieces = []
        for s in range(X.n_shards):
            local = dataclasses.replace(
                X, dense=X.dense[s * n_local:(s + 1) * n_local],
                tail_rows=X.tail_rows[s:s + 1],
                tail_cols=X.tail_cols[s:s + 1],
                tail_vals=X.tail_vals[s:s + 1]).local()
            # per-shard rows ascending (sorted segment_sum contract)
            assert (np.diff(np.asarray(local.tail_rows)) >= 0).all()
            pieces.append(np.asarray(matvec(local, w)))
        np.testing.assert_allclose(np.concatenate(pieces),
                                   np.asarray(matvec(X, w)),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("opt", ["LBFGS", "TRON", "OWLQN"])
    def test_train_glm_sharded_matches_single(self, power_law, rng, mesh8,
                                              opt):
        from photon_tpu.data.dataset import shard_hybrid_batch
        from photon_tpu.optim.config import OptimizerType

        X = power_law
        n = X.shape[0]
        w_true = rng.normal(size=X.n_features).astype(np.float32) * 0.5
        z = np.asarray(matvec(X, jnp.asarray(w_true)))
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float32)
        is_l1 = opt == "OWLQN"
        cfg = OptimizerConfig(
            optimizer=OptimizerType[opt], max_iters=40,
            reg=reg.l1() if is_l1 else reg.l2(), reg_weight=1.0,
            regularize_intercept=True)
        m_ref, _ = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION,
                             cfg)
        b = shard_hybrid_batch(make_batch(X, y), mesh8.devices.size,
                               d_dense=32)
        m_sh, res = train_glm(b, TaskType.LOGISTIC_REGRESSION, cfg,
                              mesh=mesh8)
        assert not bool(res.failed)
        np.testing.assert_allclose(np.asarray(m_sh.coefficients.means),
                                   np.asarray(m_ref.coefficients.means),
                                   atol=5e-3)

    def test_sharded_variances_match_single(self, power_law, rng, mesh8):
        from photon_tpu.data.dataset import shard_hybrid_batch
        from photon_tpu.models.variance import VarianceComputationType

        X = power_law
        n = X.shape[0]
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        cfg = OptimizerConfig(max_iters=25, reg=reg.l2(), reg_weight=2.0,
                              regularize_intercept=True)
        m_ref, _ = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION,
                             cfg, variance=VarianceComputationType.SIMPLE)
        b = shard_hybrid_batch(make_batch(X, y), mesh8.devices.size,
                               d_dense=32)
        m_sh, _ = train_glm(b, TaskType.LOGISTIC_REGRESSION, cfg, mesh=mesh8,
                            variance=VarianceComputationType.SIMPLE)
        np.testing.assert_allclose(np.asarray(m_sh.coefficients.variances),
                                   np.asarray(m_ref.coefficients.variances),
                                   rtol=1e-3, atol=1e-3)

    def test_mismatched_shards_raise(self, power_law, rng, mesh8):
        from photon_tpu.data.dataset import shard_hybrid_batch

        n = power_law.shape[0]
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        b = shard_hybrid_batch(make_batch(power_law, y), 4, d_dense=16)
        with pytest.raises(ValueError, match="4 shards"):
            train_glm(b, TaskType.LOGISTIC_REGRESSION,
                      OptimizerConfig(max_iters=2), mesh=mesh8)

    def test_plain_hybrid_under_mesh_points_at_sharded(self, power_law, rng,
                                                       mesh8):
        n = power_law.shape[0]
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        b = make_batch(to_hybrid(power_law, 16), y)
        with pytest.raises(ValueError, match="shard_hybrid_batch"):
            train_glm(b, TaskType.LOGISTIC_REGRESSION,
                      OptimizerConfig(max_iters=2), mesh=mesh8)

    def test_single_device_global_view_owlqn(self, power_law, rng):
        """A ShardedHybridRows batch also works WITHOUT a mesh (global view),
        including the OWLQN route whose fused-padding branch must not try to
        pad the laid-out shards (regression: pad_batch ValueError)."""
        from photon_tpu.data.dataset import shard_hybrid_batch

        n = power_law.shape[0]
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        b = shard_hybrid_batch(make_batch(power_law, y), 8, d_dense=16)
        cfg = OptimizerConfig(max_iters=25, reg=reg.l1(), reg_weight=2.0,
                              regularize_intercept=True)
        m_sh, res = train_glm(b, TaskType.LOGISTIC_REGRESSION, cfg)
        m_ref, _ = train_glm(make_batch(power_law, y),
                             TaskType.LOGISTIC_REGRESSION, cfg)
        assert not bool(res.failed)
        np.testing.assert_allclose(np.asarray(m_sh.coefficients.means),
                                   np.asarray(m_ref.coefficients.means),
                                   atol=5e-3)

    @pytest.mark.parametrize("l1", [False, True])
    def test_grid_on_sharded_hybrid(self, power_law, rng, mesh8, l1):
        """train_glm_grid over a ShardedHybridRows batch: vmapped lanes
        inside the shard_map solver, parity with single-device grid lanes."""
        from photon_tpu.data.dataset import shard_hybrid_batch
        from photon_tpu.models.training import train_glm_grid

        X = power_law
        n = X.shape[0]
        z = np.asarray(matvec(X, jnp.asarray(
            rng.normal(size=X.n_features).astype(np.float32) * 0.5)))
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float32)
        cfg = OptimizerConfig(max_iters=30,
                              reg=reg.l1() if l1 else reg.l2(),
                              reg_weight=0.0, regularize_intercept=True)
        weights = [0.5, 5.0]
        ref = train_glm_grid(make_batch(X, y), TaskType.LOGISTIC_REGRESSION,
                             cfg, weights)
        b = shard_hybrid_batch(make_batch(X, y), mesh8.devices.size,
                               d_dense=32)
        got = train_glm_grid(b, TaskType.LOGISTIC_REGRESSION, cfg, weights,
                             mesh=mesh8)
        for (m_r, _), (m_g, r_g) in zip(ref, got):
            assert not bool(r_g.failed)
            np.testing.assert_allclose(np.asarray(m_g.coefficients.means),
                                       np.asarray(m_r.coefficients.means),
                                       atol=5e-3)


class TestDeviceDenseBuild:
    """to_hybrid(device_dense_dtype=...) scatters the hot block on device
    from the compact COO (the bench load path: ~10x fewer host→device
    bytes than the materialized block) — it must match the host bincount build exactly up to the storage cast."""

    def test_matches_host_build(self, rng=np.random.default_rng(3)):
        n, k, d = 400, 6, 5000
        ind = rng.integers(0, d, (n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        val[rng.uniform(size=(n, k)) < 0.2] = 0.0  # padding slots
        # force duplicate (row, col) entries: summed on both paths
        ind[:, 1] = ind[:, 0]
        X = SparseRows(ind, val, d)
        host = to_hybrid(X, 64)
        dev = to_hybrid(X, 64, device_dense_dtype=jnp.float32)
        np.testing.assert_array_equal(host.dense_cols, dev.dense_cols)
        np.testing.assert_allclose(np.asarray(dev.dense), host.dense,
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(host.tail_rows, dev.tail_rows)
        np.testing.assert_array_equal(host.tail_cols, dev.tail_cols)
        np.testing.assert_array_equal(host.tail_vals, dev.tail_vals)

    def test_bf16_storage_matches_cast_host(self):
        rng = np.random.default_rng(4)
        n, k, d = 300, 5, 3000
        ind = rng.integers(0, d, (n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        X = SparseRows(ind, val, d)
        host = cast_features(make_batch(to_hybrid(X, 32), np.zeros(n)))
        dev = to_hybrid(X, 32, device_dense_dtype=jnp.bfloat16)
        assert dev.dense.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(dev.dense, np.float32),
            np.asarray(host.X.dense, np.float32))

    def test_chunked_scatter_matches(self, monkeypatch):
        """The row-chunked device scatter (bounded f32 intermediate) is
        identical to the one-shot scatter."""
        import photon_tpu.data.matrix as matrix_mod

        rng = np.random.default_rng(5)
        n, k, d = 700, 6, 4000
        ind = rng.integers(0, d, (n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        X = SparseRows(ind, val, d)
        one_shot = to_hybrid(X, 48, device_dense_dtype=jnp.float32)
        monkeypatch.setattr(matrix_mod, "_SCATTER_CHUNK_ELEMS", 48 * 128)
        chunked = to_hybrid(X, 48, device_dense_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(one_shot.dense),
                                      np.asarray(chunked.dense))
