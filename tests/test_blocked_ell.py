"""BlockedEllRows: the blocked-ELL scatter-free sparse hot path
(data/matrix.py, round 12). Parity contract: every op and every solve
must agree with the SparseRows representation of the same matrix, with
all user-facing vectors in ORIGINAL column order — across resident,
lane-grid, streamed, and mesh paths.

Mirrors tests/test_permuted.py's representation-invariance suite for the
round-5 layout (reference: com.linkedin.photon.ml.data — LabeledPoint
math is identical whatever the underlying vector type).
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_tpu import telemetry
from photon_tpu.data.dataset import (cast_features, chunk_batch,
                                     chunk_blocked_ell, make_batch,
                                     pad_batch, shard_blocked_ell_batch,
                                     with_offsets)
from photon_tpu.data.matrix import (BlockedEllRows, ShardedBlockedEllRows,
                                    SparseRows, _rung_width, _width_rungs,
                                    blocked_ell_from_scipy_csr,
                                    from_scipy_csr, last_column_is_intercept,
                                    layout_matvec, layout_matvec_lanes,
                                    matvec, matvec_lanes, rmatvec,
                                    rmatvec_lanes, rows_from_caller,
                                    rows_to_caller, shard_blocked_ell,
                                    sorted_segment_sum, sq_rmatvec,
                                    to_blocked_ell, weighted_gram)
from photon_tpu.models.training import train_glm, train_glm_grid
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.regularization import l2

# The mesh/grid/streamed cases compile multi-device solver programs; drop
# them at module teardown so the suite stays inside the live-executable
# envelope (see conftest).
pytestmark = pytest.mark.release_programs


def _power_law_sparse(rng, n=500, d=800, k=10, d_dense=32):
    """Zipf-ish column frequencies so the hot block, several ELL widths,
    and the occurrence buckets all fill. Duplicate (row, col) slots get
    value 0 (the padding convention)."""
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
    B = to_blocked_ell(X, d_dense)
    return X, B


def _labels(rng, X):
    wt = rng.normal(size=X.n_features).astype(np.float32) * 0.5
    z = np.asarray(matvec(X, jnp.asarray(wt)))
    return jnp.asarray((rng.random(X.shape[0]) < 1 / (1 + np.exp(-z)))
                       .astype(np.float32))


def _weights_offsets(rng, n):
    return (rng.uniform(0.25, 4.0, size=n).astype(np.float32),
            rng.normal(size=n).astype(np.float32))


# ------------------------------------------------------- the width ladder
# the ladder written out: every power of two, and 3·2^(j−1) between 2^j
# and 2^(j+1)
LADDER = sorted({1 << j for j in range(22)}
                | {3 << (j - 1) for j in range(1, 21)})


def _ladder_width(count):
    """The smallest width of `LADDER` that holds ``count`` (no layout
    code)."""
    return next(w for w in LADDER if w >= max(int(count), 1))


@pytest.mark.parametrize("fact", ["monotone", "holds_the_count",
                                  "rung_below_is_too_small",
                                  "pow2_or_three_halves", "is_the_ladder"])
def test_width_ladder(fact):
    """count → rung → width, the one function pair both tail structures
    are bucketed by: every count up to 2^12 + 1, and every count within
    one of a rung up to 3·2^19."""
    edges = np.asarray(LADDER[:-1], np.int64)
    counts = np.unique(np.concatenate(
        [np.arange(0, (1 << 12) + 2), edges - 1, edges, edges + 1]))
    rungs = _width_rungs(counts)
    widths = _rung_width(rungs)
    if fact == "monotone":
        assert (np.diff(rungs) >= 0).all() and (np.diff(widths) >= 0).all()
        assert rungs[0] == 0 and (np.diff(np.unique(rungs)) == 1).all()
    elif fact == "holds_the_count":
        assert (widths >= counts).all()
        # under a third of a slot row is padding
        assert (3 * (widths - np.maximum(counts, 1)) < widths).all()
    elif fact == "rung_below_is_too_small":
        up = rungs > 0
        assert (_rung_width(rungs[up] - 1) < counts[up]).all()
        assert (counts[~up] <= 1).all() and (widths[~up] == 1).all()
    elif fact == "pow2_or_three_halves":
        w = np.unique(widths)
        pow2 = (w & (w - 1)) == 0
        three = (w % 3 == 0) & (((w // 3) & (w // 3 - 1)) == 0)
        assert (pow2 | three).all()
        assert w[:9].tolist() == [1, 2, 3, 4, 6, 8, 12, 16, 24]
    else:
        assert widths.tolist() == [_ladder_width(c) for c in counts]
        assert _rung_width(np.arange(len(LADDER))).tolist() == LADDER


# ------------------------------------------------------------ layout facts
def test_bell_roundtrip_and_layout(rng):
    X, B = _power_law_sparse(rng)
    d = X.n_features
    perm = np.asarray(B.perm_cols)
    inv = np.asarray(B.inv_perm)
    assert sorted(perm.tolist()) == list(range(d))
    np.testing.assert_array_equal(perm[inv], np.arange(d))
    v = rng.normal(size=d).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(B.to_model_space(B.from_model_space(v))), v)
    # intercept (original last column, in every row) must be hot
    assert B.last_col_pos < B.d_sel
    assert np.asarray(B.dense)[:, B.last_col_pos].min() == 1.0
    # ELL widths are rungs of the width ladder, ascending, no bucket —
    # of rows or of columns — is more than a third padding, and every real
    # tail nnz is laid exactly once: padded slots carry value 0 at column 0
    widths = [v.shape[1] for v in B.ell_vals]
    assert widths == sorted(set(widths)) and set(widths) <= set(LADDER)
    occ = [v.shape[1] for v in B.bucket_vals]
    assert occ == sorted(set(occ)) and set(occ) <= set(LADDER)
    assert {3, 6} <= set(widths) and {3, 6} <= set(occ)
    for v in B.ell_vals + B.bucket_vals:
        v = np.asarray(v)
        assert 3 * int((v == 0.0).sum()) <= v.size
    laid = sum(int((np.asarray(v) != 0.0).sum()) for v in B.ell_vals)
    total = int((np.asarray(X.values) != 0.0).sum())
    # every tail nnz is laid exactly once (tail values are nonzero by
    # construction, padding slots are zero), and the tail is a subset of
    # the matrix's real nnz
    assert laid == B.tail_nnz <= total
    assert B.ell_slots >= B.tail_nnz
    assert B.tail_pad_waste >= 0.0
    # rows are STORED in concatenation order: width rung ascending,
    # tail-free rows last, original row id within a bucket (stable)
    n = X.shape[0]
    ro, rp = np.asarray(B.row_order), np.asarray(B.row_pos)
    assert sorted(ro.tolist()) == list(range(n))
    np.testing.assert_array_equal(rp[ro], np.arange(n))
    hot_cols = set(perm[:B.d_sel].tolist())
    ind, val = np.asarray(X.indices), np.asarray(X.values)
    tail_nnz = np.array([sum(1 for c, v in zip(ind[i], val[i])
                             if v != 0.0 and int(c) not in hot_cols)
                         for i in range(n)])
    assert tail_nnz.min() == 0 and (tail_nnz == 1).any() \
        and tail_nnz.max() > 4            # 0 / 1 / many tail nonzeros
    width = np.where(tail_nnz > 0,
                     [_ladder_width(c) for c in tail_nnz], 1e9)
    stored = width[ro]
    assert (np.diff(stored) >= 0).all()
    assert all((np.diff(ro[stored == w]) > 0).all()
               for w in np.unique(stored))
    assert B.tail_rows == int((tail_nnz > 0).sum()) \
        == sum(v.shape[0] for v in B.ell_vals)
    assert [int((stored == w).sum()) for w in widths] \
        == [v.shape[0] for v in B.ell_vals]


def _storage(B, bf16):
    """B with f32 or bf16 value storage, and the comparison tolerance."""
    if not bf16:
        return B, dict(rtol=2e-4, atol=2e-4)
    return (cast_features(make_batch(B, np.zeros(B.shape[0]))).X,
            dict(rtol=3e-2, atol=0.25))


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16"])
def test_bell_matvec_rmatvec_parity(rng, bf16):
    """`matvec` answers in the CALLER's row order; `layout_matvec` in the
    stored order; the transposed ops take a cotangent in the stored
    order."""
    X, B = _power_law_sparse(rng)
    B, tol = _storage(B, bf16)
    n, d = X.shape
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    r = jnp.asarray(rng.normal(size=n).astype(np.float32))
    z_ref = np.asarray(matvec(X, w))
    np.testing.assert_allclose(
        np.asarray(matvec(B, B.from_model_space(w))), z_ref, **tol)
    np.testing.assert_allclose(
        np.asarray(layout_matvec(B, B.from_model_space(w))),
        z_ref[np.asarray(B.row_order)], **tol)
    np.testing.assert_allclose(
        np.asarray(rows_to_caller(
            B, layout_matvec(B, B.from_model_space(w)))), z_ref, **tol)
    r_st = rows_from_caller(B, r)
    np.testing.assert_array_equal(np.asarray(rows_to_caller(B, r_st)),
                                  np.asarray(r))
    np.testing.assert_allclose(
        np.asarray(B.to_model_space(rmatvec(B, r_st))),
        np.asarray(rmatvec(X, r)), **tol)
    np.testing.assert_allclose(
        np.asarray(B.to_model_space(sq_rmatvec(B, r_st))),
        np.asarray(sq_rmatvec(X, r)), **tol)
    # the caller-order cotangent is NOT what the transposed pass takes:
    # the order matters, and this test can see it
    assert not np.allclose(np.asarray(B.to_model_space(rmatvec(B, r))),
                           np.asarray(rmatvec(X, r)), atol=1e-2)


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16"])
def test_bell_lane_ops_parity(rng, bf16):
    X, B = _power_law_sparse(rng)
    B, tol = _storage(B, bf16)
    n, d = X.shape
    G = 4
    W = jnp.asarray(rng.normal(size=(d, G)).astype(np.float32))
    R = jnp.asarray(rng.normal(size=(n, G)).astype(np.float32))
    perm = jnp.asarray(B.perm_cols)
    inv = np.asarray(B.inv_perm)
    Z_ref = np.asarray(matvec_lanes(X, W))
    np.testing.assert_allclose(
        np.asarray(matvec_lanes(B, W[perm])), Z_ref, **tol)
    np.testing.assert_allclose(
        np.asarray(layout_matvec_lanes(B, W[perm])),
        Z_ref[np.asarray(B.row_order)], **tol)
    np.testing.assert_allclose(
        np.asarray(rmatvec_lanes(B, rows_from_caller(B, R)))[inv],
        np.asarray(rmatvec_lanes(X, R)), **tol)


def test_bell_weighted_gram_parity(rng):
    X, B = _power_law_sparse(rng, n=200, d=120, k=6, d_dense=16)
    r = jnp.asarray(np.abs(rng.normal(size=200)).astype(np.float32))
    inv = np.asarray(B.inv_perm)
    g_ref = np.asarray(weighted_gram(X, r))
    g = np.asarray(weighted_gram(B, rows_from_caller(B, r)))[inv][:, inv]
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-4)


def test_bell_empty_tail(rng):
    # d_dense >= d: everything is hot, no ELL buckets at all — every row
    # is tail-free, so the stored order is the caller's
    X, B = _power_law_sparse(rng, n=100, d=40, k=5, d_dense=64)
    assert B.ell_vals == () and B.tail_nnz == 0 and B.tail_rows == 0
    assert B.tail_pad_waste == 0.0
    np.testing.assert_array_equal(np.asarray(B.row_order), np.arange(100))
    w = jnp.asarray(rng.normal(size=40).astype(np.float32))
    for mv in (matvec, layout_matvec):
        np.testing.assert_allclose(
            np.asarray(mv(B, B.from_model_space(w))),
            np.asarray(matvec(X, w)), rtol=2e-4, atol=2e-4)
    r = jnp.asarray(rng.normal(size=100).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(B.to_model_space(rmatvec(B, r))),
        np.asarray(rmatvec(X, r)), rtol=2e-4, atol=2e-4)


def test_bell_every_row_has_a_tail(rng):
    # one cold column of its own per row on top of the zipf draw: no
    # tail-free rows, so the concatenation carries no zero block
    n, d, k = 120, 400, 4
    col = (rng.zipf(1.5, size=(n, k)).astype(np.int64) - 1) % 100
    own = 200 + np.arange(n)[:, None]
    ind = np.concatenate([col, own], axis=1).astype(np.int32)
    val = rng.normal(size=(n, k + 1)).astype(np.float32)
    order = np.argsort(ind, axis=1, kind="stable")
    si = np.take_along_axis(ind, order, axis=1)
    dup = np.zeros_like(ind, bool)
    np.put_along_axis(dup, order[:, 1:], si[:, 1:] == si[:, :-1], axis=1)
    val[dup] = 0.0
    X = SparseRows(jnp.asarray(ind), jnp.asarray(val), d)
    B = to_blocked_ell(X, 8)
    assert B.tail_rows == n and len(B.ell_vals) >= 2
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    r = jnp.asarray(rng.normal(size=n).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(matvec(B, B.from_model_space(w))),
        np.asarray(matvec(X, w)), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(B.to_model_space(rmatvec(B, rows_from_caller(B, r)))),
        np.asarray(rmatvec(X, r)), rtol=2e-4, atol=2e-4)
    b = pad_batch(make_batch(B, np.asarray(r)), 128)
    z = np.asarray(matvec(b.X, B.from_model_space(w)))
    np.testing.assert_allclose(z[:n], np.asarray(matvec(X, w)), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_array_equal(z[n:], 0.0)


def test_bell_pad_and_cast(rng):
    X, B = _power_law_sparse(rng, n=100, d=300, k=6)
    y = jnp.asarray(rng.normal(size=100).astype(np.float32))
    wts = rng.uniform(0.5, 2.0, size=100).astype(np.float32)
    b = pad_batch(make_batch(B, y, wts), 128)
    assert b.n == 128
    # the batch is in the layout's stored order throughout, padding last
    ro = np.asarray(b.X.row_order)
    np.testing.assert_array_equal(ro[:100], np.asarray(B.row_order))
    np.testing.assert_array_equal(ro[100:], np.arange(100, 128))
    np.testing.assert_array_equal(np.asarray(b.y)[:100], np.asarray(y)[ro[:100]])
    np.testing.assert_array_equal(np.asarray(b.weights)[:100], wts[ro[:100]])
    np.testing.assert_array_equal(np.asarray(b.weights)[100:], 0.0)
    np.testing.assert_array_equal(
        np.asarray(rows_to_caller(b.X, b.y))[:100], np.asarray(y))
    w = jnp.asarray(rng.normal(size=300).astype(np.float32))
    z = np.asarray(matvec(b.X, b.X.from_model_space(w)))
    np.testing.assert_allclose(z[:100], np.asarray(matvec(X, w)), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(z[100:], 0.0, atol=1e-6)
    zl = np.asarray(layout_matvec(b.X, b.X.from_model_space(w)))
    np.testing.assert_allclose(zl[:100], np.asarray(matvec(X, w))[ro[:100]],
                               rtol=2e-4, atol=2e-4)
    r = jnp.asarray(rng.normal(size=128).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(b.X.to_model_space(
            rmatvec(b.X, rows_from_caller(b.X, r)))),
        np.asarray(rmatvec(X, r[:100])), rtol=2e-4, atol=2e-4)
    bc = cast_features(b)
    assert bc.X.dense.dtype == jnp.bfloat16
    assert all(v.dtype == jnp.bfloat16 for v in bc.X.ell_vals)
    assert all(v.dtype == jnp.bfloat16 for v in bc.X.bucket_vals)
    # bf16 multiply / f32 accumulate stays within bf16 quantization noise
    zb = np.asarray(matvec(bc.X, bc.X.from_model_space(w)))
    assert zb.dtype == np.float32
    np.testing.assert_allclose(zb[:100], np.asarray(matvec(X, w)),
                               rtol=2e-2, atol=2e-2)


def test_bell_intercept_detection(rng):
    X, B = _power_law_sparse(rng)
    assert last_column_is_intercept(B)
    # break the intercept: scale one row's intercept value
    va = np.asarray(X.values).copy()
    va[0, -1] = 2.0
    B2 = to_blocked_ell(SparseRows(np.asarray(X.indices), va,
                                   X.n_features), 32)
    assert not last_column_is_intercept(B2)


# ------------------------------------------------------- scipy CSR builder
def test_bell_from_scipy_csr(rng):
    n, d = 120, 90
    M = sp.random(n, d, density=0.08, format="csr", dtype=np.float32,
                  random_state=np.random.RandomState(0))
    B = blocked_ell_from_scipy_csr(M, d_dense=12)
    w = rng.normal(size=d).astype(np.float32)
    ref = M @ w
    got = np.asarray(matvec(B, B.from_model_space(jnp.asarray(w))))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
    r = rng.normal(size=n).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(B.to_model_space(
            rmatvec(B, jnp.asarray(rows_from_caller(B, r))))),
        M.T @ r, rtol=2e-4, atol=2e-4)


def test_from_scipy_csr_warning_reports_mass_fraction():
    M = sp.csr_matrix(np.array([[1.0, 2.0, 3.0, 4.0],
                                [0.0, 0.0, 5.0, 0.0]], np.float32))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        S = from_scipy_csr(M, k=2)
    msgs = [str(w.message) for w in caught
            if "from_scipy_csr" in str(w.message)]
    assert len(msgs) == 1
    # row 0 drops its 2 smallest-|value| entries (1, 2) of total mass 15
    assert "2 smallest-|value| entries" in msgs[0]
    assert "20.0000%" in msgs[0]
    # kept entries are the largest-|value| ones
    kept = np.sort(np.asarray(S.values)[0])
    np.testing.assert_array_equal(kept[-2:], [3.0, 4.0])


def test_from_scipy_csr_strict_raises():
    M = sp.csr_matrix(np.array([[1.0, 2.0, 3.0]], np.float32))
    with pytest.raises(ValueError, match="strict=True.*mass"):
        from_scipy_csr(M, k=2, strict=True)
    # strict with no truncation is a no-op
    S = from_scipy_csr(M, k=3, strict=True)
    assert S.values.shape == (1, 3)


# ---------------------------------------------------------- solver parity
@pytest.mark.parametrize("task", [TaskType.LOGISTIC_REGRESSION,
                                  TaskType.LINEAR_REGRESSION,
                                  TaskType.POISSON_REGRESSION])
def test_bell_train_glm_parity(rng, task):
    X, B = _power_law_sparse(rng, n=400, d=400, k=8, d_dense=24)
    if task is TaskType.LOGISTIC_REGRESSION:
        y = _labels(rng, X)
        rtol = 1e-5
    else:
        # abs-normal responses: a harder-conditioned fit whose two solves
        # stop at slightly different points of the same flat optimum
        y = jnp.asarray(np.abs(rng.normal(size=400)).astype(np.float32))
        rtol = 5e-4
    tol, lam = 1e-6, 0.1
    # max_iters leaves room to CONVERGE (70-170 iterations here): at a cap
    # of 60 neither solve had stopped on its tolerance, and the test
    # compared two truncated paths, which differ by reduction-order noise
    cfg = OptimizerConfig(max_iters=400, tolerance=tol, reg=l2(),
                          reg_weight=lam, history=5)
    # non-constant weights and offsets (logistic; the two regressions'
    # random responses are order-sensitive as they are): a batch whose
    # y / weights / offsets were left in the caller's order would solve
    # another problem
    wts, offs = (_weights_offsets(rng, 400)
                 if task is TaskType.LOGISTIC_REGRESSION else (None, None))
    m_b, r_b = train_glm(make_batch(B, y, wts, offs), task, cfg)
    m_s, r_s = train_glm(make_batch(X, y, wts, offs), task, cfg)
    assert bool(r_b.converged) and bool(r_s.converged)
    # value parity is the tight pin
    np.testing.assert_allclose(float(r_b.value), float(r_s.value), rtol=rtol)
    # ... coefficients follow as far as the stopping rule reaches: a
    # relative-decrease stop at tol leaves a gap of order tol*f, and in a
    # lam-strongly-convex objective a gap g allows a distance
    # sqrt(2g/lam) from the optimum — twice, for two solves. (The chip's
    # verdict on the same comparison: chip_smoke.py's `parity` phase.)
    atol = 2.0 * np.sqrt(2.0 * tol * float(r_s.value) / lam)
    np.testing.assert_allclose(np.asarray(m_b.coefficients.means),
                               np.asarray(m_s.coefficients.means), atol=atol)
    # model scoring translates to permuted space internally
    np.testing.assert_allclose(np.asarray(m_b.score(B)),
                               np.asarray(m_b.score(X)), rtol=2e-4,
                               atol=2e-4)


def test_bell_grid_lanes_parity(rng):
    X, B = _power_law_sparse(rng)
    y = _labels(rng, X)
    cfg = OptimizerConfig(max_iters=60, tolerance=1e-6, reg=l2(),
                          reg_weight=0.0, history=5)
    weights = [1e-1, 1.0, 30.0]
    wts, offs = _weights_offsets(rng, X.shape[0])
    batch_b, batch_s = make_batch(B, y, wts, offs), make_batch(X, y, wts, offs)
    grid_b = train_glm_grid(batch_b, TaskType.LOGISTIC_REGRESSION, cfg,
                            weights)
    grid_s = train_glm_grid(batch_s, TaskType.LOGISTIC_REGRESSION, cfg,
                            weights)
    for (m_b, r_b), (m_s, r_s) in zip(grid_b, grid_s):
        # 3e-4 and 8e-2, not 1e-4 and 3e-2: the cap cuts the 1e-1 lane
        # ~0.45 above its optimum of 385 on both layouts, and two
        # truncated paths agree as far as their reduction orders do — the
        # PARENT's two-loop with its dots summed in another order reads
        # 1.3e-4 and 0.046 here, the carried-products form 1.4e-4 and
        # 0.041 (PERF.md §6, PR 28)
        np.testing.assert_allclose(float(r_b.value), float(r_s.value),
                                   rtol=3e-4)
        np.testing.assert_allclose(np.asarray(m_b.coefficients.means),
                                   np.asarray(m_s.coefficients.means),
                                   atol=8e-2)
    # model selection pairs the batch's margins with the batch's labels:
    # the SAME models must score the same on either representation
    from photon_tpu.models.training import evaluate_glm_grid

    best_b, scores_b = evaluate_glm_grid(grid_s, batch_b)
    best_s, scores_s = evaluate_glm_grid(grid_s, batch_s)
    assert best_b == best_s
    np.testing.assert_allclose(scores_b, scores_s, rtol=1e-5)


# ------------------------------------------- where per-row data crosses
def test_bell_batch_is_in_stored_order_and_with_offsets_translates(rng):
    X, B = _power_law_sparse(rng, n=300, d=200, k=6, d_dense=16)
    y = np.asarray(_labels(rng, X))
    wts, offs = _weights_offsets(rng, 300)
    ro = np.asarray(B.row_order)
    assert (ro != np.arange(300)).any()
    b = make_batch(B, y, wts, offs)
    for got, ref in ((b.y, y), (b.weights, wts), (b.offsets, offs)):
        np.testing.assert_array_equal(np.asarray(got), ref[ro])
    offs2 = rng.normal(size=300).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(with_offsets(b, jnp.asarray(offs2)).offsets), offs2[ro])
    # the objective sees one and the same problem on either representation
    from photon_tpu.ops.objective import Objective

    obj = Objective(TaskType.LOGISTIC_REGRESSION, l2=0.3)
    w = jnp.asarray(rng.normal(size=200).astype(np.float32) * 0.3)
    v_s, g_s = obj.value_and_grad(w, with_offsets(make_batch(X, y, wts),
                                                  offs2))
    v_b, g_b = obj.value_and_grad(B.from_model_space(w),
                                  with_offsets(make_batch(B, y, wts), offs2))
    np.testing.assert_allclose(float(v_b), float(v_s), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(B.to_model_space(g_b)),
                               np.asarray(g_s), rtol=2e-4, atol=2e-4)
    # make_batch on any other X does what it always did
    d = make_batch(np.asarray(rng.normal(size=(5, 3)), np.float32),
                   [0., 1., 0., 1., 1.], offsets=np.arange(5.))
    np.testing.assert_array_equal(np.asarray(d.offsets), np.arange(5.))
    np.testing.assert_array_equal(np.asarray(d.weights), 1.0)


def test_bell_lane_tuner_validation_scores(rng):
    """tuning.lane_tuner._lane_scores pairs the validation batch's margins
    with ITS labels and weights."""
    from photon_tpu.evaluation.evaluator import default_evaluator
    from photon_tpu.tuning.lane_tuner import _lane_scores

    X, B = _power_law_sparse(rng, n=300, d=200, k=6, d_dense=16)
    y = np.asarray(_labels(rng, X))
    wts, offs = _weights_offsets(rng, 300)
    W = jnp.asarray(rng.normal(size=(4, 200)).astype(np.float32) * 0.3)
    ev = default_evaluator(TaskType.LOGISTIC_REGRESSION)
    got = _lane_scores(W, make_batch(B, y, wts, offs), ev, 3)
    ref = _lane_scores(W, make_batch(X, y, wts, offs), ev, 3)
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    assert len(set(np.round(ref, 6))) == 3


def test_bell_game_fixed_effect_with_a_random_effect(rng):
    """A GAME fit whose fixed shard is a BlockedEllRows: the random
    effect's scores reach the fixed effect as caller-ordered offsets, the
    fixed effect's margins go back caller-ordered — the fit and the
    scores match the SparseRows fit."""
    from photon_tpu.game.dataset import GameData
    from photon_tpu.game.estimator import (FixedEffectConfig, GameEstimator,
                                           RandomEffectConfig)
    from photon_tpu.game.scoring import score_game

    n, n_ent = 360, 12
    X, B = _power_law_sparse(rng, n=n, d=150, k=6, d_dense=16)
    ent = rng.integers(0, n_ent, size=n)
    Xr = rng.normal(size=(n, 2)).astype(np.float32)
    w_re = rng.normal(size=(n_ent, 2)) * 1.5
    wf = rng.normal(size=150).astype(np.float32) * 0.5
    logit = np.asarray(matvec(X, jnp.asarray(wf))) \
        + np.einsum("nd,nd->n", Xr, w_re[ent])
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    wts, offs = _weights_offsets(rng, n)
    cfg = OptimizerConfig(max_iters=60, tolerance=1e-7, reg=l2(),
                          reg_weight=1.0)

    def fit(shard):
        data = GameData.build(y, {"f": shard, "r": Xr}, {"e": ent},
                              weights=wts, offsets=offs)
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={
                "fixed": FixedEffectConfig("f", cfg),
                "per_e": RandomEffectConfig("e", "r", cfg)},
            n_sweeps=2, warm_start=False)
        model = est.fit(data)[0].model
        return model, np.asarray(score_game(model, data))

    (m_b, s_b), (m_s, s_s) = fit(B), fit(X)
    np.testing.assert_allclose(
        np.asarray(m_b["fixed"].model.coefficients.means),
        np.asarray(m_s["fixed"].model.coefficients.means), atol=5e-3)
    np.testing.assert_allclose(np.asarray(m_b["per_e"].coefficients),
                               np.asarray(m_s["per_e"].coefficients),
                               atol=5e-3)
    np.testing.assert_allclose(s_b, s_s, atol=2e-2)


def test_bell_streamed_parity(rng):
    """chunk_blocked_ell: the streamed solve over a blocked-ELL chunk
    ladder matches the resident SparseRows solve (one global permutation
    across chunks, translation at the train_glm boundary)."""
    X, _ = _power_law_sparse(rng, n=384, d=150, k=6, d_dense=16)
    y = _labels(rng, X)
    batch = make_batch(X, y)
    cb = chunk_blocked_ell(batch, 128, d_dense=16)
    assert cb.X.permuted and cb.n_chunks == 3
    # uniform chunk shapes: ONE compiled per-chunk program
    shapes = {tuple(v.shape for v in c.ell_vals) for c in cb.X.chunks}
    assert len(shapes) == 1
    cfg = OptimizerConfig(max_iters=40, tolerance=1e-7, reg=l2(),
                          reg_weight=0.3, history=5)
    m_c, r_c = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
    m_s, r_s = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
    np.testing.assert_allclose(float(r_c.value), float(r_s.value), rtol=5e-5)
    # the streamed and resident L-BFGS paths diverge on near-flat sparse
    # directions (chunked accumulation order); value parity is the tight
    # pin, coefficients agree to ~1e-2 absolute
    np.testing.assert_allclose(np.asarray(m_c.coefficients.means),
                               np.asarray(m_s.coefficients.means),
                               rtol=2e-3, atol=1e-2)


@pytest.mark.slow
def test_bell_streamed_owlqn_and_bf16_chunks(rng):
    X, _ = _power_law_sparse(rng, n=256, d=200, k=6, d_dense=16)
    y = _labels(rng, X)
    batch = make_batch(X, y)
    from photon_tpu.optim.config import OptimizerType
    from photon_tpu.optim.regularization import elastic_net

    cfg = OptimizerConfig(max_iters=30, tolerance=1e-7,
                          reg=elastic_net(0.5), reg_weight=1e-2, history=5,
                          optimizer=OptimizerType.OWLQN)
    cb = chunk_blocked_ell(batch, 128, d_dense=16,
                           feature_dtype=jnp.bfloat16)
    assert all(c.dense.dtype == jnp.bfloat16 for c in cb.X.chunks)
    m_c, r_c = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
    m_s, r_s = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
    # bf16 feature storage: value parity within quantization noise
    np.testing.assert_allclose(float(r_c.value), float(r_s.value), rtol=5e-3)


def test_bell_streamed_mesh_rejected(rng, mesh8):
    X, _ = _power_law_sparse(rng, n=160, d=120, k=5, d_dense=8)
    y = _labels(rng, X)
    cb = chunk_blocked_ell(make_batch(X, y), 80, d_dense=8)
    cfg = OptimizerConfig(max_iters=5, tolerance=1e-7, reg=l2(),
                          reg_weight=0.1, history=4)
    with pytest.raises(ValueError, match="mesh"):
        train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg, mesh=mesh8)


def test_bell_single_device_mesh_rejected(rng, mesh8):
    X, B = _power_law_sparse(rng, n=160, d=120, k=5, d_dense=8)
    y = _labels(rng, X)
    cfg = OptimizerConfig(max_iters=5, tolerance=1e-7, reg=l2(),
                          reg_weight=0.1, history=4)
    with pytest.raises(ValueError, match="single-device"):
        train_glm(make_batch(B, y), TaskType.LOGISTIC_REGRESSION, cfg,
                  mesh=mesh8)


# ----------------------------------------------------------- mesh parity
class TestShardedBlockedEll:
    def test_ops_match_single_device(self, rng):
        X, B = _power_law_sparse(rng, n=256, d=300, k=8, d_dense=16)
        S = shard_blocked_ell(SparseRows(np.asarray(X.indices),
                                         np.asarray(X.values),
                                         X.n_features), 8, d_dense=16)
        assert isinstance(S, ShardedBlockedEllRows)
        assert S.n_shards == 8 and S.n_local == 32
        n, d = X.shape
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        r = jnp.asarray(rng.normal(size=n).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(matvec(S, S.from_model_space(w))),
            np.asarray(matvec(X, w)), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(S.to_model_space(rmatvec(S, r))),
            np.asarray(rmatvec(X, r)), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(S.to_model_space(sq_rmatvec(S, r))),
            np.asarray(sq_rmatvec(X, r)), rtol=2e-4, atol=2e-4)
        G = 3
        W = jnp.asarray(rng.normal(size=(d, G)).astype(np.float32))
        R = jnp.asarray(rng.normal(size=(n, G)).astype(np.float32))
        perm = jnp.asarray(S.perm_cols)
        inv = np.asarray(S.inv_perm)
        np.testing.assert_allclose(
            np.asarray(matvec_lanes(S, W[perm])),
            np.asarray(matvec_lanes(X, W)), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(rmatvec_lanes(S, R))[inv],
            np.asarray(rmatvec_lanes(X, R)), rtol=2e-4, atol=2e-4)
        # the local views compose to the global op
        chunk0 = S.chunk(0)
        np.testing.assert_allclose(
            np.asarray(matvec(chunk0, S.from_model_space(w))),
            np.asarray(matvec(X, w))[:32], rtol=2e-4, atol=2e-4)

    def test_train_glm_mesh_matches_single_device(self, rng, mesh8):
        X, _ = _power_law_sparse(rng, n=320, d=300, k=8, d_dense=16)
        y = _labels(rng, X)
        batch = shard_blocked_ell_batch(
            make_batch(SparseRows(np.asarray(X.indices),
                                  np.asarray(X.values), X.n_features),
                       np.asarray(y)), 8, d_dense=16)
        cfg = OptimizerConfig(max_iters=40, tolerance=1e-6, reg=l2(),
                              reg_weight=0.1, history=5)
        m_m, r_m = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                             mesh=mesh8)
        m_s, r_s = train_glm(make_batch(X, y),
                             TaskType.LOGISTIC_REGRESSION, cfg)
        np.testing.assert_allclose(float(r_m.value), float(r_s.value),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(m_m.coefficients.means),
                                   np.asarray(m_s.coefficients.means),
                                   atol=5e-3)

    @pytest.mark.slow
    def test_train_glm_grid_lanes_mesh(self, rng, mesh8):
        X, _ = _power_law_sparse(rng, n=320, d=300, k=8, d_dense=16)
        y = _labels(rng, X)
        batch = shard_blocked_ell_batch(
            make_batch(SparseRows(np.asarray(X.indices),
                                  np.asarray(X.values), X.n_features),
                       np.asarray(y)), 8, d_dense=16)
        cfg = OptimizerConfig(max_iters=40, tolerance=1e-6, reg=l2(),
                              reg_weight=0.0, history=5)
        weights = [0.5, 5.0]
        grid_m = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                                weights, mesh=mesh8)
        grid_s = train_glm_grid(make_batch(X, y),
                                TaskType.LOGISTIC_REGRESSION, cfg, weights)
        for (m_m, r_m), (m_s, r_s) in zip(grid_m, grid_s):
            np.testing.assert_allclose(float(r_m.value), float(r_s.value),
                                       rtol=1e-4)
            np.testing.assert_allclose(np.asarray(m_m.coefficients.means),
                                       np.asarray(m_s.coefficients.means),
                                       atol=2e-2)

    def test_cast_features_bf16(self, rng):
        X, _ = _power_law_sparse(rng, n=64, d=80, k=5, d_dense=8)
        batch = shard_blocked_ell_batch(
            make_batch(SparseRows(np.asarray(X.indices),
                                  np.asarray(X.values), X.n_features),
                       np.zeros(64, np.float32)), 8, d_dense=8)
        bc = cast_features(batch)
        assert bc.X.dense.dtype == jnp.bfloat16
        assert all(v.dtype == jnp.bfloat16 for v in bc.X.ell_vals)
        assert all(v.dtype == jnp.bfloat16 for v in bc.X.bucket_vals)


# ------------------------------------------------- sorted-segment helper
def test_sorted_segment_sum_matches_segment_sum(rng):
    ids = np.sort(rng.integers(0, 17, size=200)).astype(np.int32)
    dat = rng.normal(size=200).astype(np.float32)
    ref = np.asarray(jax.ops.segment_sum(jnp.asarray(dat),
                                         jnp.asarray(ids),
                                         num_segments=17))
    got = np.asarray(sorted_segment_sum(jnp.asarray(dat),
                                        jnp.asarray(ids), 17))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # lane-stacked form
    dat2 = rng.normal(size=(200, 3)).astype(np.float32)
    ref2 = np.asarray(jax.ops.segment_sum(jnp.asarray(dat2),
                                          jnp.asarray(ids),
                                          num_segments=17))
    got2 = np.asarray(sorted_segment_sum(jnp.asarray(dat2),
                                         jnp.asarray(ids), 17))
    np.testing.assert_allclose(got2, ref2, rtol=1e-5, atol=1e-5)


def test_bell_chunked_margins_permuted(rng):
    """models.glm.chunked_margins translates the ladder's global
    permutation once for the whole stream."""
    from photon_tpu.models.glm import chunked_margins

    X, _ = _power_law_sparse(rng, n=200, d=150, k=6, d_dense=8)
    y = np.zeros(200, np.float32)
    cb = chunk_blocked_ell(make_batch(X, y), 64, d_dense=8)
    w = rng.normal(size=150).astype(np.float32)
    got = np.asarray(chunked_margins(cb.X, w))
    ref = np.asarray(matvec(X, jnp.asarray(w)))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# --------------------------------- the wide-bucket matrix against float64
WIDE_ROWS = 51
CROSSED = {3, 6, 12}    # the rungs between the powers of two


def _wide_bucket_coo(n, d, seed):
    """Row i carries (i % 18) + 1 DISTINCT tail columns on top of 2 hot
    ones, so the ELL side spans the widths 1/2/3/4/6/8/12/16/24; the tail
    columns are drawn by a power law, so the occurrence side spans as
    many."""
    rng = np.random.default_rng(seed)
    kmax = 21
    p = 1.0 / np.arange(1, d - 2) ** 0.9
    p /= p.sum()
    ind = np.zeros((n, kmax), np.int32)
    val = np.zeros((n, kmax), np.float32)
    for i in range(n):
        tail = (i % 18) + 1
        ind[i, :2] = (0, 1)
        ind[i, 2:2 + tail] = 2 + rng.choice(d - 3, size=tail, replace=False,
                                            p=p)
        val[i, :2 + tail] = rng.normal(size=2 + tail)
    return ind, val


def _wide_bucket_problem(view="stored", d=160, d_dense=8, seed=0,
                         bf16=False):
    """A blocked-ELL layout whose X passes cross buckets of width 3, 6 and
    12 on BOTH sides, in one of four views: ``stored`` — `to_blocked_ell`
    over 51 rows (which divide nothing), rows in bucket order; ``global``
    — `shard_blocked_ell` over two shards of 51 rows, the plain-jit view;
    ``local`` / ``chunk`` — shard 0 through `.local()` (as `shard_map`
    hands it over: every leaf's shard axis sliced to 1) and shard 1
    through `.chunk(1)`, caller-order rows over the ladder the shards
    share. Returns the layout and the dense float64 matrix of the values
    it STORES (the COO's own for f32 storage, each rounded to bf16 for bf16
    storage), rows in the caller's order, columns in model space."""
    nl = WIDE_ROWS
    n = nl if view == "stored" else 2 * nl
    ind, val = _wide_bucket_coo(n, d, seed)
    rows = slice(0, n)
    if view == "stored":
        X = to_blocked_ell(SparseRows(ind, val, d), d_dense)
    else:
        X = shard_blocked_ell(SparseRows(ind, val, d), 2, d_dense)
        if view == "local":
            X, rows = X.shard_slice(0, 1).local(), slice(0, nl)
        elif view == "chunk":
            X, rows = X.chunk(1), slice(nl, n)
    stored = val
    if bf16:
        bf = jnp.bfloat16
        X = dataclasses.replace(
            X, dense=jnp.asarray(X.dense).astype(bf),
            ell_vals=tuple(jnp.asarray(v).astype(bf) for v in X.ell_vals),
            bucket_vals=tuple(jnp.asarray(v).astype(bf)
                              for v in X.bucket_vals))
        stored = np.asarray(jnp.asarray(val).astype(bf))
    D = np.zeros((n, d), np.float64)
    np.add.at(D, (np.repeat(np.arange(n), ind.shape[1]), ind.ravel()),
              stored.astype(np.float64).ravel())
    return X, D[rows]


@pytest.fixture(scope="module",
                params=[(view, bf16)
                        for view in ("stored", "global", "local", "chunk")
                        for bf16 in (False, True)],
                ids=lambda p: f"{p[0]}-{'bf16' if p[1] else 'f32'}")
def wide_bucket(request):
    view, bf16 = request.param
    X, D = _wide_bucket_problem(view, bf16=bf16)
    assert X.shape[0] == D.shape[0]
    assert CROSSED <= {v.shape[-1] for v in X.ell_vals}
    assert CROSSED <= {v.shape[-1] for v in X.bucket_vals}
    assert (getattr(X, "row_order", None) is not None) == (view == "stored")
    return X, D, bf16


@pytest.mark.parametrize("fn", [matvec, layout_matvec, matvec_lanes,
                                layout_matvec_lanes, rmatvec, rmatvec_lanes,
                                sq_rmatvec], ids=lambda f: f.__name__)
def test_wide_bucket_matrix_against_float64(wide_bucket, fn):
    """Every public X pass of the blocked-ELL layout, over every width
    bucket the ladder makes — the rungs 3, 6 and 12 between the powers of
    two among them, in the ELL row buckets and in the occurrence buckets —
    against a dense float64 numpy product of the SAME stored values built
    from the COO (no layout code): the forward passes in the caller's
    order (`matvec`) and in the stored one (`layout_matvec`, through
    `row_order`), the transposed ones from a stored-order cotangent,
    scalar and lane-minor, f32 and bf16 storage; over `to_blocked_ell`'s
    layout, the sharded layout's global view, and a shard of it as
    `.local()` and as `.chunk(i)` (rows in the caller's order).

    Tolerance, per output element, c·Σ|x||v| over the terms of its sum.
    f32 storage: one rounding a product, one an add, ≤ 51 terms a shard
    (a column's rows) — c = 64·2^-24 a shard. bf16 storage multiplies bf16
    OPERANDS with f32 accumulation (`_matvec`'s recipe), so the reference
    rounds the vector to bf16 as the pass does; a bf16 × bf16 product is
    exact in f32, which leaves the same f32 accumulation and the same c.
    `sq_rmatvec` over bf16 storage is the exception, in its HOT columns
    only: the hot block squares in bf16 and takes the cotangent in bf16
    (two roundings of 2^-8 a term: c = 2^-7 there); its tail squares in
    f32 and keeps the first c."""
    X, D, bf16 = wide_bucket
    n, d = X.shape
    rng = np.random.default_rng(1)
    perm = np.asarray(X.perm_cols)
    order = np.arange(n) if getattr(X, "row_order", None) is None \
        else np.asarray(X.row_order)
    G = 3
    op = fn.__name__
    lanes = op.endswith("_lanes")
    forward = not op.startswith(("rmatvec", "sq_"))
    shape = ((d, G) if forward else (n, G)) if lanes \
        else ((d,) if forward else (n,))
    v = rng.normal(size=shape).astype(np.float32)   # model / caller space

    def seen(a):
        """The vector as the pass's products see it."""
        if bf16 and op != "sq_rmatvec":
            a = np.asarray(jnp.asarray(a).astype(jnp.bfloat16))
        return a.astype(np.float64)

    if forward:
        got = np.asarray(fn(X, jnp.asarray(v[perm])), np.float64)
        want, mass = D @ seen(v), np.abs(D) @ np.abs(v)
        if op.startswith("layout_"):
            want, mass = want[order], mass[order]
    else:
        got = np.asarray(fn(X, jnp.asarray(v[order])), np.float64)
        M = D * D if op == "sq_rmatvec" else D
        want, mass = (M.T @ seen(v))[perm], (np.abs(M).T @ np.abs(v))[perm]
    c = np.full(got.shape[:1], 64 * (n // WIDE_ROWS) * 2.0 ** -24)
    if bf16 and op == "sq_rmatvec":
        c[:X.d_sel] = 2.0 ** -7
    tol = (c * mass.T).T + 1e-30
    assert got.shape == want.shape
    err = np.abs(got - want)
    assert np.all(err <= tol), float(np.max(err / tol))


@pytest.mark.parametrize("sharded", [False, True], ids=["one", "sharded"])
def test_build_counts_its_tail_slots(sharded):
    """Counters `layout.tail_nnz` / `layout.ell_slots` / `layout.occ_slots`
    of a blocked-ELL build are the layout's own: its real tail nonzeros,
    and the slots of its ELL row buckets and of its occurrence buckets
    (summed over the shards of a sharded build)."""
    ind, val = _wide_bucket_coo(2 * WIDE_ROWS, 160, seed=3)
    rows = SparseRows(ind, val, 160)
    with telemetry.run("t") as run:
        X = shard_blocked_ell(rows, 2, 8) if sharded \
            else to_blocked_ell(rows, 8)
        c = run.report_compact()["counters"]
    assert c["layout.tail_nnz"] == X.tail_nnz > 0
    assert c["layout.ell_slots"] == X.ell_slots \
        == sum(int(np.prod(v.shape)) for v in X.ell_pcols)
    assert c["layout.occ_slots"] \
        == sum(int(np.prod(v.shape)) for v in X.bucket_rows)
    real = sum(int((np.asarray(v) != 0.0).sum()) for v in X.ell_vals)
    assert real == X.tail_nnz \
        == sum(int((np.asarray(v) != 0.0).sum()) for v in X.bucket_vals)
    assert X.tail_pad_waste == c["layout.ell_slots"] / real - 1.0
    if not sharded:   # its own ladder: under a third of the slots
        assert 3 * (c["layout.ell_slots"] - real) < c["layout.ell_slots"]
        assert 3 * (c["layout.occ_slots"] - real) < c["layout.occ_slots"]
