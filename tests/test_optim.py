"""Optimizer convergence tests.

Mirrors the reference's optimization suite (LBFGSTest, OWLQNTest, TRONTest:
convergence on convex problems, agreement between optimizers, L1 sparsity).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.optim.lbfgs import minimize_lbfgs
from photon_tpu.optim.owlqn import minimize_owlqn
from photon_tpu.optim.tron import minimize_tron


def _logistic_problem(rng, n=500, d=15, seed_scale=0.5):
    X = rng.normal(size=(n, d)).astype(np.float32)
    wt = (rng.normal(size=d) * seed_scale).astype(np.float32)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-X @ wt))).astype(np.float32)
    Xj, yj = jnp.asarray(X), jnp.asarray(y)

    def vg(w):
        z = Xj @ w
        return (
            jnp.sum(jax.nn.softplus(z) - yj * z),
            Xj.T @ (jax.nn.sigmoid(z) - yj),
        )

    def hvp(w, v):
        s = jax.nn.sigmoid(Xj @ w)
        return Xj.T @ (s * (1 - s) * (Xj @ v))

    return X, y, vg, hvp


def test_lbfgs_quadratic():
    A = jnp.diag(jnp.array([1.0, 10.0, 100.0], jnp.float32))
    b = jnp.array([1.0, 2.0, 3.0], jnp.float32)
    vg = jax.value_and_grad(lambda w: 0.5 * w @ A @ w - b @ w)
    res = minimize_lbfgs(vg, jnp.zeros(3), max_iters=60, tolerance=1e-9)
    np.testing.assert_allclose(res.w, [1.0, 0.2, 0.03], atol=1e-3)
    assert bool(res.converged)


def test_lbfgs_rosenbrock():
    def rosen(w):
        return jnp.sum(100.0 * (w[1:] - w[:-1] ** 2) ** 2 + (1.0 - w[:-1]) ** 2)

    res = minimize_lbfgs(jax.value_and_grad(rosen), jnp.zeros(6),
                         max_iters=300, tolerance=1e-10)
    np.testing.assert_allclose(res.w, np.ones(6), atol=1e-3)


def test_lbfgs_matches_sklearn_l2_logistic(rng):
    from sklearn.linear_model import LogisticRegression

    X, y, vg, _ = _logistic_problem(rng)
    lam = 1.0

    def vg_l2(w):
        f, g = vg(w)
        return f + 0.5 * lam * w @ w, g + lam * w

    res = minimize_lbfgs(vg_l2, jnp.zeros(X.shape[1]), max_iters=300)
    sk = LogisticRegression(C=1.0 / lam, fit_intercept=False, tol=1e-10,
                            max_iter=5000).fit(X, y)
    np.testing.assert_allclose(res.w, sk.coef_[0], atol=2e-3)


def test_tron_matches_lbfgs(rng):
    X, y, vg, hvp = _logistic_problem(rng)
    lam = 0.5

    def vg_l2(w):
        f, g = vg(w)
        return f + 0.5 * lam * w @ w, g + lam * w

    def hvp_l2(w, v):
        return hvp(w, v) + lam * v

    rl = minimize_lbfgs(vg_l2, jnp.zeros(X.shape[1]), max_iters=300)
    rt = minimize_tron(vg_l2, hvp_l2, jnp.zeros(X.shape[1]), max_iters=100)
    assert bool(rt.converged)
    np.testing.assert_allclose(rt.w, rl.w, atol=2e-3)


def test_owlqn_matches_sklearn_l1(rng):
    from sklearn.linear_model import LogisticRegression

    X, y, vg, _ = _logistic_problem(rng, n=400, d=20)
    lam = 10.0
    res = minimize_owlqn(vg, jnp.zeros(20), lam, max_iters=300)
    # Pure-L1 baseline, spelled per sklearn version: before 1.8,
    # penalty="l1" is the ONLY way to get L1 out of liblinear
    # (l1_ratio is silently ignored there and the fit is L2 — the
    # baseline objective then lands ~7 units above the true L1 optimum);
    # penalty= is deprecated in 1.8 and removed in 1.10, where
    # l1_ratio=1.0 takes over.
    import sklearn

    if tuple(int(v) for v in sklearn.__version__.split(".")[:2]) >= (1, 8):
        kw = {"l1_ratio": 1.0}
    else:
        kw = {"penalty": "l1"}
    sk = LogisticRegression(C=1.0 / lam,
                            solver="liblinear", fit_intercept=False,
                            tol=1e-9, max_iter=3000, **kw).fit(X, y)
    wsk = sk.coef_[0]

    def F(w):
        z = X @ w
        return np.sum(np.logaddexp(0, z) - y * z) + lam * np.abs(w).sum()

    # Two-sided: our objective matches the sklearn optimum (within f32 noise),
    # not merely "no worse" — guards against the baseline silently degrading.
    assert abs(float(res.value) - F(wsk)) <= 1e-2 * max(1.0, F(wsk))
    # And produce a genuinely sparse solution.
    assert int((np.asarray(res.w) != 0).sum()) < 20


def test_owlqn_zero_l1_matches_lbfgs(rng):
    X, y, vg, _ = _logistic_problem(rng, n=300, d=10)

    def vg_l2(w):
        f, g = vg(w)
        return f + 0.5 * w @ w, g + w

    r0 = minimize_owlqn(vg_l2, jnp.zeros(10), 0.0, max_iters=200)
    r1 = minimize_lbfgs(vg_l2, jnp.zeros(10), max_iters=200)
    np.testing.assert_allclose(r0.w, r1.w, atol=2e-3)


def test_vmapped_lbfgs(rng):
    """The random-effect pattern: many independent solves under one vmap."""
    A = jnp.diag(jnp.array([1.0, 5.0, 25.0], jnp.float32))
    bs = jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32))

    def solve(b):
        vg = jax.value_and_grad(lambda w: 0.5 * w @ A @ w - b @ w)
        return minimize_lbfgs(vg, jnp.zeros(3), max_iters=60, tolerance=1e-8).w

    ws = jax.jit(jax.vmap(solve))(bs)
    exact = np.asarray(bs) / np.array([1.0, 5.0, 25.0])
    np.testing.assert_allclose(ws, exact, atol=2e-3)


def test_loss_history_tracking():
    A = jnp.diag(jnp.array([1.0, 10.0], jnp.float32))
    b = jnp.array([1.0, 1.0], jnp.float32)
    vg = jax.value_and_grad(lambda w: 0.5 * w @ A @ w - b @ w)
    res = minimize_lbfgs(vg, jnp.zeros(2), max_iters=50)
    h = res.history()
    assert len(h) == int(res.iterations) + 1
    assert h[-1] <= h[0]


def test_line_search_failure_reports_failed_not_converged():
    """A non-descending objective (grad lies) must end as failed, not
    converged — the reference distinguishes Breeze line-search failure
    from convergence (ADVICE r1, medium)."""
    import jax.numpy as jnp

    def lying_vg(w):
        # f increases along the claimed descent direction.
        return jnp.sum(jnp.abs(w)), jnp.ones_like(w)

    res = minimize_lbfgs(lying_vg, jnp.zeros(3), max_iters=20)
    assert bool(res.failed)
    assert not bool(res.converged)


def test_grad_norm_history_tracking():
    A = jnp.diag(jnp.array([1.0, 10.0], jnp.float32))
    b = jnp.array([1.0, 1.0], jnp.float32)
    vg = jax.value_and_grad(lambda w: 0.5 * w @ A @ w - b @ w)
    res = minimize_lbfgs(vg, jnp.zeros(2), max_iters=50)
    gh = res.grad_history()
    assert len(gh) == int(res.iterations) + 1
    assert gh[-1] < gh[0]


def test_tron_nan_region_shrinks_not_grows():
    """A trial point landing where f is NaN must shrink the trust region
    (a NaN rho compares False to every threshold and would otherwise grow
    it forever, silently stalling with failed=False)."""
    def vg(w):
        sq = jnp.sum(w * w)
        f = -jnp.log(1.0 - sq) + 10.0 * jnp.sum(w)
        g = 2.0 * w / (1.0 - sq) + 10.0
        return f, g

    def hvp(w, v):
        return jax.jvp(lambda u: vg(u)[1], (w,), (v,))[1]

    res = minimize_tron(vg, hvp, jnp.zeros(2), max_iters=60)
    # Must make real progress into the interior (true min has f < -5).
    assert np.isfinite(float(res.value)) and float(res.value) < -5.0
    assert not bool(res.failed)


# ---------------------------------------------------- tolerance 0: fixed depth
def _stalling_problem(rng):
    """A small, well-conditioned L2 logistic problem: every solver is at
    f32 resolution long before 60 iterations."""
    X, y, vg0, hvp0 = _logistic_problem(rng, n=64, d=6)

    def vg(w):
        f, g = vg0(w)
        return f + 0.5 * 5.0 * w @ w, g + 5.0 * w

    return vg, lambda w, v: hvp0(w, v) + 5.0 * v


def _solve_with(solver: str, vg, hvp, max_iters: int, tolerance: float):
    w0 = jnp.zeros(6, jnp.float32)
    if solver == "lbfgs":
        return minimize_lbfgs(vg, w0, max_iters=max_iters,
                              tolerance=tolerance)
    if solver == "owlqn":
        return minimize_owlqn(vg, w0, 0.01, max_iters=max_iters,
                              tolerance=tolerance)
    return minimize_tron(vg, hvp, w0, max_iters=max_iters,
                         tolerance=tolerance)


@pytest.mark.parametrize("solver", ["lbfgs", "owlqn", "tron"])
def test_tolerance_zero_is_a_fixed_depth(rng, solver):
    """`optim.config.stop_state`, every solver alike: at ``tolerance`` 0 a
    solve that stalls at f32 resolution runs its whole ``max_iters``,
    repeats its last point (the same objective a deeper budget ends at,
    nothing non-finite), counts the repeats in ``iterations``, and ends
    converged and not failed; a positive tolerance stops at the first
    stop."""
    vg, hvp = _stalling_problem(rng)
    early = _solve_with(solver, vg, hvp, 60, 1e-7)
    assert int(early.iterations) < 60 and bool(early.converged)
    fixed = _solve_with(solver, vg, hvp, 60, 0.0)
    deeper = _solve_with(solver, vg, hvp, 90, 0.0)
    assert int(fixed.iterations) == 60 and int(deeper.iterations) == 90
    for res in (fixed, deeper):
        assert bool(res.converged) and not bool(res.failed)
        assert np.all(np.isfinite(np.asarray(res.w)))
        assert np.all(np.isfinite(np.asarray(res.loss_history)))
    hist = np.asarray(fixed.loss_history)
    assert np.all(np.diff(hist) <= 0.0)           # never a worse point
    assert np.all(hist[40:] == hist[-1])          # the stall: repeats
    np.testing.assert_allclose(float(fixed.value), float(deeper.value),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fixed.w), np.asarray(deeper.w),
                               atol=1e-5)
    np.testing.assert_allclose(float(fixed.value), float(early.value),
                               rtol=1e-5)


def test_tolerance_zero_lanes_step_together_under_vmap(rng):
    """Under `vmap` every lane of a fixed-depth solve takes ``max_iters``
    steps, whichever lane stalls first."""
    vg, _ = _stalling_problem(rng)
    scales = jnp.asarray([0.5, 1.0, 4.0], jnp.float32)

    def one(scale):
        return minimize_lbfgs(lambda w: vg(w * scale), jnp.zeros(6),
                              max_iters=30, tolerance=0.0)

    res = jax.vmap(one)(scales)
    assert np.asarray(res.iterations).tolist() == [30, 30, 30]
    assert np.asarray(res.converged).all() and not np.asarray(
        res.failed).any()
