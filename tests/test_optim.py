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


# ------------------------------------------------- the carried-Gram history
# The direction is computed on coefficients over carried inner products
# (optim.lbfgs.History / optim.lane_lbfgs.LaneHistory). The plain two-loop
# recursion over the STORED vectors, in float64 numpy, stays here as the
# reference for every form of it.

_M, _D, _LANES = 5, 48, 3


def _two_loop_reference(pairs, v):
    """pairs: oldest → newest (s, y, sᵀy, yᵀy), the vectors as stored and
    the steering products of the pair as given."""
    q = np.asarray(v, np.float64).copy()
    alphas = []
    for s, y, sy, _ in reversed(pairs):
        a = (s @ q) / max(sy, 1e-20)
        q -= a * y
        alphas.append(a)
    r = q * (pairs[-1][2] / max(pairs[-1][3], 1e-20) if pairs else 1.0)
    for (s, y, sy, _), a in zip(pairs, reversed(alphas)):
        r += (a - (y @ r) / max(sy, 1e-20)) * s
    return r


def _history_events(scenario):
    """Per push: (s, y, accept, v) with s, y, v (LANES, D) f32 and accept
    (LANES,) bool — the lane solvers' own veto; a pair may also fail
    curvature. ``v`` is the vector the next direction is of."""
    rng = np.random.default_rng(7)
    if scenario == "illcond":
        return _illconditioned_events(rng), rng
    B = rng.normal(size=(_D, _D))
    A = B @ B.T / _D + 0.5 * np.eye(_D)  # condition number ~ 10
    n = {"empty": 0, "partial": 3, "skipped": 8, "holes": 9,
         "stalled": 26}.get(scenario)
    if n is None:  # "rot<r>": the write slot ends at r
        n = _M + int(scenario[3:])
    events = []
    for k in range(n):
        s = rng.normal(size=(_LANES, _D)) * 0.3
        y = s @ A
        accept = np.ones(_LANES, bool)
        if scenario == "skipped" and k in (3, 5):
            y = -y  # negative curvature in every lane: the push is skipped
        if scenario == "holes":
            if k in (2, 6):
                y[1] = -y[1]
            if k in (0, 4, 5):
                accept[2] = False
                s[2], y[2] = 0.0, 0.0  # a lane that did not step
        if scenario == "stalled" and k >= 6:
            s[0], y[0] = 0.0, 0.0  # GLMix's stalled lane: s = 0, 20 times
        v = rng.normal(size=(_LANES, _D))
        events.append((s.astype(np.float32), y.astype(np.float32), accept,
                       v.astype(np.float32)))
    return events, rng


def _illconditioned_events(rng, cond=1e4, n=40):
    """The pairs and gradients of a real L-BFGS run (float64, exact line
    search) on a quadratic of condition number 1e4, scaled so |s| is about
    1e-4: every stored vector nearly parallel to the next gradient, where
    the coefficient recursion's differences of carried products cancel
    most — the case that would show an error growing with conditioning."""
    Q, _ = np.linalg.qr(rng.normal(size=(_D, _D)))
    A = (Q * np.logspace(0.0, np.log10(cond), _D)) @ Q.T
    per_lane = []
    for _ in range(_LANES):
        b = 2e-3 * rng.normal(size=_D)
        w, g, pairs, run = np.zeros(_D), -b, [], []
        for _ in range(n):
            d = -_two_loop_reference(
                [(s, y, s @ y, y @ y) for s, y in pairs], g)
            a = -(g @ d) / (d @ A @ d)
            s, y = a * d, a * (A @ d)
            w, g = w + s, g + y
            pairs = (pairs + [(s, y)])[-_M:]
            run.append((s, y, g))
        per_lane.append(run)
    f32 = np.float32
    return [tuple(np.stack([lane[k][j] for lane in per_lane]).astype(f32)
                  for j in (0, 1)) + (np.ones(_LANES, bool),
            np.stack([lane[k][2] for lane in per_lane]).astype(f32))
            for k in range(n)]


def _stored(x, dtype):
    return np.asarray(jnp.asarray(x).astype(dtype).astype(jnp.float32),
                      np.float64)


def _accepted(s, y, accept):
    sy, yy = float(s.astype(np.float64) @ y), float(y.astype(np.float64) @ y)
    return bool(accept) and sy > 1e-10 * max(yy, 1e-20), sy, yy


@pytest.fixture(scope="module")
def history_forms():
    from photon_tpu.optim import lane_lbfgs, lbfgs

    return {
        "scalar": (jax.jit(lbfgs._push), jax.jit(lbfgs.two_loop)),
        "vmap": (jax.jit(jax.vmap(lbfgs._push)),
                 jax.jit(jax.vmap(lbfgs.two_loop))),
        "lanes": (jax.jit(lane_lbfgs._push_lanes),
                  jax.jit(lane_lbfgs.two_loop_lanes)),
    }


@pytest.mark.parametrize("scenario", [
    "empty", "partial", "rot0", "rot1", "rot2", "rot3", "rot4", "skipped",
    "holes", "stalled", "illcond"])
@pytest.mark.parametrize("form", ["scalar", "scalar-tiled", "vmap",
                                  "lanes-f32", "lanes-bf16"])
def test_direction_matches_plain_two_loop(history_forms, form, scenario,
                                          monkeypatch):
    """After every push the new direction equals the float64 two-loop over
    the stored vectors, and the carried products equal the products
    recomputed from the stored slots.

    atol 2e-5 of the direction's largest entry. Measured against the
    float64 reference, the coefficient recursion and the SAME recursion on
    f32 vectors err alike and both grow with conditioning — 2e-7 / 1e-7
    of the direction's norm at condition number 10, 1.5e-6 / 1.5e-6 at
    1e4 ("illcond": a real run's pairs and gradients, |s| ~ 1e-4), 1e-5 /
    9e-6 at 1e6 — so 2e-5 holds every case here with room for the
    reduction order, and a wrong slot order, mask, gamma or Gram entry is
    off by percents."""
    from photon_tpu.optim import lane_lbfgs, lbfgs

    events, rng = _history_events(scenario)
    lanes = form.startswith("lanes")
    hdtype = jnp.bfloat16 if form == "lanes-bf16" else jnp.float32
    if form == "scalar-tiled":  # the large-d layout, at a width not of 128
        monkeypatch.setattr(lbfgs, "_TILED_FROM", _D)
        form = "scalar"
    push, direction = history_forms["lanes" if lanes else form]
    L = 1 if form == "scalar" else _LANES
    v = rng.normal(size=(_LANES, _D)).astype(np.float32)
    if form == "scalar":
        h = lbfgs.empty_history(_M, _D, jnp.float32)
        assert h.S.ndim == (3 if lbfgs._TILED_FROM == _D else 2)
    elif form == "vmap":
        h = jax.vmap(lambda _: lbfgs.empty_history(_M, _D, jnp.float32))(
            jnp.arange(L))
    else:
        h = lane_lbfgs.empty_lane_history(_M, _D, L, hdtype)
    # the reference's own bookkeeping, per lane: the scalar form keeps the
    # last m ACCEPTED pairs; the lane form rotates one global slot and a
    # lane that does not take it leaves a hole
    kept = [[] for _ in range(L)]

    def check():
        if form == "scalar":
            got = np.asarray(direction(h, jnp.asarray(v[0])))[None]
        elif form == "vmap":
            got = np.asarray(direction(h, jnp.asarray(v)))
        else:
            got = np.asarray(direction(h, jnp.asarray(v.T))).T
        for lane in range(L):
            pairs = [p for p in kept[lane] if p is not None]
            want = _two_loop_reference(pairs, v[lane])
            np.testing.assert_allclose(
                got[lane], want, rtol=0, atol=2e-5 * np.abs(want).max(),
                err_msg=f"lane {lane} after {len(kept[lane])} pushes")
        # carried products (flat, recency order: entry [i, k] at i·m + k,
        # index i the pair i pushes back, slot idx − 1 − i) against the
        # slots as stored; row m of the scalar form's S, Y is the unread one
        S, Y = np.asarray(h.S, np.float64), np.asarray(h.Y, np.float64)
        if form == "scalar":  # flat or tiled
            S, Y = (A.reshape(_M + 1, -1)[None, :_M, :_D] for A in (S, Y))
        elif form == "vmap":
            S, Y = S[:, :_M], Y[:, :_M]
        else:
            S, Y = np.moveaxis(S, 2, 0), np.moveaxis(Y, 2, 0)
        S, Y = S.copy(), Y.copy()
        idx = np.broadcast_to(np.asarray(h.idx), (L,))
        for lane in range(L):
            order = (idx[lane] - 1 - np.arange(_M)) % _M
            S[lane], Y[lane] = S[lane][order], Y[lane][order]

        def lane_first(x):
            x = np.asarray(x)
            return (np.moveaxis(x, -1, 0) if lanes
                    else x[None] if form == "scalar" else x)

        v64 = v[:L].astype(np.float64)[:, None]
        for name, got_blk, A, B in (("sy", h.sy, S, Y), ("yy", h.yy, Y, Y),
                                    ("sv", h.sv, S, v64), ("yv", h.yv, Y, v64)):
            # to f32 rounding of the factors' sizes, not of the product's:
            # a real run's newest step is orthogonal to its new gradient
            size = (np.linalg.norm(A, axis=2).max()
                    * np.linalg.norm(B, axis=2).max())
            np.testing.assert_allclose(
                lane_first(got_blk).reshape(L, -1),
                np.einsum("lad,lbd->lab", A, B).reshape(L, -1),
                rtol=0, atol=2e-5 * max(size, 1e-30), err_msg=name)

    check()
    for s, y, accept, v in events:
        for lane in range(L):
            ok, sy, yy = _accepted(s[lane], y[lane], accept[lane])
            pair = (_stored(s[lane], hdtype), _stored(y[lane], hdtype),
                    sy, yy) if ok else None
            if lanes or ok:  # a lane-form veto leaves a hole (None)
                kept[lane] = (kept[lane] + [pair])[-_M:]
        if form == "scalar":
            h = push(h, jnp.asarray(s[0]), jnp.asarray(y[0]),
                     jnp.asarray(v[0]))
        elif form == "vmap":
            h = push(h, jnp.asarray(s), jnp.asarray(y), jnp.asarray(v))
        else:
            h = push(h, jnp.asarray(s.T), jnp.asarray(y.T),
                     jnp.asarray(accept), jnp.asarray(v.T))
        check()
    if scenario.startswith("rot") and form != "vmap":
        assert int(h.idx) == int(scenario[3:]) % _M
    if scenario == "holes" and form == "vmap":
        assert len(set(np.asarray(h.idx).tolist())) > 1  # per-lane idx


_COUPLED_SOLVERS = ["lbfgs", "lbfgs_margin", "owlqn", "lanes", "lanes_owlqn",
                    "streamed", "streamed_owlqn"]


@pytest.mark.parametrize("solver", _COUPLED_SOLVERS)
def test_direction_is_of_the_vector_the_push_was_given(solver, rng,
                                                       monkeypatch):
    """`two_loop(h, v)` / `two_loop_lanes(h, v)` are only right for the
    ``v`` the history's last push was given (``h.sv`` / ``h.yv`` are
    products with THAT vector) and nothing in their signature can enforce
    it. So every solver is run eagerly with the four functions recording:
    each direction call must be handed the vector of the push that made
    the history it is handed."""
    import dataclasses

    from photon_tpu.data.dataset import chunk_batch, make_batch
    from photon_tpu.models.training import train_glm, train_glm_grid
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim import (lane_lbfgs, lane_owlqn, lbfgs, owlqn,
                                  regularization as reg, streamed)
    from photon_tpu.optim.config import OptimizerConfig

    pushed = {}  # id of a history's sv buffer -> the v it was pushed with
    calls = {"push": 0, "direction": 0}

    def recording_push(fn, v_at):
        def push(*args):
            h = fn(*args)
            pushed[id(h.sv)] = (h.sv, np.asarray(args[v_at]))
            calls["push"] += 1
            return h
        return push

    def recording_direction(fn, has_pairs):
        def direction(h, v):
            if bool(np.any(has_pairs(h))):
                kept, v_pushed = pushed[id(h.sv)]
                assert kept is h.sv
                np.testing.assert_array_equal(np.asarray(v), v_pushed)
                calls["direction"] += 1
            return fn(h, v)
        return direction

    scalar = lambda h: h.count > 0
    lane = lambda h: h.valid
    push, push_lanes = (recording_push(lbfgs._push, 3),
                        recording_push(lane_lbfgs._push_lanes, 4))
    two, two_lanes = (recording_direction(lbfgs.two_loop, scalar),
                      recording_direction(lane_lbfgs.two_loop_lanes, lane))
    for mod, name, fn in (
            (lbfgs, "_push", push), (owlqn, "_push", push),
            (streamed, "_push_history", push),
            (lbfgs, "two_loop", two), (owlqn, "two_loop", two),
            (streamed, "two_loop", two),
            (lane_lbfgs, "_push_lanes", push_lanes),
            (lane_owlqn, "_push_lanes", push_lanes),
            (lane_lbfgs, "two_loop_lanes", two_lanes),
            (lane_owlqn, "two_loop_lanes", two_lanes)):
        monkeypatch.setattr(mod, name, fn)

    X, y, vg, _ = _logistic_problem(rng, n=60, d=5)
    task = TaskType.LOGISTIC_REGRESSION
    l1 = solver.endswith("owlqn")
    cfg = OptimizerConfig(max_iters=4, tolerance=0.0, history=2,
                          reg=reg.l1() if l1 else reg.l2(),
                          reg_weight=0.05 if l1 else 0.5)
    with jax.disable_jit():
        if solver == "lbfgs":
            minimize_lbfgs(vg, jnp.zeros(5), max_iters=4, tolerance=0.0,
                           history=2)
        elif solver in ("lbfgs_margin", "owlqn"):
            train_glm(make_batch(X, y), task, cfg)
        elif solver.startswith("lanes"):
            train_glm_grid(make_batch(X, y), task,
                           dataclasses.replace(cfg, reg_weight=0.0),
                           [0.05, 0.5])
        else:
            train_glm(chunk_batch(make_batch(X, y), 20), task, cfg)
    assert calls["push"] == 4 and calls["direction"] == 3, calls
