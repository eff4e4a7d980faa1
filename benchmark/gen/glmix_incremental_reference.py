"""Plain references for the incremental GLMix cell: one entity's logistic
problem under a Gaussian prior, its Newton optimum, and the diagonal of the
inverse Hessian — numpy float64 on the host, no vmap, no buckets, no code
of the program under test.

The prior objective is Photon-ML's incremental-training objective as this
repo states it (`ops.objective.Objective`, `optim.prior`): Σ_i log(1 +
e^{z_i}) − y_i·z_i + ½·Σ_j (l2 + τ_j)·(w_j − μ_j)², z = offsets + X·w,
with μ the previous model's coefficients and τ_j = 1 / its variance (0
where it has none). Every intercept is regularized, as the configuration's
coordinates are.
"""
from __future__ import annotations

import numpy as np

GRAD_TOL = 1e-7     # Newton stops at this gradient norm
MAX_STEPS = 50
ROW_BLOCK = 1 << 17  # rows a float64 Gram pass holds at a time


def _exact(a):
    return a


def objective(X, y, offsets, w, l2: float, mu, tau) -> float:
    z = offsets + X @ w
    dw = w - mu
    return float(np.sum(np.logaddexp(0.0, z) - y * z)
                 + 0.5 * np.sum((l2 + tau) * dw * dw))


def gradient(X, y, offsets, w, l2: float, mu, tau) -> np.ndarray:
    z = offsets + X @ w
    return X.T @ (1.0 / (1.0 + np.exp(-z)) - y) + (l2 + tau) * (w - mu)


def _curvature(X, y, offsets, w) -> np.ndarray:
    p = 1.0 / (1.0 + np.exp(-(offsets + X @ w)))
    return p * (1.0 - p)


def newton(X, y, offsets, l2: float, mu, tau) -> tuple:
    """(w*, objective at w*): damped Newton from w = μ to a gradient norm
    of `GRAD_TOL`; the Hessian Aᵀ·A + D (D = diag(l2 + τ) > 0) is solved in
    the rows where there are fewer rows than columns (Woodbury)."""
    D = l2 + np.asarray(tau, np.float64)
    w = np.array(mu, np.float64)
    f = objective(X, y, offsets, w, l2, mu, tau)
    for _ in range(MAX_STEPS):
        g = gradient(X, y, offsets, w, l2, mu, tau)
        if np.linalg.norm(g) <= GRAD_TOL:
            return w, f
        A = X * np.sqrt(_curvature(X, y, offsets, w))[:, None]
        if A.shape[0] < A.shape[1]:
            AD = A / D
            d = g / D - AD.T @ np.linalg.solve(
                np.eye(A.shape[0]) + AD @ A.T, AD @ g)
        else:
            d = np.linalg.solve(A.T @ A + np.diag(D), g)
        t = 1.0
        while True:
            f_new = objective(X, y, offsets, w - t * d, l2, mu, tau)
            if f_new <= f or t < 1e-8:
                break
            t *= 0.5
        w, f = w - t * d, f_new
    raise RuntimeError(f"Newton did not reach {GRAD_TOL} in {MAX_STEPS} "
                       f"steps (gradient norm {np.linalg.norm(g):.3g})")


def hessian(X, y, offsets, w, l2: float, tau, rd=_exact) -> np.ndarray:
    """Xᵀ·diag(p(1−p))·X + diag(l2 + τ) at ``w``, in row blocks; ``rd``
    rounds both operands of the Gram product (`glmix_reference.bf16`: the
    product at the precision below the configuration's float32)."""
    d = X.shape[1]
    H = np.diag(l2 + np.broadcast_to(np.asarray(tau, np.float64), (d,)))
    for lo in range(0, X.shape[0], ROW_BLOCK):
        Xb = np.asarray(X[lo:lo + ROW_BLOCK], np.float64)
        r = _curvature(Xb, y[lo:lo + ROW_BLOCK], offsets[lo:lo + ROW_BLOCK],
                       w)
        H += rd(Xb * r[:, None]).T @ rd(Xb)
    return H


def full_variances(H) -> np.ndarray:
    """diag(H⁻¹): VarianceComputationType.FULL."""
    return np.diag(np.linalg.inv(H))


def simple_variances(H) -> np.ndarray:
    """1 / diag(H): VarianceComputationType.SIMPLE, the control."""
    return 1.0 / np.diag(H)


def prior_precision(variances) -> np.ndarray:
    """τ = 1 / variance where the previous model estimated one, else 0 (no
    prior): `PriorDistribution.from_variances`."""
    var = np.asarray(variances, np.float64)
    return np.where(var > 0.0, 1.0 / np.maximum(var, 1e-12), 0.0)
