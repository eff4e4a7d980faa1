"""Gaussian-process surrogate for Bayesian hyperparameter search.

Reference parity: com.linkedin.photon.ml.hyperparameter.estimators.
{GaussianProcessEstimator, GaussianProcessModel} and kernels.{RBF, Matern52,
StationaryKernel}. The reference fits a GP to (hyperparameter → validation
metric) observations, sampling kernel hyperparameters; here kernel
hyperparameters (log amplitude, log lengthscales, log noise) are fitted by
maximizing the exact log marginal likelihood with the in-house L-BFGS — the
whole fit is one jit'd program over (n, n) matrices (n = observations,
tiny: ≤ hundreds).

All inputs are assumed pre-scaled to [0, 1]^d (search.py handles ranges and
log-scaling), matching the reference's normalized search space.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.analysis.rules import TraceSignatureLog
from photon_tpu.data.matrix import next_pow2
from photon_tpu.optim.lbfgs import minimize_lbfgs


def _host_cpu():
    """The GP surrogate is DRIVER-side math over tiny (n≤hundreds) matrices
    (the reference fits it on the Spark driver too), so it runs on the
    host CPU backend by design: its eager primitives and per-rung
    re-traces are host work that should neither queue behind the chip's
    training programs nor pay a device launch per tiny op. A process
    without a CPU backend (JAX_PLATFORMS excluding ``cpu``) raises here."""
    return jax.devices("cpu")[0]


JITTER = 1e-6
# f32 Cholesky of a near-noiseless kernel Gram goes unstable; floor the
# fitted noise at NOISE_FLOOR × amplitude (y is standardized, so this is a
# ~1% noise floor — still effectively interpolating).
NOISE_FLOOR = 1e-4

# Pow2 observation-history ladder: a tuning run's observation count grows
# by one batch per round, so an unpadded fit would compile a fresh
# (n, n)-shaped NLL while_loop at EVERY round (the tier-1 conftest's
# "~100 growing training-set shapes"). (X, y) pad to the next pow2 rung
# (floor HISTORY_FLOOR) with a 0/1 mask that makes the padded Gram exactly
# block-diagonal — [K_real + σ²I, 0; 0, I] — so the masked NLL, posterior
# solve, and every query are BITWISE the unpadded math on the real block,
# while one compiled program per rung serves the whole run. _FIT_SIG_LOG
# records each fit's padded trace signature; the signature-count test pins
# the ladder.
HISTORY_FLOOR = 8
_FIT_SIG_LOG = TraceSignatureLog()
FIT_SIG_NAME = "tuning.fit_gp"


def _sqdist(X1, X2, inv_lengthscales):
    a = X1 * inv_lengthscales
    b = X2 * inv_lengthscales
    return jnp.maximum(
        jnp.sum(a * a, -1)[:, None]
        - 2.0 * a @ b.T
        + jnp.sum(b * b, -1)[None, :],
        0.0,
    )


def rbf_kernel(X1, X2, amplitude, inv_lengthscales):
    """Reference: kernels.RBF."""
    return amplitude * jnp.exp(-0.5 * _sqdist(X1, X2, inv_lengthscales))


def matern52_kernel(X1, X2, amplitude, inv_lengthscales):
    """Reference: kernels.Matern52."""
    r = jnp.sqrt(_sqdist(X1, X2, inv_lengthscales) + 1e-12)
    s = jnp.sqrt(5.0) * r
    return amplitude * (1.0 + s + s * s / 3.0) * jnp.exp(-s)


KERNELS: dict[str, Callable] = {"rbf": rbf_kernel, "matern52": matern52_kernel}


@dataclasses.dataclass(frozen=True)
class GaussianProcess:
    """Fitted GP posterior (reference: GaussianProcessModel)."""

    X: jnp.ndarray  # (N, d) observed points, padded to the pow2 ladder
    y_mean: float
    y_std: float
    alpha: jnp.ndarray  # K⁻¹ y_centered (padded entries exactly 0)
    L: jnp.ndarray  # chol(K + σ²I); identity on the padded block
    amplitude: float
    inv_lengthscales: jnp.ndarray
    noise: float
    kernel_name: str = "matern52"
    mask: Optional[jnp.ndarray] = None  # (N,) 1=real observation, 0=pad

    def _query(self, Xq) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(standardized-space posterior mean, whitened cross-solve v) at
        query points — the shared core of predict and sample_joint. Padded
        observations are invisible: the cross-covariance columns into the
        pad are zeroed, their alpha entries are already 0, and L's padded
        block is the identity, so the whitened solve rows vanish too."""
        kern = KERNELS[self.kernel_name]
        Kq = kern(jnp.asarray(Xq, jnp.float32), self.X,
                  self.amplitude, self.inv_lengthscales)
        if self.mask is not None:
            Kq = Kq * self.mask[None, :]
        v = jax.scipy.linalg.solve_triangular(self.L, Kq.T, lower=True)
        return Kq @ self.alpha, v

    def predict(self, Xq) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Posterior mean and stddev at query points (n_q, d)."""
        with jax.default_device(_host_cpu()):
            mean, v = self._query(Xq)
            var = jnp.maximum(
                self.amplitude + self.noise - jnp.sum(v * v, axis=0), JITTER
            )
            return (mean * self.y_std + self.y_mean,
                    jnp.sqrt(var) * self.y_std)

    def sample_joint(self, Xq, n_samples: int, seed: int = 0) -> np.ndarray:
        """(n_samples, n_q) JOINT posterior draws at the query points —
        the fantasies behind true q-EI (acquisition.qei_*): correlations
        between query points are carried exactly (full posterior
        covariance, one Cholesky), where the constant-liar heuristic
        pretends each pick resolved to a point value.

        Draws are PREDICTIVE (the fitted observation noise is on the
        diagonal), matching predict()'s variance — so single-point MC q-EI
        converges to the closed-form EI (pinned by tests)."""
        with jax.default_device(_host_cpu()):
            Xq = jnp.asarray(np.asarray(Xq, np.float32))
            kern = KERNELS[self.kernel_name]
            mean, v = self._query(Xq)
            C = (kern(Xq, Xq, self.amplitude, self.inv_lengthscales)
                 - v.T @ v)
            C = C + (self.noise + JITTER) * jnp.eye(Xq.shape[0])
            Lc = jnp.linalg.cholesky(C)
            z = np.random.default_rng(seed).standard_normal(
                (n_samples, Xq.shape[0])).astype(np.float32)
            Z = np.asarray(mean)[None, :] + z @ np.asarray(Lc).T
            if not np.isfinite(Z).all():
                # f32 round-off can push the pool covariance past the
                # jitter into non-PSD; cholesky then yields silent NaNs.
                # Degrade to INDEPENDENT predictive draws (exact marginals,
                # no cross-candidate correlation) rather than hand
                # downstream argmaxes an all-NaN array.
                mean_p, std_p = self.predict(Xq)
                return (np.asarray(mean_p)[None, :]
                        + z * np.asarray(std_p)[None, :])
            return Z * self.y_std + self.y_mean


def _masked_gram(kern, X, mask, amp, inv_ls, noise):
    """K over padded points, exactly block-diagonal: the real block gets
    kern + σ²I, padded rows/cols are zeroed and their diagonal set to 1 —
    so Cholesky, logdet, and every solve reduce bitwise to the unpadded
    math (padded logdet contribution: log 1 = 0; padded solves: y = 0)."""
    n = X.shape[0]
    M = mask[:, None] * mask[None, :]
    return (kern(X, X, amp, inv_ls) * M
            + jnp.eye(n) * (noise * mask + (1.0 - mask)))


def _nll_builder(X, y, mask, kernel_name):
    kern = KERNELS[kernel_name]
    n, d = X.shape

    def nll_vg(theta):
        def nll(theta):
            amp = jnp.exp(theta[0])
            inv_ls = jnp.exp(-theta[1:1 + d])
            noise = jnp.exp(theta[-1]) + NOISE_FLOOR * amp
            K = _masked_gram(kern, X, mask, amp, inv_ls, noise)
            L = jnp.linalg.cholesky(K)
            a = jax.scipy.linalg.cho_solve((L, True), y)
            # The 2π term uses the PADDED count: a shape constant, so one
            # program serves every real count on the rung (the real count
            # would bake a fresh literal per fit). It offsets the NLL by
            # 0.5·(n_pad − n_real)·log 2π — constant in theta, so the
            # argmin (all the fit consumes) is untouched.
            return (0.5 * y @ a
                    + jnp.sum(jnp.log(jnp.diagonal(L)))
                    + 0.5 * n * jnp.log(2.0 * jnp.pi))

        return jax.value_and_grad(nll)(theta)

    return nll_vg


@partial(jax.jit, static_argnames=("kernel", "max_iters"))
def _fit_theta(X, y, mask, theta0, *, kernel, max_iters):
    """The whole hyperparameter fit as ONE jitted program with (X, y,
    mask) as ARGUMENTS. fit_gp used to hand minimize_lbfgs a fresh
    nll closure per call, so jax's jit cache — keyed on function
    identity, not just shapes — recompiled the ~1.3 s NLL while_loop on
    EVERY fit even when the pow2 ladder made the shapes identical. A
    module-level function keeps the identity stable: one compile per
    (rung shape, d, kernel, max_iters) serves the process."""
    nll_vg = _nll_builder(X, y, mask, kernel)
    return minimize_lbfgs(nll_vg, theta0, max_iters=max_iters,
                          tolerance=1e-9).w


def fit_gp(
    X,
    y,
    kernel: str = "matern52",
    max_iters: int = 60,
) -> GaussianProcess:
    """Fit kernel hyperparameters by exact marginal-likelihood maximization
    (reference samples them; direct optimization is cheaper and determin-
    istic). Observations are standardized internally. Runs on the host CPU
    backend (see _host_cpu)."""
    with jax.default_device(_host_cpu()):
        return _fit_gp_body(X, y, kernel, max_iters)


def _fit_gp_body(X, y, kernel, max_iters) -> GaussianProcess:
    X_real = np.asarray(X, np.float32)
    y_raw = np.asarray(y, np.float32)
    y_mean = float(y_raw.mean())
    y_std = float(y_raw.std()) or 1.0
    n_real, d = X_real.shape

    # Pad to the pow2 history rung (weight-0 masking; see HISTORY_FLOOR
    # note above): one compiled NLL/posterior program per rung serves the
    # whole tuning run instead of one per observation count.
    n = next_pow2(n_real, floor=HISTORY_FLOOR)
    X_pad = np.zeros((n, d), np.float32)
    X_pad[:n_real] = X_real
    y_pad = np.zeros((n,), np.float32)
    y_pad[:n_real] = (y_raw - y_mean) / y_std
    mask_np = np.zeros((n,), np.float32)
    mask_np[:n_real] = 1.0
    X = jnp.asarray(X_pad)
    y = jnp.asarray(y_pad)
    mask = jnp.asarray(mask_np)

    theta0 = jnp.zeros((d + 2,), jnp.float32)  # log amp, log ls_i, log noise
    theta0 = theta0.at[-1].set(-4.0)
    _FIT_SIG_LOG.record(FIT_SIG_NAME, (X, y, mask, theta0))
    theta = _fit_theta(X, y, mask, theta0, kernel=kernel,
                       max_iters=max_iters)
    if not bool(jnp.isfinite(theta).all()):
        theta = theta0  # hyperparameter fit diverged; prior defaults

    kern = KERNELS[kernel]

    def _posterior(theta):
        amp = float(jnp.exp(theta[0]))
        inv_ls = jnp.exp(-theta[1:1 + d])
        noise = float(jnp.exp(theta[-1])) + NOISE_FLOOR * amp
        K = _masked_gram(kern, X, mask, amp, inv_ls, noise)
        L = jnp.linalg.cholesky(K)
        alpha = jax.scipy.linalg.cho_solve((L, True), y)
        return amp, inv_ls, noise, L, alpha

    amp, inv_ls, noise, L, alpha = _posterior(theta)
    if not bool(jnp.isfinite(alpha).all()):
        amp, inv_ls, noise, L, alpha = _posterior(theta0)
    return GaussianProcess(
        X=X, y_mean=y_mean, y_std=y_std, alpha=alpha, L=L,
        amplitude=amp, inv_lengthscales=inv_ls, noise=noise,
        kernel_name=kernel, mask=mask,
    )
