"""Generalized linear model classes.

Reference parity: com.linkedin.photon.ml.supervised.model.GeneralizedLinearModel
and its subclasses (classification.LogisticRegressionModel,
regression.{LinearRegressionModel, PoissonRegressionModel},
classification.SmoothedHingeLossLinearSVMModel), plus model.Coefficients
(means + optional variances).

The intercept, as in the reference, is just another feature column
(Constants.INTERCEPT_KEY); nothing here special-cases it.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from photon_tpu.data.matrix import (BlockedEllRows, Matrix,
                                    PermutedHybridRows,
                                    layout_matvec_lanes, matvec,
                                    matvec_lanes)
from photon_tpu.ops.losses import TaskType, mean_fn


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("means", "variances"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class Coefficients:
    """Reference: com.linkedin.photon.ml.model.Coefficients."""

    means: jax.Array  # (d,)
    variances: Optional[jax.Array] = None  # (d,) or None

    @property
    def dim(self) -> int:
        return self.means.shape[0]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("coefficients",),
    meta_fields=("task",),
)
@dataclasses.dataclass(frozen=True)
class GeneralizedLinearModel:
    coefficients: Coefficients
    task: TaskType

    @property
    def weights(self) -> jax.Array:
        return self.coefficients.means

    def score(self, X: Matrix, offsets=0.0) -> jax.Array:
        """Raw margin x·w + offset (reference: computeScore)."""
        from photon_tpu.data.dataset import ChunkedMatrix

        if isinstance(X, ChunkedMatrix):
            return chunked_margins(X, self.coefficients.means,
                                   jnp.asarray(offsets, jnp.float32))
        return _margin_jit(X, self.coefficients.means,
                           jnp.asarray(offsets, jnp.float32))

    def predict_mean(self, X: Matrix, offsets=0.0) -> jax.Array:
        """Mean response via the inverse link (reference: computeMean)."""
        from photon_tpu.data.dataset import ChunkedMatrix

        if isinstance(X, ChunkedMatrix):
            return mean_fn(self.task)(self.score(X, offsets))
        return _mean_jit(self.task, X, self.coefficients.means,
                         jnp.asarray(offsets, jnp.float32))

    def predict_class(self, X: Matrix, offsets=0.0, threshold=0.5) -> jax.Array:
        """Binary decision for classification tasks."""
        if self.task is TaskType.LOGISTIC_REGRESSION:
            return (self.predict_mean(X, offsets) >= threshold).astype(jnp.int32)
        if self.task is TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM:
            return (self.score(X, offsets) >= 0.0).astype(jnp.int32)
        raise ValueError(f"{self.task} is not a classification task")


# Jitted at the entry point: one device dispatch per scoring call instead
# of one per primitive. User-facing
# coefficient vectors are in ORIGINAL column order; a PermutedHybridRows
# design matrix works in its permuted space, so scoring translates w at
# the boundary (one gather — see PermutedHybridRows docstring).
@jax.jit
def _margin_jit(X, w, offsets):
    if isinstance(X, (PermutedHybridRows, BlockedEllRows)):
        w = X.from_model_space(w)
    return matvec(X, w) + offsets


@partial(jax.jit, static_argnames=("task",))
def _mean_jit(task, X, w, offsets):
    return mean_fn(task)(_margin_jit(X, w, offsets))


@jax.jit
def _chunk_margin(X, w):
    return matvec(X, w)


def chunked_margins(X, w, offsets=0.0) -> jax.Array:
    """Margins over a host-resident ChunkedMatrix: stream each chunk through
    one jitted matvec (uploads overlap compute via jax's async transfers)
    and concatenate on device — the scoring side of the streamed objective
    regime. Returns (n_real,) — internal chunk padding is trimmed."""
    import jax as _jax

    w = jnp.asarray(w, jnp.float32)
    if getattr(X, "permuted", False):
        # blocked-ELL chunk ladder: every chunk shares ONE global column
        # permutation — translate once for the whole stream.
        w = w[jnp.asarray(X.perm_cols)]
    parts, nxt = [], _jax.device_put(X.chunks[0])
    for i in range(X.n_chunks):
        cur = nxt
        if i + 1 < X.n_chunks:
            nxt = _jax.device_put(X.chunks[i + 1])
        parts.append(_chunk_margin(cur, w))
    z = jnp.concatenate(parts)[:X.n_real]
    return z + offsets


@partial(jax.jit, static_argnames=("stored_rows",))
def _score_many(W, X, offsets, stored_rows=False):
    """(G, n) margins of stacked (G, d) coefficients. Rows — and the rows
    of ``offsets`` — are in the CALLER's order, or with ``stored_rows`` in
    the order X stores them (a GLMBatch's; the two differ for a
    `to_blocked_ell` layout only)."""
    if isinstance(X, (PermutedHybridRows, BlockedEllRows)):
        mv = layout_matvec_lanes if stored_rows else matvec_lanes
        return mv(X, W[:, X.perm_cols].T).T + offsets
    return jax.vmap(lambda w: matvec(X, w))(W) + offsets


def _stack_means(models):
    return jnp.stack([jnp.asarray(m.coefficients.means) for m in models])


def score_models(models, X: Matrix, offsets=0.0) -> jax.Array:
    """(G, n) raw margins of G same-shape models over one design matrix, as
    ONE device program — the scoring side of a `train_glm_grid` sweep (the
    dense case compiles to a single (n, d)×(d, G) matmul; per-model scoring
    would pay a dispatch round-trip per model). Rows, and ``offsets``, in
    the caller's order."""
    return _score_many(_stack_means(models), X,
                       jnp.asarray(offsets, jnp.float32))


def score_models_on_batch(models, batch) -> jax.Array:
    """`score_models` over a GLMBatch, its offsets included, rows in the
    BATCH's order: what pairs with ``batch.y`` / ``batch.weights``."""
    return _score_many(_stack_means(models), batch.X,
                       jnp.asarray(batch.offsets, jnp.float32),
                       stored_rows=True)


def logistic_regression(coeffs, variances=None):
    return GeneralizedLinearModel(
        Coefficients(jnp.asarray(coeffs), variances), TaskType.LOGISTIC_REGRESSION
    )


def linear_regression(coeffs, variances=None):
    return GeneralizedLinearModel(
        Coefficients(jnp.asarray(coeffs), variances), TaskType.LINEAR_REGRESSION
    )


def poisson_regression(coeffs, variances=None):
    return GeneralizedLinearModel(
        Coefficients(jnp.asarray(coeffs), variances), TaskType.POISSON_REGRESSION
    )
