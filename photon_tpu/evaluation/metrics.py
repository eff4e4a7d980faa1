"""Evaluation metrics, jit-safe and weight-aware.

Reference parity: com.linkedin.photon.ml.evaluation.{AreaUnderROCCurveEvaluator,
RMSEEvaluator, SquaredLossEvaluator, LogisticLossEvaluator, PoissonLossEvaluator,
SmoothedHingeLossEvaluator, PrecisionAtKEvaluator}.

The reference computes AUC with a Spark sort + sliding aggregation over score
ties; here the whole metric is one XLA program: sort, tie-group segmentation
via `segment_sum`/`segment_max`, single reduction. Rows with weight 0 are
padding and contribute nothing, so metrics compose with the padded static
shapes used everywhere else in photon-tpu.

Conventions: `scores` are raw margins or mean predictions as each metric
expects (AUC is rank-based so either works); binary labels are {0, 1}.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from photon_tpu.evaluation.grouped import grouped_auc, grouped_aupr
from photon_tpu.ops.losses import TaskType, loss_fns

# Every metric body is wrapped in jax.jit: each call then costs ONE device
# dispatch instead of one per primitive, which adds up over a grid of
# per-lane evaluations.


def _asarrays(scores, labels, weights):
    scores = jnp.asarray(scores, jnp.float32)
    labels = jnp.asarray(labels, jnp.float32)
    if weights is None:
        weights = jnp.ones_like(scores)
    else:
        weights = jnp.asarray(weights, jnp.float32)
    return scores, labels, weights


# ------------------------------------------------------------------------ AUC
def auc(scores, labels, weights=None) -> jax.Array:
    """Weighted, tie-aware area under the ROC curve.

    AUC = P(score⁺ > score⁻) + ½ P(score⁺ = score⁻) under the weighted
    empirical distribution — the same quantity the reference's
    AreaUnderROCCurveEvaluator computes with its sorted sliding sum.
    Returns NaN when either class has zero total weight (reference returns
    an error there; NaN lets callers mask invalid groups).

    Implemented as the one-group case of evaluation.grouped.grouped_auc so
    the tie-handling math lives in exactly one place.
    """
    scores, labels, weights = _asarrays(scores, labels, weights)
    return _auc_jit(scores, labels, weights)


@jax.jit
def _auc_jit(scores, labels, weights):
    per_group, _, _ = grouped_auc(
        scores, labels, weights, jnp.zeros_like(scores, jnp.int32), 1
    )
    return per_group[0]


# ----------------------------------------------------------------------- AUPR
def aupr(scores, labels, weights=None) -> jax.Array:
    """Weighted, tie-aware area under the precision–recall curve, in the
    step-wise average-precision form (sklearn's average_precision_score;
    reference: AreaUnderPRCurveEvaluator). NaN when positive weight is
    zero. One-group case of evaluation.grouped.grouped_aupr, so the
    threshold/tie math lives in exactly one place."""
    scores, labels, weights = _asarrays(scores, labels, weights)
    return _aupr_jit(scores, labels, weights)


@jax.jit
def _aupr_jit(scores, labels, weights):
    per_group, _, _ = grouped_aupr(
        scores, labels, weights, jnp.zeros_like(scores, jnp.int32), 1
    )
    return per_group[0]


# --------------------------------------------------------------- loss metrics
def rmse(scores, labels, weights=None) -> jax.Array:
    """Weighted root-mean-squared error (reference: RMSEEvaluator; scores are
    mean predictions for linear regression, i.e. the raw margin)."""
    return _rmse_jit(*_asarrays(scores, labels, weights))


@jax.jit
def _rmse_jit(scores, labels, weights):
    d = scores - labels
    return jnp.sqrt(jnp.sum(weights * d * d) / jnp.sum(weights))


def _mean_pointwise_loss(task: TaskType):
    loss, _, _ = loss_fns(task)

    @jax.jit
    def _body(scores, labels, weights):
        return jnp.sum(weights * loss(scores, labels)) / jnp.sum(weights)

    def metric(scores, labels, weights=None) -> jax.Array:
        return _body(*_asarrays(scores, labels, weights))

    return metric


# Reference evaluators take the raw margin (offset + score) for these.
logistic_loss = _mean_pointwise_loss(TaskType.LOGISTIC_REGRESSION)
squared_loss = _mean_pointwise_loss(TaskType.LINEAR_REGRESSION)
poisson_loss = _mean_pointwise_loss(TaskType.POISSON_REGRESSION)
smoothed_hinge_loss = _mean_pointwise_loss(TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM)


# -------------------------------------------------------------- precision@k
def precision_at_k(scores, labels, k: int, weights=None) -> jax.Array:
    """Fraction of positives among the k highest-scoring (non-padding) rows.

    Reference: PrecisionAtKEvaluator. Label counting is unweighted (weights
    only mark padding via weight 0), matching the reference, which computes
    P@K from labels alone. If fewer than k real rows exist, divides by the
    number of rows considered.
    """
    scores, labels, weights = _asarrays(scores, labels, weights)
    return _precision_at_k_jit(scores, labels, weights, k=int(k))


@partial(jax.jit, static_argnames=("k",))
def _precision_at_k_jit(scores, labels, weights, k):
    real = weights > 0.0
    key = jnp.where(real, scores, -jnp.inf)
    order = jnp.argsort(-key)
    topk = order[:k]
    mask = real[topk].astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(labels[topk] * mask) / denom
