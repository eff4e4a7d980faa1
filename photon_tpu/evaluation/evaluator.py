"""Evaluator objects + factory.

Reference parity: com.linkedin.photon.ml.evaluation.{EvaluatorType,
EvaluatorFactory, Evaluator} — including `betterThan` comparison direction
(AUC/P@K: higher is better; the loss metrics: lower is better) used by
GameEstimator for validation model selection, and the per-task default
evaluator used when none is configured (TaskType → evaluator mapping in the
reference's Driver).
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import jax.numpy as jnp

from photon_tpu.evaluation import grouped, metrics
from photon_tpu.ops.losses import TaskType


class EvaluatorType(enum.Enum):
    AUC = "AUC"
    AUPR = "AUPR"
    RMSE = "RMSE"
    SQUARED_LOSS = "SQUARED_LOSS"
    LOGISTIC_LOSS = "LOGISTIC_LOSS"
    POISSON_LOSS = "POISSON_LOSS"
    SMOOTHED_HINGE_LOSS = "SMOOTHED_HINGE_LOSS"
    PRECISION_AT_K = "PRECISION_AT_K"
    SHARDED_AUC = "SHARDED_AUC"
    SHARDED_AUPR = "SHARDED_AUPR"
    SHARDED_PRECISION_AT_K = "SHARDED_PRECISION_AT_K"


_HIGHER_IS_BETTER = {
    EvaluatorType.AUC,
    EvaluatorType.AUPR,
    EvaluatorType.SHARDED_AUPR,
    EvaluatorType.PRECISION_AT_K,
    EvaluatorType.SHARDED_AUC,
    EvaluatorType.SHARDED_PRECISION_AT_K,
}

_SHARDED = {EvaluatorType.SHARDED_AUC, EvaluatorType.SHARDED_AUPR,
            EvaluatorType.SHARDED_PRECISION_AT_K}

_METRIC_FNS = {
    EvaluatorType.AUC: metrics.auc,
    EvaluatorType.AUPR: metrics.aupr,
    EvaluatorType.RMSE: metrics.rmse,
    EvaluatorType.SQUARED_LOSS: metrics.squared_loss,
    EvaluatorType.LOGISTIC_LOSS: metrics.logistic_loss,
    EvaluatorType.POISSON_LOSS: metrics.poisson_loss,
    EvaluatorType.SMOOTHED_HINGE_LOSS: metrics.smoothed_hinge_loss,
}


@dataclasses.dataclass(frozen=True)
class Evaluator:
    """One metric over (scores, labels, weights[, groups]).

    `k` applies to the P@K evaluators; `num_groups` to the sharded ones
    (groups are dense int ids, see evaluation.grouped).
    """

    kind: EvaluatorType
    k: int = 10
    num_groups: Optional[int] = None

    @property
    def higher_is_better(self) -> bool:
        return self.kind in _HIGHER_IS_BETTER

    @property
    def needs_groups(self) -> bool:
        return self.kind in _SHARDED

    def better_than(self, a: float, b: Optional[float]) -> bool:
        """Is score `a` better than incumbent `b`? (reference: Evaluator.betterThan)"""
        if b is None or jnp.isnan(b):
            return True
        return a > b if self.higher_is_better else a < b

    def lane_metric(self):
        """The metric as a traceable ``fn(scores, labels, weights)`` of one
        set of scores — what a regularization grid's lanes are ranked by
        on the device — or None for one that needs groups or a k."""
        return None if self.needs_groups else _METRIC_FNS.get(self.kind)

    def evaluate(self, scores, labels, weights=None, groups=None) -> float:
        if self.needs_groups:
            if groups is None or self.num_groups is None:
                raise ValueError(f"{self.kind} requires groups and num_groups")
            if weights is None:
                weights = jnp.ones_like(jnp.asarray(scores, jnp.float32))
            if self.kind is EvaluatorType.SHARDED_AUC:
                _, _, mean = grouped.grouped_auc(
                    scores, labels, weights, groups, self.num_groups
                )
            elif self.kind is EvaluatorType.SHARDED_AUPR:
                _, _, mean = grouped.grouped_aupr(
                    scores, labels, weights, groups, self.num_groups
                )
            else:
                _, _, mean = grouped.grouped_precision_at_k(
                    scores, labels, weights, groups, self.num_groups, self.k
                )
            return float(mean)
        if self.kind is EvaluatorType.PRECISION_AT_K:
            return float(metrics.precision_at_k(scores, labels, self.k, weights))
        fn = _METRIC_FNS.get(self.kind)
        if fn is None:
            raise ValueError(f"unknown evaluator kind: {self.kind}")
        return float(fn(scores, labels, weights))


def evaluate_with_entity(evaluator: Evaluator, scores, labels, weights,
                         entity_ids: dict, entity: Optional[str]) -> float:
    """Shared sharded-evaluator path for GameEstimator and both drivers:
    densify the raw entity-id column to group ids and evaluate. ONE
    implementation so SHARDED_* numbers are comparable everywhere.
    Raises ValueError when the entity column is missing."""
    import numpy as np

    if entity is None or entity not in entity_ids:
        raise ValueError(
            f"sharded evaluator {evaluator.kind.name} needs an entity id "
            f"column; got {entity!r}, available: {list(entity_ids)}")
    _, groups = np.unique(np.asarray(entity_ids[entity]),
                          return_inverse=True)
    ev = dataclasses.replace(evaluator, num_groups=int(groups.max()) + 1)
    return ev.evaluate(scores, labels, weights, groups)


def parse_evaluator(spec: str) -> Evaluator:
    """Evaluator from its config-string form (reference: the driver's
    evaluatorTypes strings, e.g. ``AUC``, ``RMSE``, ``PRECISION@5``).
    Accepts EvaluatorType names case-insensitively with an optional ``@k``
    or ``:k`` suffix for the precision evaluators."""
    s = spec.strip().upper().replace("@", ":")
    k = None
    if ":" in s:
        s, _, knum = s.partition(":")
        k = int(knum)
    s = s.strip()
    if s == "PRECISION":
        s = "PRECISION_AT_K"
    try:
        kind = EvaluatorType[s]
    except KeyError:
        raise ValueError(
            f"unknown evaluator {spec!r}; valid: "
            f"{[e.name for e in EvaluatorType]}") from None
    if k is not None and kind not in (EvaluatorType.PRECISION_AT_K,
                                      EvaluatorType.SHARDED_PRECISION_AT_K):
        raise ValueError(
            f"evaluator {spec!r}: @k only applies to the precision "
            "evaluators (did you mean PRECISION@k?)")
    return Evaluator(kind, k=10 if k is None else k)


def evaluator_name(ev: Evaluator) -> str:
    """Display/config name round-tripping parse_evaluator."""
    if ev.kind in (EvaluatorType.PRECISION_AT_K,
                   EvaluatorType.SHARDED_PRECISION_AT_K):
        return f"{ev.kind.name}@{ev.k}"
    return ev.kind.name


def default_evaluator(task: TaskType) -> Evaluator:
    """Per-task default suite head (reference: Driver's TaskType → evaluator)."""
    if task is TaskType.LOGISTIC_REGRESSION:
        return Evaluator(EvaluatorType.AUC)
    if task is TaskType.LINEAR_REGRESSION:
        return Evaluator(EvaluatorType.RMSE)
    if task is TaskType.POISSON_REGRESSION:
        return Evaluator(EvaluatorType.POISSON_LOSS)
    return Evaluator(EvaluatorType.AUC)


def evaluator_suite(task: TaskType) -> list[Evaluator]:
    """All applicable unsharded evaluators for a task."""
    if task is TaskType.LOGISTIC_REGRESSION:
        return [
            Evaluator(EvaluatorType.AUC),
            Evaluator(EvaluatorType.AUPR),
            Evaluator(EvaluatorType.LOGISTIC_LOSS),
            Evaluator(EvaluatorType.PRECISION_AT_K),
        ]
    if task is TaskType.LINEAR_REGRESSION:
        return [Evaluator(EvaluatorType.RMSE), Evaluator(EvaluatorType.SQUARED_LOSS)]
    if task is TaskType.POISSON_REGRESSION:
        return [Evaluator(EvaluatorType.POISSON_LOSS)]
    return [Evaluator(EvaluatorType.AUC), Evaluator(EvaluatorType.SMOOTHED_HINGE_LOSS)]
