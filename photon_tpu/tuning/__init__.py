"""Bayesian hyperparameter tuning (reference: com.linkedin.photon.ml.hyperparameter)."""
from photon_tpu.tuning.gp import GaussianProcess, fit_gp
from photon_tpu.tuning.acquisition import expected_improvement, lower_confidence_bound
from photon_tpu.tuning.search import SearchRange, SearchSpace, candidates
from photon_tpu.tuning.tuner import TuningResult, tune, tune_glm_reg
from photon_tpu.tuning.lane_tuner import (
    LaneBudget, LaneTuningResult, RoundBudgetError, tune_glm_reg_lanes,
)

__all__ = [
    "GaussianProcess", "fit_gp", "expected_improvement",
    "lower_confidence_bound", "SearchRange", "SearchSpace", "candidates",
    "TuningResult", "tune", "tune_glm_reg",
    "LaneBudget", "LaneTuningResult", "RoundBudgetError",
    "tune_glm_reg_lanes",
]
