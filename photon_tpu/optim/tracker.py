"""Solver result + per-iteration state tracking.

Reference parity: com.linkedin.photon.ml.optimization.OptimizationStatesTracker
(loss / gradient-norm per iteration). History arrays are fixed-length
(max_iters + 1), NaN-padded, so the whole solve stays jittable.

`converged` reports ONLY the gradient/function tolerance criteria;
`failed` reports abnormal termination (line-search failure, trust region
collapsed) — mirroring the reference, which distinguishes Breeze's
line-search failure (FailedLineSearch) from convergence.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import numpy as np


class OptResult(NamedTuple):
    w: jax.Array
    value: jax.Array
    grad_norm: jax.Array
    iterations: jax.Array
    converged: jax.Array  # tolerance criteria met
    failed: jax.Array  # abnormal stop (line search / trust region failure)
    loss_history: jax.Array  # (max_iters + 1,), NaN-padded
    grad_norm_history: jax.Array  # (max_iters + 1,), NaN-padded
    # line-search evaluations taken over the whole solve (the searches' own
    # trial counts, summed; lock-step for a lane solver, so one scalar for
    # all lanes; the streamed host loops sum their Wolfe trials, and the
    # rungs their OWL-QN ladders priced). Stays on the device like every
    # other field; None where the solver has no line search (TRON).
    evaluations: Optional[jax.Array] = None

    def history(self) -> np.ndarray:
        h = np.asarray(self.loss_history)
        return h[~np.isnan(h)]

    def grad_history(self) -> np.ndarray:
        h = np.asarray(self.grad_norm_history)
        return h[~np.isnan(h)]
