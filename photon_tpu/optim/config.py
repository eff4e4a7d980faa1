"""Optimizer configuration.

Reference parity: com.linkedin.photon.ml.optimization.{OptimizerType,
OptimizerConfig, GLMOptimizationConfiguration}.
"""
from __future__ import annotations

import dataclasses
import enum

from photon_tpu.optim.regularization import RegularizationContext, NONE


class OptimizerType(enum.Enum):
    LBFGS = "lbfgs"
    OWLQN = "owlqn"  # selected automatically when L1 weight > 0, as in reference
    TRON = "tron"


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    optimizer: OptimizerType = OptimizerType.LBFGS
    max_iters: int = 100
    # relative convergence tolerance (reference default 1e-7); 0 asks for
    # the whole iteration budget, in every solver alike: `stop_state`
    tolerance: float = 1e-7
    # L-BFGS/OWL-QN history length (Breeze default m=10 in reference LBFGS).
    history: int = 10
    # TRON: max conjugate-gradient iterations per Newton step.
    cg_max_iters: int = 20
    reg: RegularizationContext = NONE
    reg_weight: float = 0.0
    regularize_intercept: bool = True  # reference regularizes the intercept feature
    # Lane-minor grid solver only: storage dtype for the (m, d, G) L-BFGS
    # (s, y) history, e.g. "bfloat16" (None = solver dtype, f32). The
    # history is the biggest solver-state HBM stream at large d×G, so
    # halving it buys real throughput (+7-10% on the 10M-feature 8/16-lane
    # bench, docs/PERF.md); inner products (rho, gamma, curvature tests)
    # stay f32 — computed from the UNROUNDED pair at push time and
    # cached — so only the two-loop direction sees the rounding, and the
    # Wolfe search vets it as usual (quality pinned by
    # tests/test_lane_solver.py::test_lane_grid_bf16_history_quality).
    lane_history_dtype: str | None = None

    def effective_optimizer(self) -> OptimizerType:
        """The reference forces OWLQN whenever an L1 term is present."""
        if self.reg.l1_weight(self.reg_weight) > 0.0:
            return OptimizerType.OWLQN
        return self.optimizer


def stop_state(tolerance: float, before: tuple, converged, stopped, broke):
    """(done, converged, failed) after one iteration — the ONE stopping rule
    of every solver here (L-BFGS, OWL-QN, TRON; scalar, lane and streamed).

    ``before`` is the (done, converged, failed) the iteration started from;
    ``converged`` this iteration's convergence tests; ``stopped`` whether it
    ends an early-stopping solve (it converged, its line search failed, its
    trust region collapsed); ``broke`` whether it ends it WITHOUT having
    converged. Booleans or boolean arrays (a lane solver passes them masked
    to its active lanes).

    ``tolerance`` > 0: the solve is done at its first stop.

    ``tolerance`` 0 is a FIXED DEPTH: the loop runs its ``max_iters``
    whatever happens in it, which is what callers pass 0 for (benchmarks,
    kill/restore matrices, lock-step `vmap` lanes whose chunk would
    otherwise run as deep as its luckiest-with-rounding lane). An
    iteration that cannot improve the point keeps it — a failed line search
    or a rejected trust-region step already does — so a stalled solve
    repeats its last point, and ``iterations`` counts those repeats.
    ``converged`` is then sticky and clears ``failed``: a solve that has
    reached f32 resolution once is not failed by the searches it loses
    afterwards."""
    done0, conv0, fail0 = before
    if tolerance > 0:
        return done0 | stopped, converged, fail0 | broke
    conv = conv0 | converged
    return done0, conv, (fail0 | broke) & ~conv
