"""Plain float64 references the cells' `correct` is decided against.

Copied from chip_smoke.py (`np_logistic_objective`, `stored`, `check_lane`,
`rank_auc`); they share no code with the program under test.
"""
from __future__ import annotations

import numpy as np

# bf16 unit roundoff is 2^-9; every X-pass operand (stored values, and the
# coefficients at the matmul) is rounded to bf16 at most once on each side
# of a comparison, the logistic loss is 1-Lipschitz in the margin, and the
# per-row sum of |x_j w_j| stays below the per-row loss — so two correct
# evaluations of one objective agree to one bf16 ulp, relative
# (chip_smoke.LOSS_RTOL). A route that lost a bucket, a lane or a precision
# step misses it by far.
LOSS_RTOL = 2.0 ** -8
# n·log 2 at w = 0 involves no coefficient: f32 summation noise only
LOSS0_RTOL = 1e-5


def np_logistic_objective(z, y, w, l2: float) -> float:
    """Σ log(1 + e^z) − y·z + ½·l2·‖w‖², float64 on the host."""
    z = np.asarray(z, np.float64)
    return float(np.sum(np.logaddexp(0.0, z) - np.asarray(y, np.float64) * z)
                 + 0.5 * l2 * np.dot(w, w))


def stored(values, dtype) -> np.ndarray:
    """Host values as the device stores them (e.g. rounded to bf16), f64."""
    return np.asarray(np.asarray(values).astype(dtype), np.float64)


def check_lane(history, value, w, reference: float, n_log2: float) -> dict:
    """One solved lane against the plain reference: loss at w = 0 equals
    n·log 2, the reported final loss equals the numpy loss at the final w,
    the loss never rose, w is finite. Returns the verdict with its numbers."""
    h = np.asarray(history, np.float64)
    h = h[~np.isnan(h)]
    rel = abs(float(value) - reference) / reference
    rel0 = abs(float(h[0]) - n_log2) / n_log2
    monotone = bool(np.all(np.isfinite(w))) and bool(h[-1] < h[0]) \
        and bool(np.all(np.diff(h) <= 0))
    return {"ok": rel0 <= LOSS0_RTOL and rel <= LOSS_RTOL and monotone,
            "loss0_rel": rel0, "final_rel": rel, "monotone": monotone,
            "final_loss": float(value), "reference_loss": reference}


def rank_auc(scores, labels) -> float:
    """Mann-Whitney AUC with average ranks for ties, float64."""
    from scipy.stats import rankdata

    y = np.asarray(labels) > 0.5
    r = rankdata(np.asarray(scores, np.float64))
    n1, n0 = int(y.sum()), int((~y).sum())
    return float((r[y].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))
