"""Plain references for the GLMix regularization-grid cell: each grid point
(a LANE) is the model `glmix_reference` checks, under its own L2 weights —
numpy float64 on the host, one lane and one entity at a time, no vmap, no
buckets, no projection tables, no code of the program under test.

What a lane is, as Photon-ML's `GameTrainingDriver` defines the grid: the
cross product of every coordinate's ``reg_weights`` list, in the order the
coordinates are listed, one `GameOptimizationConfiguration` a point; the
driver keeps the best point by the validation evaluator
(`ModelOutputMode.BEST`). Departures from Photon-ML, each the program's
own contract: the lanes never chain warm starts across grid points (every
lane starts from zeros, where Photon-ML may warm-start a point from the one
before); every intercept is regularized with the rest. And one of the
reference's: it runs no coordinate descent — it holds each returned
coordinate to the optimum of its own lane's objective at the offsets the
lane's other returned coordinates give, which the LAST coordinate of the
sequence was solved at exactly and the earlier ones within the drift of
the later ones' last sweep.
"""
from __future__ import annotations

import itertools

import numpy as np

from benchmark.gen import glmix_reference as ref

GRAD_TOL = 1e-6     # Newton stops at this gradient norm (the fixed effect)
MAX_STEPS = 50


def lane_weights(config: dict) -> list:
    """[{coordinate: L2 weight}] a lane: the cross product of the
    coordinates' ``reg_weights`` lists in the configuration's order, every
    other coordinate at its ``reg_weight``."""
    coords = config["coordinates"]
    listed = [n for n, spec in coords.items() if spec.get("reg_weights")]
    out = []
    for combo in itertools.product(*(coords[n]["reg_weights"]
                                     for n in listed)):
        weights = {n: float(spec["reg_weight"]) for n, spec in coords.items()}
        weights.update({n: float(w) for n, w in zip(listed, combo)})
        out.append(weights)
    return out


def entity_optimum(X, y, offsets, l2: float, rd=ref._exact) -> tuple:
    """One entity's (w*, objective at w*) under its lane's ``l2``: the
    damped Newton solve of `glmix_reference.newton`, offsets included;
    ``rd`` rounds every product (`glmix_reference.bf16`: the control)."""
    w, f, _ = ref.newton(X, y, offsets, l2, rd)
    return w, f


def fixed_objective(X, y, offsets, w, l2: float) -> float:
    """The fixed effect's objective over ALL rows: Σ log(1 + e^z) − y·z +
    ½·l2·‖w‖², z = offsets + X·w."""
    return ref.objective(np.asarray(X, np.float64), y, offsets, w, l2)


def fixed_optimum(X, y, offsets, l2: float) -> tuple:
    """The fixed effect's (w*, objective at w*) under ``l2`` at the given
    offsets: damped Newton from 0 over every row, float64."""
    X = np.asarray(X, np.float64)
    w = np.zeros(X.shape[1])
    f = fixed_objective(X, y, offsets, w, l2)
    for _ in range(MAX_STEPS):
        z = offsets + X @ w
        p = 1.0 / (1.0 + np.exp(-z))
        g = X.T @ (p - y) + l2 * w
        if np.linalg.norm(g) <= GRAD_TOL * max(1.0, np.sqrt(X.shape[0])):
            return w, f
        H = (X * (p * (1.0 - p))[:, None]).T @ X + l2 * np.eye(X.shape[1])
        d = np.linalg.solve(H, g)
        t = 1.0
        while True:
            f_new = fixed_objective(X, y, offsets, w - t * d, l2)
            if f_new <= f or t < 1e-8:
                break
            t *= 0.5
        if f_new >= f:  # flat to float64's resolution
            return w, f
        w, f = w - t * d, f_new
    return w, f


def lane_loss(margins: dict, y) -> float:
    """A lane's unregularized loss: the float64 log-loss of the sum of its
    coordinates' margins (`glmix_reference.log_loss`)."""
    return ref.log_loss(sum(margins.values()), y)
