"""Convergence-masked lanes in the RE grid: the A/B (VERDICT r3 item 8).

Question: a lane-axis GAME grid runs every (entity, lane) solve in
lock-step — each chunk iterates until its SLOWEST lane converges, with
converged lanes' updates masked (jax's batched `lax.while_loop`
select-masks carries but still executes every member's FLOPs). Can
masking converged lanes recover the cost of a skewed grid, or is the
per-lane-adaptive sequential path the only structure that does?

Method: one random-effect coordinate (2000 entities x 8 rows), 4-lane
reg-weight grids of three difficulty profiles, vectorized (lane-axis) vs
sequential (per-lane adaptive) paths, warm wall-clock best-of-N.

Run: JAX_PLATFORMS=cpu python benches/re_grid_masking.py
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import numpy as np

def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--entities", type=int, default=2000)
    p.add_argument("--rows-per", type=int, default=8)
    p.add_argument("--reps", type=int, default=3)
    args = p.parse_args()

    from photon_tpu.game.dataset import GameData
    from photon_tpu.game.estimator import (
        GameEstimator,
        RandomEffectConfig,
    )
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim import regularization as reg
    from photon_tpu.optim.config import OptimizerConfig

    rng = np.random.default_rng(0)
    E, m = args.entities, args.rows_per
    n = E * m
    d = 4
    ids = np.repeat([f"e{i}" for i in range(E)], m)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, -1] = 1.0  # intercept
    true_w = rng.normal(size=(E, d)).astype(np.float32)
    margin = np.einsum("nd,nd->n", X, true_w[np.repeat(np.arange(E), m)])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    data = GameData.build(y, {"s": X}, {"ent": ids})

    def make_estimator():
        return GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={"re": RandomEffectConfig(
                "ent", "s",
                OptimizerConfig(max_iters=60, tolerance=1e-7, reg=reg.l2(),
                                reg_weight=1.0))},
            n_sweeps=1, warm_start=False, vectorized_grid=True)

    def grid_of(weights):
        est = make_estimator()
        return est, [
            {"re": RandomEffectConfig(
                "ent", "s",
                OptimizerConfig(max_iters=60, tolerance=1e-7, reg=reg.l2(),
                                reg_weight=float(w)))}
            for w in weights
        ]

    profiles = {
        "uniform fast (4x l2=100)": [100.0] * 4,
        "uniform slow (4x l2=1e-3)": [1e-3] * 4,
        "skewed (100, 10, 1, 1e-3)": [100.0, 10.0, 1.0, 1e-3],
    }

    import dataclasses as dc

    def run(est, grid, vectorize):
        est2 = dc.replace(est, vectorized_grid=vectorize)
        return est2.fit(data, config_grid=grid)

    print(f"RE grid A/B: {E} entities x {m} rows, d={d}, 4 lanes, "
          f"1 sweep, logistic")
    for label, weights in profiles.items():
        row = {}
        for mode, vec in (("lane-axis", True), ("sequential", False)):
            est, grid = grid_of(weights)
            run(est, grid, vec)  # warm the jit caches
            best = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                out = run(est, grid, vec)
                best = min(best, time.perf_counter() - t0)
            row[mode] = best
            del out
        ratio = row["sequential"] / row["lane-axis"]
        verdict = (f"lane-axis {ratio:.2f}x faster" if ratio >= 1
                   else f"sequential {1 / ratio:.2f}x faster")
        print(f"  {label:28s}: lane-axis {row['lane-axis'] * 1e3:7.0f} ms  "
              f"sequential {row['sequential'] * 1e3:7.0f} ms  ({verdict})")


if __name__ == "__main__":
    main()
