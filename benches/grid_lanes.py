"""Grid-lane scaling: aggregate throughput of the
vmapped reg-weight sweep vs lane count, on either headline leg.

The sparse leg is the flagship question: if the single-lane 10M-feature
solve is bound by its d-length L-BFGS state rather than by X work (the
traffic model of benches/roofline.py; not measured on the current chip),
lanes that share every X pass should multiply rows·iters/s until the
(G, d) solver state saturates HBM. Timing closes with an O(1)-byte readback (device_results=True):
fetching the (G, 10M) coefficient block would put a G×40 MB device→host
copy inside the timed region.

Run: python benches/grid_lanes.py --leg sparse --lanes 1 2 4 8
     python benches/grid_lanes.py --leg dense  --lanes 8 16 32
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
    p.add_argument("--leg", choices=["sparse", "dense"], default="sparse")
    p.add_argument("--lanes", type=int, nargs="+", default=[1, 2, 4, 8])
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--rows", type=int, default=None,
                   help="sparse-leg row count (default bench.S_ROWS)")
    p.add_argument("--history-dtype", default=None,
                   help="lane solver S/Y storage dtype (e.g. bfloat16); "
                        "prints per-lane final losses for the quality A/B")
    p.add_argument("--reg", choices=["l2", "elastic"], default="l2",
                   help="elastic = elastic_net(0.5): the sweep rides the "
                        "lane-minor OWL-QN road (L1 production shape)")
    p.add_argument("--opt", choices=["lbfgs", "tron"], default="lbfgs",
                   help="tron: the sweep rides the lane-minor margin-"
                        "cached TRON (smooth reg only)")
    args = p.parse_args()
    if args.opt == "tron" and args.reg == "elastic":
        # lane_weight_arrays force-routes any L1 sweep to OWL-QN (upstream
        # rule), so this combination would silently measure the OWL-QN
        # solver under a TRON label.
        p.error("--opt tron requires --reg l2 (L1 sweeps always run OWL-QN)")
    if args.opt == "tron" and args.leg == "sparse":
        import bench  # the guard must track the sparse leg's REAL default

        if (args.rows or bench.S_ROWS) > 1 << 20:
            # docs/PERF.md: the TRON lane program at the 2M-row shape
            # reproducibly crashes the remote-compile service; 1M compiles
            # and runs. Refuse the documented-fatal default instead of
            # taking the shared compiler down.
            p.error("--opt tron on the sparse leg needs --rows <= 1048576 "
                    "(the 2M-row TRON lane program kills the remote "
                    "compile service; docs/PERF.md)")

    import jax
    import jax.numpy as jnp

    import bench
    from photon_tpu.models.training import train_glm_grid
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig, OptimizerType
    from photon_tpu.optim.regularization import elastic_net, l2

    if args.leg == "sparse":
        rows = args.rows or bench.S_ROWS
        t0 = time.perf_counter()
        batch, _ = bench.sparse_problem(rows=rows)
        jax.block_until_ready(batch.X.dense)
        print(f"sparse problem ({rows} rows x {bench.S_FEATURES} features) "
              f"loaded in {time.perf_counter() - t0:.0f}s")
        iters_cfg = bench.S_ITERS
    else:
        rows = bench.D_ROWS
        batch = bench.dense_problem()
        jax.block_until_ready(batch.X)
        iters_cfg = bench.D_ITERS
    cfg = OptimizerConfig(
        optimizer=(OptimizerType.TRON if args.opt == "tron"
                   else OptimizerType.LBFGS),
        max_iters=iters_cfg, tolerance=0.0,
        reg=elastic_net(0.5) if args.reg == "elastic" else l2(),
        reg_weight=0.0, history=5,
        lane_history_dtype=args.history_dtype)

    dev = jax.devices()[0]
    for g in args.lanes:
        weights = list(np.geomspace(1e-4, 1e-2, g)) if g > 1 else [1e-3]

        def run():
            res, _ = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION,
                                    cfg, weights, device_results=True)
            # O(1)-byte readback closes the timing (see module docstring);
            # the (G,) final losses ride along for the quality A/B.
            return jax.device_get((jnp.sum(res.w), jnp.sum(res.iterations),
                                   res.value))

        try:
            t0 = time.perf_counter()
            _, iters, losses = run()  # compile + autotune
            t_compile = time.perf_counter() - t0
            best = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                _, iters, losses = run()
                best = min(best, time.perf_counter() - t0)
        except Exception as e:  # OOM at some G is an answer, not a crash
            print(f"G={g:3d}: FAILED ({type(e).__name__}: {str(e)[:200]})")
            continue
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", 0) / 2**30
        agg = rows * int(iters) / best
        print(f"G={g:3d}: {best * 1e3:7.0f} ms  {int(iters):4d} lane-iters  "
              f"{agg:.3e} rows*iters/s aggregate  "
              f"({agg / g:.3e}/lane, compile {t_compile:.0f}s, "
              f"peak HBM {peak:.1f} GiB)")
        print(f"       final losses: "
              + " ".join(f"{v:.8e}" for v in np.asarray(losses)))


if __name__ == "__main__":
    main()
