"""Fresh-process time-to-first-result for the lane-grid solve: tracing
(re-paid every process) vs an AOT export replay (utils/aot.py).

Both modes enable the persistent XLA compilation cache, so the A/B
isolates exactly the cost jax.export removes: trace + lower. Protocol —
run each mode twice in FRESH processes; the second invocation is the
measurement (first populates the XLA cache / AOT store):

    python benches/aot_glm.py --aot off   # populate, then again: measure
    python benches/aot_glm.py --aot on    # populate, then again: measure

Row count is deliberately small (524k): tracing/lowering cost depends on
the program structure, not the row count, and the data build would
otherwise dominate the wall clock.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--aot", choices=["on", "off"], default="off")
    p.add_argument("--rows", type=int, default=1 << 19)
    p.add_argument("--dir", default="/tmp/photon_aot_bench")
    args = p.parse_args()

    from photon_tpu.utils.compile_cache import enable_compilation_cache

    os.makedirs(args.dir, exist_ok=True)
    enable_compilation_cache()

    import jax
    import jax.numpy as jnp

    import bench
    from photon_tpu.models.training import (_lane_solve, lane_weight_arrays,
                                            make_objective)
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    t0 = time.perf_counter()
    batch, _ = bench.sparse_problem(rows=args.rows)
    jax.block_until_ready(batch.X.dense)
    t_data = time.perf_counter() - t0

    cfg = OptimizerConfig(max_iters=bench.S_ITERS, tolerance=0.0, reg=l2(),
                          reg_weight=0.0, history=5,
                          lane_history_dtype="bfloat16")
    weights = list(bench.S_GRID)
    l2s, l1s, static_cfg = lane_weight_arrays(cfg, weights)
    d = batch.X.n_features
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d)
    w0 = jnp.zeros((d,), jnp.float32)

    def fn(batch, w0, obj, l2s):
        return _lane_solve(obj, batch, w0, l2s, None, static_cfg)

    t0 = time.perf_counter()
    if args.aot == "on":
        from photon_tpu.utils.aot import AotStore

        store = AotStore(os.path.join(args.dir, "aot"))
        # The key carries the closure-captured static config: avals alone
        # can't see it, and a stale replay would silently measure the old
        # program (AotStore.call docstring).
        res = store.call(f"lane_grid@{args.rows}x{d}|{static_cfg}", fn,
                         batch, w0, obj, l2s)
    else:
        res = jax.jit(fn)(batch, w0, obj, l2s)
    jax.device_get(jnp.sum(res.w))
    t_first = time.perf_counter() - t0
    print(f"aot={args.aot}: data {t_data:.1f}s, "
          f"first result {t_first:.1f}s (trace+compile+solve)")


if __name__ == "__main__":
    main()
