"""Whole GLM solves on a resident blocked-ELL batch, back to back.

``lanes`` > 1: `train_glm_grid` over the lanes' L2 weights (one lock-step
program, lanes share every X pass). ``lanes`` == 1: `train_glm` (the scalar
margin-cached L-BFGS). Settings are bench.py's `run_sparse_grid` /
`run_sparse`. A unit is one whole solve closed by an O(1)-byte readback —
never the 10M-wide ``w``.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark.gen import reference, sparse
from benchmark.lib.xpass_bytes import xpass_evaluation_bytes

GAP_LABELS = {"bench.solve": "solve", "bench.readback": "readback"}


@dataclasses.dataclass
class State:
    batch: object
    coo: tuple            # host (indices, values, labels) for the reference
    task: object
    cfg: object
    lams: list            # one L2 weight per lane
    rows: int
    params: dict
    clocks: dict          # host-clock seconds of set-up steps
    facts: dict           # shape-derived facts for the per-layer readers


def setup(config: dict, params: dict, seed: int, dirs: dict) -> State:
    import jax

    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    rows, d = int(config["n_rows"]), int(config["n_features"])
    t0 = time.perf_counter()
    ind, va, y = sparse.sparse_coo(
        seed, rows, d, int(config["nnz_per_row"]),
        float(config["zipf_exponent"]),
        int(config["planted_signal_columns"]), dirs["shared"])
    t1 = time.perf_counter()
    batch = sparse.sparse_batch(ind, va, y, d,
                                int(config["hot_block_columns"]))
    jax.block_until_ready(batch)
    t2 = time.perf_counter()
    lanes = int(params["lanes"])
    if lanes == 1:
        lams = [float(params["reg_weight"])]
        cfg = OptimizerConfig(
            max_iters=int(params["max_iters"]),
            tolerance=float(params["tolerance"]), reg=l2(),
            reg_weight=lams[0], history=int(params["history"]))
    else:
        lo, hi, n = params["reg_weights_geomspace"]
        lams = [float(v) for v in np.geomspace(lo, hi, int(n))]
        if len(lams) != lanes:
            raise ValueError("reg_weights_geomspace does not give "
                             f"{lanes} lanes")
        cfg = OptimizerConfig(
            max_iters=int(params["max_iters"]),
            tolerance=float(params["tolerance"]), reg=l2(), reg_weight=0.0,
            history=int(params["history"]),
            lane_history_dtype=params.get("lane_history_dtype"))
    return State(
        batch=batch, coo=(ind, va, y),
        task=TaskType[config["task"]], cfg=cfg, lams=lams, rows=rows,
        params=params,
        clocks={"generate_s": t1 - t0, "layout_build_s": t2 - t1},
        facts={"xpass_bytes": xpass_evaluation_bytes(batch.X, lanes)})


def unit(state: State, keep: bool = False) -> dict:
    """One whole solve. ``work`` is rows × iterations taken, summed over
    lanes."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.models.training import train_glm, train_glm_grid

    with jax.profiler.TraceAnnotation("bench.solve"):
        if len(state.lams) == 1:
            _, res = train_glm(state.batch, state.task, state.cfg)
        else:
            res, _ = train_glm_grid(state.batch, state.task, state.cfg,
                                    state.lams, device_results=True)
        small = (jnp.sum(res.w), res.iterations, res.value, res.failed)
    with jax.profiler.TraceAnnotation("bench.readback"):
        _, iters, value, bad = jax.device_get(small)
    iterations = int(np.sum(iters))
    out = {"work": float(state.rows) * iterations,
           "iterations": iterations, "steps": int(np.max(iters)),
           "failed": bool(np.any(bad)) or not bool(
               np.all(np.isfinite(value)))}
    if keep:
        # first and last lane: two (d,) vectors and the histories, pulled
        # to the host here so nothing of the warm-up stays on the device
        G = len(state.lams)
        lanes = sorted({0, G - 1})
        w = np.asarray(res.w).reshape(G, -1) if G == 1 else np.stack(
            [np.asarray(res.w[g]) for g in lanes])
        out["evidence"] = {
            "lanes": lanes, "w": w,
            "value": np.asarray(res.value).reshape(G)[lanes],
            "history": np.asarray(res.loss_history).reshape(G, -1)[lanes]}
    return out


def metrics(state: State, units: list, elapsed_s: float) -> dict:
    """rows·iterations per second over ALL the work and ALL the time of
    the window (host clock; every unit closed by its readback)."""
    return {"rows_iters_per_s": sum(r["work"] for _, r in units) / elapsed_s}


def check(state: State, evidence: dict) -> dict:
    """Loss at w = 0 is n·log 2; each kept lane's reported final loss is the
    float64 numpy objective at that lane's w; losses never rise."""
    ind, va, y = state.coo
    va64 = reference.stored(va, state.batch.X.dense.dtype)
    n_log2 = state.rows * float(np.log(2.0))
    lanes = []
    for g, w, value, hist in zip(evidence["lanes"], evidence["w"],
                                 evidence["value"], evidence["history"]):
        w64 = np.asarray(w, np.float64)
        ref = reference.np_logistic_objective(
            np.einsum("nk,nk->n", va64, w64[ind]), y, w64, state.lams[g])
        lanes.append({"lane": g,
                      **reference.check_lane(hist, value, w64, ref, n_log2)})
    return {"ok": all(v["ok"] for v in lanes), "lanes": lanes}


def traced_sections(state: State) -> list:
    """``xpass``: bare value-and-gradient evaluations at a fixed w, the
    objective built as the solvers build it (`make_objective`, as
    chip_smoke.all_reduces_per_evaluation does). Compiled here, before the
    trace starts."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.models.training import lane_weight_arrays, make_objective
    from photon_tpu.ops import lane_objective

    X = state.batch.X
    d, G = int(X.shape[1]), len(state.lams)
    obj = make_objective(state.task, state.cfg, d,
                         intercept_index=X.last_col_pos)
    if G == 1:
        w = jnp.zeros((d,), jnp.float32)
        evaluate = jax.jit(lambda o, b, w: o.value_and_grad(w, b))
        args = (obj, state.batch, w)
    else:
        l2s, _, _ = lane_weight_arrays(state.cfg, state.lams)
        w = jnp.zeros((d, G), jnp.float32)

        def lanes_vg(o, b, l2v, W):
            z = lane_objective.margin_lanes(o, W, b)
            return lane_objective.value_and_grad_at_margin_lanes(
                o, l2v, W, z, b)

        evaluate = jax.jit(lanes_vg)
        args = (obj, state.batch, l2s, w)
    jax.block_until_ready(evaluate(*args))
    n = int(state.params["xpass_evaluations"])

    def xpass():
        for _ in range(n):
            out = evaluate(*args)
        jax.block_until_ready(out)
        return {"evaluations": n}

    return [("xpass", xpass)]
