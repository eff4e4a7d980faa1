"""Whole GLM solves on a ROW-SHARDED resident blocked-ELL batch, one shard
a chip, back to back: `train_glm(batch, task, cfg, mesh=mesh)` — the scalar
margin-cached L-BFGS of `glm_solve`'s one-lane cells under `shard_map`,
coefficients and solver state replicated, one all-reduce of the (value,
gradient) pair an evaluation. A unit is one whole solve closed by an
O(1)-byte readback — never the 10M-wide ``w``.

The FIRST thing `setup` does is a probe at the configuration's rehearse
sizes on the real mesh: the sharded builder has to hand the hot block
back with one addressable shard a device. A program that assembles the
whole block on one device (every program before the shard-by-shard build)
fails there in seconds, with that message — not minutes later in a 17 GB
allocation on a 16 GB chip.

`check` holds the solve to one float64 pass over all the rows
(`gen/reference_blocked.py`) — and, beside the summed losses, the
program's own evaluation code to the per-row margins and the first
gradient of that pass, which is where a precision step shows. The
``xpass`` traced section times that same sharded value-and-gradient bare.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark.gen import reference, reference_blocked, sparse_mesh
from benchmark.lib.xpass_bytes import xpass_evaluation_bytes
# the same annotations, and the same rate over all the work of the window
from benchmark.traffic.glm_solve import GAP_LABELS, metrics  # noqa: F401

# every device holds a quarter of every sharded leaf and a whole copy of
# what is replicated: their bytes in use differ by allocator rounding only
BYTES_IN_USE_RTOL = 0.15
LOST_SHARDS = 1  # the lost-shard control leaves out the last shard's rows
# the per-row margins (‖program − float64‖ / ‖float64‖) and the first
# gradient (`reference_blocked.gradient_error`), at points where the X
# pass's operand casts are exact (gen/reference_blocked.py). A program
# that stores bf16 and accumulates f32 reads f32 summation noise there,
# and in the margins about 1e-5 on the chip: where a row repeats a hot
# column the block holds the f32 sum of the repeats rounded to bf16, and
# one such sum in 10^5 lies so near a bf16 tie that the order of the f32
# additions decides it. One precision step lost reads a bf16 roundoff
# (2^-9: 1.6e-3 to 3.9e-3 in these norms). Each limit is the geometric
# middle of its two readings on the chip; all of them in PERF.md §2.
MARGIN_RTOL = 2.0 ** -13
GRAD0_RTOL = 2.0 ** -14


@dataclasses.dataclass
class State:
    batch: object
    mesh: object
    coo: tuple            # host (indices, values, labels) for the reference
    task: object
    cfg: object
    lam: float
    rows: int
    n_shards: int
    params: dict
    clocks: dict          # host-clock seconds of set-up steps
    facts: dict           # what the per-layer readers and `check` keep
    programs: dict = dataclasses.field(default_factory=dict)  # compiled once


def _sizes(config: dict) -> dict:
    return {"rows": int(config["n_rows"]),
            "features": int(config["n_features"]),
            "nnz": int(config["nnz_per_row"]),
            "zipf": float(config["zipf_exponent"]),
            "hot_signal": int(config["planted_signal_columns"]),
            "n_shards": int(config["n_shards"])}


def probe_shard_by_shard_build(config: dict, mesh, cache_dir: str) -> None:
    """Raise unless `shard_blocked_ell_batch(..., mesh=mesh)` hands back a
    hot block with one addressable shard on each device of ``mesh``, at
    the configuration's rehearse sizes (seconds)."""
    import jax.numpy as jnp

    from photon_tpu.data.dataset import make_batch, shard_blocked_ell_batch
    from photon_tpu.data.matrix import SparseRows

    tiny = {**config, **config.get("rehearse", {})}
    small = _sizes(tiny)
    n_shards = small.pop("n_shards")
    message = ("glm_mesh_solve: this program does not build a sharded "
               "blocked-ELL batch shard by shard — ")
    ind, va, y = sparse_mesh.sharded_coo(1, n_shards=n_shards,
                                         cache_dir=cache_dir, **small)
    try:
        X = shard_blocked_ell_batch(
            make_batch(SparseRows(ind, va, small["features"]), y), n_shards,
            d_dense=int(tiny["hot_block_columns"]),
            device_dense_dtype=jnp.bfloat16, mesh=mesh).X
    except TypeError as e:
        raise SystemExit(message + "`shard_blocked_ell_batch` takes no "
                         f"mesh ({e}); at {config['n_rows']} rows the whole "
                         "hot block would be assembled on one device and "
                         "does not fit it") from e
    shards = getattr(X.dense, "addressable_shards", [])
    devices = {s.device for s in shards}
    if len(shards) != n_shards or devices != set(mesh.devices.flat):
        raise SystemExit(
            message + f"the hot block came back on {len(devices)} "
            f"device(s) in {len(shards)} shard(s), not one on each of the "
            f"mesh's {n_shards}")


def one_shard_xpass_bytes(X) -> dict:
    """`lib/xpass_bytes.py`'s count for what ONE chip moves an evaluation:
    its shard of every sharded leaf at the common PADDED shapes, the whole
    of the replicated ``w`` and gradient."""
    import types

    import jax

    S = int(X.n_shards)

    def cut(a):   # (n, ...) rows over the shards
        return jax.ShapeDtypeStruct((a.shape[0] // S,) + tuple(a.shape[1:]),
                                    a.dtype)

    def drop(a):  # (S, ...) one slice a shard
        return jax.ShapeDtypeStruct(tuple(a.shape[1:]), a.dtype)

    return xpass_evaluation_bytes(types.SimpleNamespace(
        shape=(int(X.shape[0]) // S, int(X.shape[1])), dense=cut(X.dense),
        ell_pcols=[drop(a) for a in X.ell_pcols],
        ell_vals=[drop(a) for a in X.ell_vals], row_pos=drop(X.row_pos),
        bucket_rows=[drop(a) for a in X.bucket_rows],
        bucket_vals=[drop(a) for a in X.bucket_vals]), 1)


def _bytes_in_use(mesh) -> list:
    """`bytes_in_use` of every device of the mesh; None where the backend
    reports no memory stats (the CPU rehearsal)."""
    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in mesh.devices.flat]


def setup(config: dict, params: dict, seed: int, dirs: dict) -> State:
    import jax

    from photon_tpu import telemetry
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2
    from photon_tpu.parallel.mesh import make_mesh

    sizes = _sizes(config)
    n_shards = sizes["n_shards"]
    if len(jax.devices()) < n_shards:
        raise SystemExit(f"glm_mesh_solve: {n_shards} shards need "
                         f"{n_shards} devices, jax sees "
                         f"{len(jax.devices())}")
    if int(params["lanes"]) != 1:
        raise ValueError("glm_mesh_solve runs the single lane")
    mesh = make_mesh(n_devices=n_shards)
    t0 = time.perf_counter()
    probe_shard_by_shard_build(config, mesh, dirs["shared"])
    t1 = time.perf_counter()
    ind, va, y = sparse_mesh.sharded_coo(seed, cache_dir=dirs["shared"],
                                         **sizes)
    t2 = time.perf_counter()
    with telemetry.run("glm_mesh_solve.setup") as run:
        batch = sparse_mesh.sharded_batch(
            ind, va, y, sizes["features"],
            int(config["hot_block_columns"]), mesh)
        jax.block_until_ready(batch)
        report = run.report_compact()
    t3 = time.perf_counter()
    lam = float(params["reg_weight"])
    cfg = OptimizerConfig(
        max_iters=int(params["max_iters"]),
        tolerance=float(params["tolerance"]), reg=l2(), reg_weight=lam,
        history=int(params["history"]))
    shards = batch.X.dense.addressable_shards
    return State(
        batch=batch, mesh=mesh, coo=(ind, va, y),
        task=TaskType[config["task"]], cfg=cfg, lam=lam,
        rows=sizes["rows"], n_shards=n_shards, params=params,
        clocks={"probe_s": t1 - t0, "generate_s": t2 - t1,
                "layout_build_s": t3 - t2,
                "shard_build_s": sum(
                    v for k, v in report["span_totals"].items()
                    if k.split("/")[-1] == "layout.shard_build")},
        facts={"build_counters": {
                   k: v for k, v in report["counters"].items()
                   if k.startswith("layout.shard_bytes_")},
               "xpass_bytes": one_shard_xpass_bytes(batch.X),
               "hot_block_shards": len(shards),
               "hot_block_devices": len({s.device for s in shards}),
               "bytes_in_use": _bytes_in_use(mesh)})


def unit(state: State, keep: bool = False) -> dict:
    """One whole sharded solve. ``work`` is ALL the rows × the iterations
    taken."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.models.training import train_glm

    with jax.profiler.TraceAnnotation("bench.solve"):
        _, res = train_glm(state.batch, state.task, state.cfg,
                           mesh=state.mesh)
        small = (jnp.sum(res.w), res.iterations, res.value, res.failed)
    with jax.profiler.TraceAnnotation("bench.readback"):
        _, iters, value, bad = jax.device_get(small)
    iterations = int(iters)
    out = {"work": float(state.rows) * iterations,
           "iterations": iterations, "steps": iterations,
           "failed": bool(bad) or not bool(np.isfinite(value))}
    if keep:
        out["evidence"] = {"w": np.asarray(res.w), "value": float(value),
                           "history": np.asarray(res.loss_history)}
    return out


def _sharded(fn, batch, mesh, out_specs):
    """``fn(obj, local batch, w)`` of every shard under `shard_map`, the
    objective and ``w`` replicated — as `models.training.
    _contract_sharded_vg` wraps the value-and-gradient."""
    import jax
    from jax.sharding import PartitionSpec as P

    from photon_tpu.models.training import _hybrid_specs
    from photon_tpu.parallel.mesh import shard_map

    spec = _hybrid_specs(batch.X, tuple(mesh.axis_names))

    def run(obj, b, w):
        return shard_map(
            lambda o, b, w: fn(o, b._replace(X=b.X.local()), w), mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(), obj), spec, P()),
            out_specs=out_specs)(obj, b, w)

    return run


def evaluators(state: State) -> dict:
    """The program's own evaluation code over this batch, compiled once a
    run: "vg" the sharded value-and-gradient every solver iteration runs
    (`_contract_sharded_vg`; the `xpass` section times it, `check` counts
    its all-reduces and reads the first gradient from it), "margins" the
    objective's per-row margins; "args" builds their arguments from
    layout-order coefficients."""
    if state.programs:
        return state.programs
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from photon_tpu.models.training import (_contract_sharded_vg,
                                            make_objective)
    from photon_tpu.parallel.mesh import replicated

    batch, mesh = state.batch, state.mesh
    d = int(batch.X.shape[1])
    obj = make_objective(state.task, state.cfg, d,
                         axis_name=mesh.axis_names[0],
                         intercept_index=batch.X.last_col_pos)

    def args(w):
        return obj, batch, jax.device_put(jnp.asarray(w, jnp.float32),
                                          replicated(mesh))

    zero = args(np.zeros(d, np.float32))
    state.programs.update(
        args=args,
        vg=jax.jit(_contract_sharded_vg(batch, mesh)).lower(*zero).compile(),
        margins=jax.jit(_sharded(
            lambda o, b, w: o.margin(w, b), batch, mesh,
            P(tuple(mesh.axis_names)))).lower(*zero).compile())
    return state.programs


def traced_sections(state: State) -> list:
    """``xpass``: bare sharded value-and-gradient evaluations at w = 0 —
    one shard's X pass a chip and the one all-reduce, nothing of the
    solver. Compiled here, before the trace starts."""
    import jax

    programs = evaluators(state)
    zero = programs["args"](np.zeros(int(state.batch.X.shape[1]),
                                     np.float32))
    jax.block_until_ready(programs["vg"](*zero))
    n = int(state.params["xpass_evaluations"])

    def xpass():
        for _ in range(n):
            out = programs["vg"](*zero)
        jax.block_until_ready(out)
        return {"evaluations": n}

    return [("xpass", xpass)]


def probe(state: State, w) -> dict:
    """{"margins": (n,), "grad0": (d,)} as the PROGRAM computes them, model
    column order: the margins at `reference_blocked.probe_coefficients(w)`
    and the gradient at w = 0."""
    X = state.batch.X
    programs = evaluators(state)
    perm, inv = np.asarray(X.perm_cols), np.asarray(X.inv_perm)
    wq = reference_blocked.probe_coefficients(w, X.dense.dtype)
    margins = programs["margins"](*programs["args"](wq[perm]))
    _, grad0 = programs["vg"](*programs["args"](np.zeros_like(wq)))
    return {"margins": np.asarray(margins, np.float64),
            "grad0": np.asarray(grad0, np.float64)[inv]}


LIMITS = (("loss0_rel", reference.LOSS0_RTOL),
          ("final_rel", reference.LOSS_RTOL),
          ("margin_rel", MARGIN_RTOL), ("grad0_rel", GRAD0_RTOL))


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare(program: dict, ref: dict, lam: float, rows: int) -> dict:
    """One comparison of what a program gave — "history", "value", "w",
    "margins", "grad0" — with a reference pass ``ref`` over ``rows`` rows
    (the first ``rows`` margins are compared): the verdict with its
    numbers, and the limits a reading is over."""
    w64 = np.asarray(program["w"], np.float64)
    lane = reference.check_lane(
        program["history"], program["value"], w64,
        reference_blocked.objective(ref["loss"], w64, lam),
        rows * float(np.log(2.0)))
    lane["margin_rel"] = _rel(program["margins"][:rows],
                              ref["margins"][:rows])
    lane["grad0_rel"] = reference_blocked.gradient_error(
        program["grad0"], ref["grad0"], ref["grad0_scale"])
    refused_by = [name for name, limit in LIMITS if lane[name] > limit]
    if not lane["monotone"]:
        refused_by.append("monotone")
    return {**lane, "ok": not refused_by, "refused_by": refused_by}


def check(state: State, evidence: dict) -> dict:
    """The warm-up solve and the program's probe against ONE float64 pass
    in row blocks over all the rows, values as stored: loss at w = 0 is
    n·log 2; the reported final loss is the objective at the returned
    ``w``; losses never rise; the per-row margins and the first gradient
    are the reference's to f32 summation noise (`MARGIN_RTOL`,
    `GRAD0_RTOL`); one all-reduce an evaluation; every device holds its
    share. Then three CONTROLS go through the SAME comparison and each has
    to be refused, or the run is not correct: the reference over all
    shards but the last (a lost shard), the reference over values NOT
    rounded to the stored bf16, and — in the program's place — the
    reference computed one precision step down (bf16 products and
    results). ``evidence`` may bring the probe's readings (a test plants
    faults there); else they are taken here, outside window and set-up."""
    ind, va, y = state.coo
    S, rows = state.n_shards, state.rows
    t0 = time.perf_counter()
    if "margins" not in evidence:
        evidence = {**evidence, **probe(state, evidence["w"])}
    t1 = time.perf_counter()
    X = state.batch.X
    ref = reference_blocked.shard_pass(
        ind, va, y, evidence["w"], S, X.dense.dtype,
        np.asarray(X.perm_cols)[:int(X.dense.shape[1])])
    t2 = time.perf_counter()
    whole = {name: {**r, "grad0": np.sum(r["grad0"], axis=0)}
             for name, r in ref.items() if name != "lower"}
    kept = S - LOST_SHARDS
    lost = {**whole["stored"], "loss": ref["stored"]["loss"][:kept],
            "grad0": np.sum(ref["stored"]["grad0"][:kept], axis=0)}
    low = ref["lower"]
    lower = {**evidence, "margins": low["margins"], "grad0": low["grad0"],
             "value": float(reference.stored(reference_blocked.objective(
                 low["loss"], evidence["w"], state.lam),
                 state.batch.X.dense.dtype))}
    fit = compare(evidence, whole["stored"], state.lam, rows)
    controls = {
        "lost_shard": compare(evidence, lost, state.lam, rows // S * kept),
        "unrounded": compare(evidence, whole["unrounded"], state.lam, rows),
        "lower_precision": compare(lower, whole["stored"], state.lam, rows)}
    text = evaluators(state)["vg"].as_text()
    from photon_tpu.analysis import hlo_all_reduce_count

    all_reduces = hlo_all_reduce_count(text)
    in_use = state.facts["bytes_in_use"]
    if any(b is None for b in in_use):
        balanced, spread = True, None
    else:
        mean = sum(in_use) / len(in_use)
        spread = max(abs(b - mean) for b in in_use) / mean
        balanced = spread <= BYTES_IN_USE_RTOL
    one_shard_a_device = (state.facts["hot_block_shards"] == S
                          and state.facts["hot_block_devices"] == S)
    controls_refused = not any(c["ok"] for c in controls.values())
    return {"ok": (fit["ok"] and controls_refused and all_reduces == 1
                   and balanced and one_shard_a_device),
            "fit": fit, "controls": controls,
            "controls_refused": controls_refused,
            "all_reduces_per_evaluation": all_reduces,
            "bytes_in_use": in_use, "bytes_in_use_spread": spread,
            "hot_block_shards": state.facts["hot_block_shards"],
            "probe_s": t1 - t0, "reference_s": t2 - t1}
