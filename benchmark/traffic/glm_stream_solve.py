"""Whole GLM solves over a HOST chunk ladder streamed through ONE chip, back
to back: `train_glm(chunked_batch, task, cfg)` — the normal path hands a
`ChunkedBatch` to `optim/streamed.py`'s host-looped margin-cached L-BFGS,
every evaluation one pass of all the chunks over the host link
(`DeviceChunkRing`, two in flight), one aggregate a pass. The data set is
never resident: it is larger than the chip. A unit is one whole solve
closed by an O(1)-byte readback — never the 10M-wide ``w``.

The FIRST thing `setup` does is a probe at the configuration's ``probe``
sizes (seconds, 34 MB): the ladder has to be built in its stored dtype
piece by piece. A program that builds the hot block as one float32 array
and recasts it chunk by chunk afterwards (every program before the
piece-by-piece build) is refused there by its `tracemalloc` peak, with
that message — not minutes later in a 34 GB allocation that a 40 GiB host
answers by swapping, or by killing the run.

`check` holds the solve to one float64 pass over all the rows
(`gen/reference_stream.py`) with the mesh cell's comparison
(`glm_mesh_solve.compare`: the summed losses, and the per-row margins and
the first gradient taken through the solve's own upload ring from the
three CHUNK programs a unit runs: `probe`), the
mesh cell's three controls with a chunk where it has a shard, and what
streaming adds: every pass consumed every chunk once, the bytes the
program says it uploaded are the ladder's, and the device never held the
data set.
"""
from __future__ import annotations

import dataclasses
import resource
import sys
import time
import tracemalloc

import numpy as np

from benchmark.gen import reference, reference_blocked, reference_stream
from benchmark.gen import sparse_stream
from benchmark.lib.stream_bytes import chunk_upload_bytes, ladder_bytes
# the same annotations, the same rate over all the work of the window, and
# the mesh cell's comparison with its limits (their reasons are there: a
# chunk's programs are a shard's, at the same shapes)
from benchmark.traffic.glm_solve import GAP_LABELS, metrics  # noqa: F401
from benchmark.traffic.glm_mesh_solve import (GRAD0_RTOL,  # noqa: F401
                                              LIMITS, MARGIN_RTOL, compare)

LOST_CHUNKS = 1  # the lost-chunk control leaves out the last chunk's rows
# The probe's limit: what the build may hold at its peak BESIDE the ladder
# it returns, in hot blocks of one chunk. A piece-by-piece build holds the
# (n, k) index and mask arrays of the host pass and one scatter piece:
# under one block at the probe's sizes. A whole-block float32 build holds
# the ladder twice over (8 blocks at four chunks) and its float64 scatter
# scratch besides. Both readings are in PERF.md §6.
PROBE_BLOCKS = 3.0
# `memory_peak_bytes` has to show the ring and the solver state on the chip
# (the driver's floor for a new cell) and may not reach the ladder's bytes
MEMORY_FLOOR = 0.25


@dataclasses.dataclass
class State:
    batch: object         # the host ChunkedBatch
    coo: dict             # `sparse_stream.chunked_coo`'s arguments: the
    #                       reference draws the rows again (2.2 GB that a
    #                       40 GiB host cannot keep beside the ladder)
    task: object
    cfg: object
    lam: float
    rows: int
    n_chunks: int
    params: dict
    clocks: dict          # host-clock seconds of set-up steps (and, so
    #                       that the set-up line prints them, the host's
    #                       MemTotal and the process's peak RSS in bytes)
    facts: dict           # what the per-layer readers and `check` keep


def _sizes(config: dict) -> dict:
    rows, chunk_rows = int(config["n_rows"]), int(config["chunk_rows"])
    if rows % chunk_rows != 0:
        raise ValueError(f"{rows} rows do not divide into chunks of "
                         f"{chunk_rows}")
    return {"rows": rows, "features": int(config["n_features"]),
            "nnz": int(config["nnz_per_row"]),
            "zipf": float(config["zipf_exponent"]),
            "hot_signal": int(config["planted_signal_columns"]),
            "n_chunks": rows // chunk_rows}


def _ladder(config: dict, seed: int, cache_dir: str):
    """(the generator's arguments, ChunkedBatch) of a configuration's
    sizes; the host COO is let go once the ladder is laid."""
    import jax.numpy as jnp

    sizes = _sizes(config)
    recipe = {"seed": seed, "cache_dir": cache_dir, **sizes}
    ind, va, y = sparse_stream.chunked_coo(**recipe)
    batch = sparse_stream.chunked_batch(
        ind, va, y, sizes["features"], int(config["hot_block_columns"]),
        int(config["chunk_rows"]), jnp.dtype(config["feature_dtype"]))
    return recipe, batch


def probe_piece_by_piece_build(config: dict, cache_dir: str) -> dict:
    """Raise unless `chunk_blocked_ell` builds the ladder of the
    configuration's ``probe`` sizes holding, at its `tracemalloc` peak, no
    more than `PROBE_BLOCKS` chunks' hot blocks beside the ladder it
    returns. Returns the reading."""
    small = {**config, **config["probe"]}
    sizes = _sizes(small)
    ind, va, y = sparse_stream.chunked_coo(1, cache_dir=cache_dir, **sizes)
    import jax.numpy as jnp

    tracemalloc.start()
    try:
        batch = sparse_stream.chunked_batch(
            ind, va, y, sizes["features"], int(small["hot_block_columns"]),
            int(small["chunk_rows"]), jnp.dtype(small["feature_dtype"]))
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    block = chunk_upload_bytes(batch)["hot_block"]
    beside = (peak - ladder_bytes(batch)) / block
    if batch.X.chunks[0].dense.dtype != jnp.dtype(small["feature_dtype"]):
        raise SystemExit("glm_stream_solve: the ladder's hot block came "
                         f"back as {batch.X.chunks[0].dense.dtype}, not "
                         f"{small['feature_dtype']}")
    if beside > PROBE_BLOCKS:
        raise SystemExit(
            "glm_stream_solve: this program does not build a host chunk "
            "ladder in its stored dtype piece by piece — at "
            f"{sizes['rows']} rows x {small['hot_block_columns']} hot "
            f"columns the build held {beside:.1f} chunks' hot blocks "
            f"beside the ladder it returned (limit {PROBE_BLOCKS}); at "
            f"{config['n_rows']} rows that is a float32 block of "
            f"{int(config['n_rows']) * int(config['hot_block_columns']) * 4 / 1e9:.1f}"
            " GB on the host")
    return {"probe_blocks_beside": beside}


def _mem_total_bytes():
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


def _peak_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


# Compiling the chunk programs leaves 5.7 GB of freed compiler memory in
# the process (a checkout's first run: 32.7 → 38.4 GB beside the ladder, my
# chip run, PR 34), and a traced run's profiler takes 6.5 GB more on a 40
# GiB host: what the warm-up left is handed back. Only where it left
# something — pages handed back are faulted in again by the next solve, at
# 1.15 s a GB on that host (0.36 GB returned cost the window's first unit
# 0.3–0.45 s of its 32.4).
TRIM_OVER_BYTES = 1 << 30


def _return_freed_memory() -> None:
    """Hand the allocator's free pages back to the host (`malloc_trim`); a
    libc without it changes nothing."""
    import ctypes

    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def _device_peak_bytes():
    import jax

    from benchmark.lib import harness

    return harness.memory_peak_bytes(jax)


def _rss_bytes():
    """The process's resident bytes now; None where /proc has none."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


def setup(config: dict, params: dict, seed: int, dirs: dict) -> State:
    from photon_tpu import telemetry
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    if int(params["lanes"]) != 1:
        raise ValueError("glm_stream_solve runs the single lane")
    t0 = time.perf_counter()
    probed = probe_piece_by_piece_build(config, dirs["shared"])
    t1 = time.perf_counter()
    rss_before = _rss_bytes()
    sizes = _sizes(config)
    with telemetry.run("glm_stream_solve.setup") as run:
        coo, batch = _ladder(config, seed, dirs["shared"])
        report = run.report_compact()
    t2 = time.perf_counter()
    build_s = sum(v for k, v in report["span_totals"].items()
                  if k.split("/")[-1] == "layout.shard_build")
    lam = float(params["reg_weight"])
    cfg = OptimizerConfig(
        max_iters=int(params["max_iters"]),
        tolerance=float(params["tolerance"]), reg=l2(), reg_weight=lam,
        history=int(params["history"]))
    chunk = chunk_upload_bytes(batch)
    return State(
        batch=batch, coo=coo, task=TaskType[config["task"]], cfg=cfg,
        lam=lam, rows=sizes["rows"], n_chunks=sizes["n_chunks"],
        params=params,
        clocks={"probe_s": t1 - t0, "generate_s": t2 - t1 - build_s,
                "layout_build_s": build_s,
                "host_mem_total_bytes": _mem_total_bytes(),
                "rss_before_ladder_bytes": rss_before,
                "rss_with_ladder_bytes": _rss_bytes(),
                "setup_peak_rss_bytes": _peak_rss_bytes()},
        facts={**probed, "chunk_bytes": chunk,
               "ladder_bytes": ladder_bytes(batch),
               "storage_dtype": batch.X.chunks[0].dense.dtype,
               "hot_columns": np.asarray(batch.X.perm_cols)[
                   :int(batch.X.chunks[0].dense.shape[1])],
               "build_counters": {k: v for k, v in
                                  report["counters"].items()
                                  if k.startswith("layout.")}})


def unit(state: State, keep: bool = False) -> dict:
    """One whole streamed solve. ``work`` is ALL the rows × the iterations
    taken. With ``keep`` the solve runs under a telemetry run of its own
    and the evidence carries the program's ``stream.*`` / ``solver.*``
    counters beside the result."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from photon_tpu import telemetry
    from photon_tpu.models.training import train_glm

    counted = (telemetry.run("glm_stream_solve.warmup") if keep
               else contextlib.nullcontext())
    with counted as run:
        with jax.profiler.TraceAnnotation("bench.solve"):
            _, res = train_glm(state.batch, state.task, state.cfg)
            small = (jnp.sum(res.w), res.iterations, res.value, res.failed,
                     res.evaluations)
        with jax.profiler.TraceAnnotation("bench.readback"):
            _, iters, value, bad, trials = jax.device_get(small)
        counters = run.report_compact()["counters"] if keep else None
    if keep:  # the warm-up compiled every program: outside the window
        rss = {"solved": _rss_bytes()}
        left = (rss["solved"] or 0) - (
            state.clocks["rss_with_ladder_bytes"] or 0)
        if left > TRIM_OVER_BYTES:
            _return_freed_memory()
            rss["trimmed"] = _rss_bytes()
    iterations = int(iters)
    out = {"work": float(state.rows) * iterations,
           "iterations": iterations, "steps": iterations,
           "failed": bool(bad) or not bool(np.isfinite(value))}
    if keep:
        out["evidence"] = {"w": np.asarray(res.w), "value": float(value),
                           "history": np.asarray(res.loss_history),
                           "iterations": iterations, "trials": int(trials),
                           "counters": counters, "warmup_rss_bytes": rss,
                           "warmup_device_peak_bytes": _device_peak_bytes()}
    return out


def probe(state: State, w) -> dict:
    """{"margins", "grad0", "margins_init", "grad0_init"} as the PROGRAM
    computes them, model column order — from the programs and the ring the
    window drives: `optim.streamed`'s own backend (`_SingleDeviceStream`:
    its `DeviceChunkRing`, its donated chunk programs), the objective built
    as `train_glm_streamed` builds it, four passes of the ladder as the
    solve makes them.

    A dz pass (`_chunk_dz_phi_don`, 40 of a unit's 84 chunk programs) with
    the direction `reference_blocked.probe_coefficients(w)` at cached
    margins 0 and step 0: its dz IS the per-row margins X·wq. A gradient
    pass at cached margins (`_chunk_grad_at_margin_don`, 40 of 84) at
    margins 0, summed by `_acc` and closed by `_finish` at w = 0: the first
    gradient. Then the first pass's program (`_chunk_init_don`, 4 of 84)
    at wq and at 0 for the same two readings. At these points every
    operand cast of the X pass is exact (wq is on the stored dtype's grid;
    the residual at margin 0 is ±w/2), so a reading past f32 summation
    noise is a pass that lost precision, or a ring that handed out the
    wrong rows."""
    import jax.numpy as jnp

    from photon_tpu.models.training import make_objective
    from photon_tpu.optim import streamed

    batch = state.batch
    X = batch.X
    obj = make_objective(state.task, state.cfg, int(X.n_features),
                         intercept_index=X.last_col_pos)
    perm, inv = np.asarray(X.perm_cols), np.asarray(X.inv_perm)
    wq = reference_blocked.probe_coefficients(w, X.chunks[0].dense.dtype)
    p = jnp.asarray(wq[perm], jnp.float32)
    w0 = jnp.zeros_like(p)
    z0 = np.zeros(batch.chunk_rows, np.float32)

    def summed(parts_of):
        acc = None
        for _, b in be.iter_chunks():
            parts = parts_of(b)
            acc = parts if acc is None else streamed._acc(acc, parts)
        return np.asarray(be.finish(obj, w0, acc)[1], np.float64)[inv]

    be = streamed._backend(batch, None, 2)
    try:
        margins = np.concatenate([be.chunk_dz_phi(obj, p, z0, 0.0, b)[0]
                                  for _, b in be.iter_chunks()])
        grad0 = summed(lambda b: be.chunk_grad(obj, z0, b))
        margins_init = np.concatenate([be.chunk_init(obj, p, b)[0]
                                       for _, b in be.iter_chunks()])
        grad0_init = summed(lambda b: be.chunk_init(obj, w0, b)[1])
    finally:
        be.close()
    return {"margins": np.asarray(margins, np.float64), "grad0": grad0,
            "margins_init": np.asarray(margins_init, np.float64),
            "grad0_init": grad0_init}


def stream_verdict(state: State, evidence: dict) -> dict:
    """What the program's own counters of the warm-up solve say of its
    passes: every pass consumed every chunk once, there were as many as
    the solver asked for (one at the start, one or two an iteration), and
    the bytes it says it uploaded are those chunks' (this module's count,
    `lib/stream_bytes.py`)."""
    c = evidence["counters"]
    passes = c.get("stream.passes", 0.0)
    uploads = c.get("stream.chunk_uploads", 0.0)
    its = evidence["iterations"]
    out = {"passes": passes, "chunk_uploads": uploads,
           "upload_bytes": c.get("stream.upload_bytes"),
           "feature_streams": c.get("solver.feature_streams"),
           "chunk_bytes": state.facts["chunk_bytes"]["total"],
           "result_trials": evidence["trials"],
           "counted_trials": c.get("solver.linesearch_trials")}
    out["ok"] = bool(
        passes == out["feature_streams"]
        and 1 + its <= passes <= 1 + 2 * its
        and uploads == state.n_chunks * passes
        and out["upload_bytes"] == uploads * out["chunk_bytes"]
        and c.get("solver.iterations") == its
        and out["counted_trials"] == out["result_trials"] >= its)
    return out


def memory_verdict(state: State) -> dict:
    """`memory_peak_bytes` lies between a quarter of the chip and the
    ladder's bytes: the ring and the solver state are on the chip and the
    data set is not. A backend that reports no memory (the rehearsal) is
    not judged."""
    import jax

    from benchmark.lib import harness
    from benchmark.lib.peaks import DEVICE_PEAKS

    peak = harness.memory_peak_bytes(jax)
    kind = jax.devices()[0].device_kind
    out = {"memory_peak_bytes": peak,
           "ladder_bytes": state.facts["ladder_bytes"]}
    if peak is None or kind not in DEVICE_PEAKS:
        return {**out, "ok": True}
    chip = DEVICE_PEAKS[kind]["hbm_bytes"]
    return {**out, "chip_bytes": chip,
            "ok": bool(MEMORY_FLOOR * chip <= peak < out["ladder_bytes"])}


def _release_ladder(state: State) -> None:
    """Let the host ladder go NOW: a hot block in pinned host memory
    (`data.matrix.PinnedRows`, an accelerator's ladder) is freed piece by
    piece, not when the last view of it is collected."""
    import jax

    for leaf in jax.tree_util.tree_leaves(state.batch.X.chunks):
        if not isinstance(leaf, np.ndarray) and hasattr(leaf, "delete"):
            leaf.delete()
    state.batch = None


def check(state: State, evidence: dict) -> dict:
    """The warm-up solve and the program's probe against ONE float64 pass
    in row blocks over all the rows, values as stored — the mesh cell's
    comparison (`glm_mesh_solve.compare`: n·log 2 at w = 0, the final loss
    at the returned ``w``, losses never rise, margins and first gradient
    to f32 summation noise — ``fit`` holds the dz and gradient passes'
    readings to it, ``fit_init`` the first pass's program's) — then its
    three CONTROLS through the SAME
    comparison, each of which has to be refused or the run is not correct:
    the reference over all chunks but the last (a chunk the ring dropped,
    or streamed twice in place of another), the reference over values NOT
    rounded to the stored bf16, and — in the program's place — the
    reference computed one precision step down. Then `stream_verdict` and
    `memory_verdict`. ``evidence`` may bring the probe's readings (a test
    plants faults there); else they are taken here, outside window and
    set-up — and then the ladder is let go: the run's last pass over it is
    the probe's, and on a 40 GiB host the float64 pass needs the room (a
    TPU process holds some 14 GB of the host before it holds any data)."""
    S, rows = state.n_chunks, state.rows
    dtype = state.facts["storage_dtype"]
    rss = {"warmup": evidence.get("warmup_rss_bytes"),
           "check": _rss_bytes()}
    device_peak = {"warmup": evidence.get("warmup_device_peak_bytes"),
                   "window": _device_peak_bytes()}
    t0 = time.perf_counter()
    if "margins" not in evidence:
        evidence = {**evidence, **probe(state, evidence["w"])}
        rss["probed"] = _rss_bytes()
        _release_ladder(state)
        rss["ladder_released"] = _rss_bytes()
        print(f"glm_stream_solve.check: host rss {rss}", file=sys.stderr,
              flush=True)
    t1 = time.perf_counter()  # reference_s holds the rows' second draw
    ind, va, y = sparse_stream.chunked_coo(**state.coo)
    rss["coo_drawn"] = _rss_bytes()
    ref = reference_stream.chunk_pass(
        ind, va, y, evidence["w"], S, dtype, state.facts["hot_columns"])
    rss["reference"] = _rss_bytes()
    t2 = time.perf_counter()
    whole = {name: {**r, "grad0": np.sum(r["grad0"], axis=0)}
             for name, r in ref.items() if name != "lower"}
    kept = S - LOST_CHUNKS
    lost = {**whole["stored"], "loss": ref["stored"]["loss"][:kept],
            "grad0": np.sum(ref["stored"]["grad0"][:kept], axis=0)}
    low = ref["lower"]
    lower = {**evidence, "margins": low["margins"], "grad0": low["grad0"],
             "value": float(reference.stored(reference_blocked.objective(
                 low["loss"], evidence["w"], state.lam), dtype))}
    fit = compare(evidence, whole["stored"], state.lam, rows)
    fit_init = compare({**evidence, "margins": evidence["margins_init"],
                        "grad0": evidence["grad0_init"]},
                       whole["stored"], state.lam, rows)
    controls = {
        "lost_chunk": compare(evidence, lost, state.lam, rows // S * kept),
        "unrounded": compare(evidence, whole["unrounded"], state.lam, rows),
        "lower_precision": compare(lower, whole["stored"], state.lam, rows)}
    controls_refused = not any(c["ok"] for c in controls.values())
    stream = stream_verdict(state, evidence)
    memory = memory_verdict(state)
    return {"ok": (fit["ok"] and fit_init["ok"] and controls_refused
                   and stream["ok"] and memory["ok"]),
            "fit": fit, "fit_init": fit_init, "controls": controls,
            "controls_refused": controls_refused,
            "stream": stream, "memory": memory,
            "host_mem_total_bytes": state.clocks["host_mem_total_bytes"],
            "rss_bytes": rss, "peak_rss_bytes": _peak_rss_bytes(),
            "device_peak_bytes": device_peak,
            "probe_s": t1 - t0, "reference_s": t2 - t1}
