"""Run telemetry spine: spans, counters/gauges, and a live per-iteration
solver stream across the resident/streamed/mesh/GAME paths.

The reference leans on Spark's UI + event log (per-stage timing, driver
diagnostics via `PhotonLogger`, `OptimizationStatesTracker`,
`util.Timer`); this package is the TPU port's runtime half of that story:
one process-wide `Run` recorder the instrumented hot paths report into.

::

    from photon_tpu import telemetry

    with telemetry.run("flagship", jsonl_path="out/run.jsonl") as r:
        train_glm(batch, task, config)          # streamed solves emit
    report = r.report()                          # live iteration events

Three primitives (see `run.Run`): nestable host-side **spans** (also fed
to `jax.profiler.TraceAnnotation`, so they appear on XProf timelines;
`utils.timing.PhaseTimers` forwards the drivers' phase blocks here
automatically), **counters/gauges** (the streamed chunk pipeline's
`stream.*` family — passes/chunk_uploads/upload_bytes/
uploads_behind_compute/stall_seconds/
issue_seconds/compute_seconds/stalled_passes counters beside the
prefetch_depth gauge (stream.upload_bytes: the host bytes of the chunks a
pass consumed, every leaf `device_put` is handed; stream.issue_seconds:
the host seconds a pass spent handing chunks to the runtime;
stream.compute_seconds: the rest of the pass's wall once
stream.stall_seconds and stream.issue_seconds are taken off, the
consumer's own time — the three add up to the wall;
stream.uploads_behind_compute: the uploads the ring issued while the
chunk program it had just been told of was still running), with one
`stream.pass` span around every pass a streamed solver makes over the
chunks, its ``kind`` attribute init / dz / gradient / refresh /
value_grad / ladder and ``n`` the pass's number in its solve, and under
it one timeline a consumed chunk, each span opened where the host does
the work: `stream.upload` (`DeviceChunkRing`: each upload call;
``chunk``, the ladder index, ``behind``, whether the program it was
issued behind was still running), `stream.handout` (the wait for the
chunk about to be handed out; ``chunk``), `stream.release` (the wait for
a consumed chunk's program and the freeing of its leaves, and `close()`'s
wait for what a solve primed and never read; ``chunk``),
`stream.dispatch` (the one-device backend: the chunk program's call
alone; ``program`` init / grad / dz_phi / value_many — the ring's spans
are its siblings, not its children), `stream.readback` (``what``:
margins, a chunk's per-row output read to the host; totals, the program
and the scalars that close a pass), and between the passes of the
streamed L-BFGS `solve.host_step` (``part`` direction / linesearch /
update; OWL-QN's loop opens none). ONE measurement, two sinks:
stream.issue_seconds and stream.stall_seconds are the sums of the
`stream.upload` and `stream.handout` spans' own clock readings; with no
run attached every one of these sites is the shared no-op span. A span's
scalar attributes go to its `TraceAnnotation` as the event's stats, the
event's name stays the bare path; the streamed
solver loops' `solver.*` family — iterations/evaluations/
feature_streams/linesearch_trials plus the margin_cache.hits/
margin_cache.refreshes cache pair; retrace.new_signatures riding
`analysis.TraceSignatureLog`; the GAME descent's `game.*` —
sweeps/coordinate_updates/grid_points; the training driver's
train.dataset_estimate_bytes/train.hbm_budget_bytes gauges; the chunked
scoring driver's score.chunks/score.rows; the ingest scan's
ingest.chunks/ingest.rows/ingest.device_shards plus the multi-process
spine's ingest.chunks_skipped (blocks another rank decodes instead);
the random-effect block pipeline's `game_re.*` family —
blocks/blocks_in_flight/readback_wait_ns plus the straggler compaction's
straggler_entities/tail_resolves/iters_saved and the fused-update gate's
fused_gate_offs, with per-block upload/solve/readback/tail_solve spans;
block_bytes_real/block_bytes_padded (a dataset build's bucket plan: the
bytes its entities' capped rows × solve widths hold, and the bytes of the
padded blocks allocated for them), and from the one-dispatch update, as
`count_device` values, row_iterations (Σ over entities of weight-carrying
rows × solver iterations taken), moved_row_iterations (the part of it
whose iteration lowered its lane's loss: the rest repeated a point),
iterations / linesearch_trials (Σ over entities of solver iterations and
of line-search evaluations) and block_steps (Σ over blocks of the
block's lock-step iterations: the largest over its lanes), and per
update on the host block_scored_rows / table_scored_rows (the training
rows whose margin came from a bucket's block pass, and from the table
gather: together the rows × the updates) and warm_carried / warm_cold /
warm_adopted (the one-dispatch updates by where their warm starts came
from: the buckets' solutions of the descent's previous update, zeros, or
a caller's table through the `game_re.adopt` program), and of an
incremental fit's prior and variances: variance_lanes (entities whose
variances an update computed), prior_seen / prior_unseen (entities with
and without a row in the prior model, once a coordinate a descent) and
fused_prior_updates (one-dispatch updates that carried a prior), beside
the fixed effect's game_fixed.row_iterations (rows × iterations taken);
the blocked-ELL builds' `layout.*` family — tail_nnz / ell_slots /
occ_slots (`data.matrix.to_blocked_ell` and `shard_blocked_ell`: the
tail's real nonzeros and the slots the ELL row buckets and the occurrence
buckets hold for them under the width ladder, summed over shards), and of
the sharded build alone shard_bytes_real / shard_bytes_padded (the bytes
of the shards' ELL and occurrence buckets each laid out to its own shapes,
and padded to the common shapes every shard shares), with one
`layout.shard_build` span a build — and the mesh solve's mesh.psum_bytes
(`models.training.train_glm(mesh=)`: the payload bytes of the gradient
all-reduces of one sharded L-BFGS solve — the f32 gradient's bytes,
static from its shape, × (iterations + 1), the iterations read from the
result through `count_device`; the lane sweep, OWL-QN and TRON on a mesh
are not counted: no cell or test reads them yet);
the pod-scale GAME composition's `game_e2e.*` family —
streamed_fixed_updates/host_offset_sums/objective_chunks counters from
the descent loop's host-margin-cache exchange,
score_stream_chunks/score_stream_rows from the streamed coordinate
scorer, chunked_fit_points from the estimator, and pod_scale_runs from
the training driver; the online serving tier's
`serving.*` family — requests/batches/batch_rows/pad_waste/cold_misses/
hot_swaps counters (pad_waste is shared with the offline chunked scorer;
hot_swaps counts `CoefficientStore.reload_coefficients` cutovers),
quant_refusals (a quantized ProgramLadder's warmup accuracy gate
breached its epsilon — the ladder refused to serve), the
overload-round admission counters admitted/shed/deadline_expired
(admitted = entered the queue; shed = watermark or bounded-submit
drops; deadline_expired = admitted but dropped before a batch slot —
each resolves its Future to a typed `serving.Shed`) and the replica
fleet's fleet_dispatches/fleet_failovers/fleet_degraded counters with
the fleet_replicas gauge,
queue_depth/batch_fill/latency_p50_ms/latency_p95_ms/latency_p99_ms
gauges, per-flush `serving.flush` spans, and one `serving_batch` event
per dispatched micro-batch; the elastic-runs `checkpoint.*` family —
snapshots/bytes/restores plus the per-layer scope_restores/
solver_restores/re_restores/descent_restores and gc_snapshots, with
`checkpoint.pack`/`checkpoint.write` spans — and its `faults.*` sibling
— injected_kills/injected_errors/io_retries/backoff_seconds — the
continual-flywheel `continual.*` family — plans/touched_entities/
deferred_new_keys counters from delta ingestion (deferred_new_keys also
logs at INFO — the new-entity-admission breadcrumb),
touched_buckets/skipped_buckets/refresh_solves/refresh_iterations/
refreshes from the partial re-solve, probe_entities/swap_refusals from
the parity-probed hot swap (the in-process cutover itself counts on
`serving.hot_swaps`), with delta_diff/refresh/refresh_coordinate/
refresh_solve/probe/swap spans — the
the continual flywheel's staleness_s gauge (rows-changed → servable
seconds, gauged by `continual/swap.py::hot_swap(rows_changed_unix=...)`
at cutover — the model-freshness number `telemetry.health` exports) — the
grouped-evaluation `eval.*` family — scatter_elems_saved, the elements
per metric call that would have entered combining scatters before the
round-12 sorted-segment rework of `evaluation/grouped.py` — the
round-14 ingest-plane additions to the `ingest.*` family —
worker_chunks/worker_deaths counters and the workers gauge from the
sharded decode pool (a death = one chunk degraded to in-process
decode), cache_hits/cache_misses/cache_builds/cache_commits/
cache_chunks/cache_bytes/cache_invalid from the decode-once chunk
cache — the lane-batched tuner's `tuning.*` family —
rounds/configs/survivor_resolves counters and the
round_model_flops gauge (the modeled FLOPs `profiling.model.estimate_fn`
priced the round's lane dispatch at, published BEFORE dispatch so a
budget breach is attributable), with one `tuning.round` span per
GP-propose/screen/halve/re-solve round — with the stall-driven prefetch's
stream.prefetch_widened/stream.prefetch_narrowed counters and one
`prefetch_decision` event per depth verdict beside the existing
stream.prefetch_depth gauge — and HBM
watermarks — the hbm.bytes_in_use.max / hbm.peak_bytes_in_use.max
gauge pair, with per-tag suffixes), and the
**iteration stream** — one event per solver
iteration, free in the streamed/mesh host loops and opt-in for the jitted
resident solvers via `Run(resident_tap=True)` (a `jax.debug.callback`
compiled out by default; the registered `telemetry_off_is_free`
ContractSpec enforces exactly that).

**Device scopes** (`DEVICE_SCOPES`, entered with `device_scope(name)` =
`jax.named_scope`): names written into the compiled programs' op metadata
(the HLO ``op_name`` path), always on and free at run time, so a profiler
trace can put device time to a phase INSIDE one jitted solve. Flat dotted
names, grouped by prefix: the X pass — xpass.fwd / xpass.t (the public
`data/matrix.py` dispatchers, forward and transposed), xpass.fwd.hot /
xpass.t.hot (the hot-block matmuls), xpass.fwd.tail / xpass.t.tail (the
blocked-ELL gathers; for a layout whose rows are stored in concatenation
order, `to_blocked_ell`'s, also the concatenate-and-add that lays the
forward tail over the rows), xpass.fwd.reassemble (the `row_pos` gather:
per evaluation only over a shard / chunk view, whose rows stay in the
caller's order; over a stored-order layout only where public `matvec`
hands a result back in the caller's order, never inside a solve);
objective.loss (loss value / derivative at cached margins); the L-BFGS
phases lbfgs.two_loop, lbfgs.push, lbfgs.linesearch, lbfgs.direction
(descent test and ``dphi0``), lbfgs.update (accepted step, convergence,
history write); solve.prologue / solve.epilogue (before and after the
`while_loop`, and the lane-minor → lane-major transpose); the phases of a
coordinate-descent update — game_re.gather (offsets laid into a bucket's
rows; the warm starts are handed to the update in the buckets' own space,
not read from the table), game_re.solve (the bucket's vmapped per-entity
solves: the L-BFGS and X-pass scopes nest under it), game_re.scatter
(results written to the (E, d) table through the bucket's index map),
game_re.score (per-row margins: block passes for the rows
the buckets hold, the table gather for the rest, one reassembly gather),
game_re.adopt (a program of its own, once a coordinate a descent at most:
a CALLER's table enters the descent — warm starts read through the index
maps, the columns outside them cleared; never in a cold-start fit),
game_re.prior (a program of its own, once an incremental coordinate a
descent: the prior model's means and variances gathered into the buckets'
own spaces), game_re.variance (the buckets' per-entity variances: Gram,
Cholesky factor, the diagonal of the inverse), game_fixed.solve (the
fixed effect's solve, same nesting), game_fixed.variance (its variances)
and
game.objective (offsets sum and the tracking objective); and mesh.psum
(the objective's all-reduces over the mesh axis — `Objective._psum` /
`_psum_many`, so the scalar and the lane objective alike — entered only
where an axis name is set: a one-device solve traces none). The resident
solves report the `solver.*` pair iterations / linesearch_trials through
`count_device` — a counter whose value is still a device array: the
attached `Run` keeps the reference and resolves every pending array in
ONE `device_get` when a report is asked for, never at the call site.
`device_trace(dir)` is the one way to take a profiler trace (Python
tracer off, host tracer at the level that keeps `TraceAnnotation`s).

The multi-process spine's `parallel.*` span family holds one timed
barrier span — ``parallel.barrier_wait``, opened by
`parallel/mesh.py::cluster_barrier` — whose per-rank totals are what
`telemetry.aggregate` reads to name the straggler rank.

Sinks: `Run.report()` (in-memory dict), a JSONL event file
(`sinks.read_jsonl` / `sinks.load_report`), and a human end-of-run
summary through `photon_logger` at close.

The observability plane on top of the spine (round 19):
`telemetry.trace` — per-request distributed tracing (trace id +
causally-ordered hops across the dispatcher's submit→queue→flush→retire
threads and the fleet's failover attempts) with a bounded reservoir of
tail exemplars, OFF by default and pinned free-when-off by the
``serving_trace_off_is_free`` ContractSpec; `telemetry.aggregate` —
cross-rank JSONL merge into one cluster report (per-rank rollups,
barrier-wait/decode skew attribution, wall-clock-aligned timelines);
`telemetry.health` — fixed-size quantile digests, counter-rate windows,
declarative watchdog rules (OK/DEGRADED/CRITICAL), and the staleness
gauge, exported as JSON + Prometheus textfile via ``python -m
photon_tpu.telemetry --health``.

THE OFF-STATE CONTRACT: every module-level helper here starts with
``if _CURRENT is None: return`` — a run-less process pays one global load
and one branch per instrumentation point, and the resident solver
programs contain no callback at all (docs/OBSERVABILITY.md).

CLI: ``python -m photon_tpu.telemetry --selftest`` smoke-checks the
spine (sink round-trip + the off-is-free contract) and exits non-zero on
failure.

This docstring is the HUMAN registry of telemetry names; the
machine-readable twin is :data:`TELEMETRY_REGISTRY` at the bottom of
this module. ``python -m photon_tpu.lint``'s ``telemetry_sync`` rule
holds all three sides: every counter/gauge literal the package emits is
in the registry, every registry name is emitted somewhere, and every
registry name appears in this docstring.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

from photon_tpu.telemetry.run import Run, Span  # noqa: F401
from photon_tpu.telemetry.sinks import (  # noqa: F401
    load_report,
    read_jsonl,
    repair_jsonl_tail,
)
from photon_tpu.telemetry.taps import (  # noqa: F401
    set_resident_tap,
    solver_tap,
    tap_disabled,
    tap_enabled,
)

__all__ = [
    "Run", "Span", "read_jsonl", "load_report",
    "start_run", "finish_run", "run", "current_run", "enabled",
    "span", "count", "count_device", "gauge", "iteration", "event",
    "record_signature", "sample_device_memory",
    "DEVICE_SCOPES", "device_scope", "device_trace",
    "solver_tap", "tap_enabled", "set_resident_tap", "tap_disabled",
]

_CURRENT: Optional[Run] = None
_ATTACH_LOCK = threading.Lock()


# ------------------------------------------------------------- run lifecycle
def start_run(name: str = "run", jsonl_path: Optional[str] = None,
              resident_tap: bool = False, logger=None,
              append: bool = False) -> Run:
    """Create a Run and attach it as the process-wide current run. One run
    at a time: starting while one is attached finishes the old one first
    (runs are process-scoped, like the reference's one Spark UI per app)."""
    global _CURRENT
    # construct (and close the displaced run) OUTSIDE the attach lock:
    # Run() opens the JSONL sink and close() flushes it — file IO a
    # concurrent counter bump must never wait behind (blocking_under_lock)
    r = Run(name=name, jsonl_path=jsonl_path, resident_tap=resident_tap,
            logger=logger, append=append)
    with _ATTACH_LOCK:
        old, _CURRENT = _CURRENT, r
        set_resident_tap(resident_tap)
    if old is not None:
        old.close()
    return r


def finish_run() -> Optional[dict]:
    """Close and detach the current run; returns its final report."""
    global _CURRENT
    with _ATTACH_LOCK:
        r, _CURRENT = _CURRENT, None
        set_resident_tap(False)
    return r.close() if r is not None else None


@contextlib.contextmanager
def run(name: str = "run", jsonl_path: Optional[str] = None,
        resident_tap: bool = False, logger=None, append: bool = False):
    """`with telemetry.run(...) as r:` — start_run/finish_run scoped."""
    r = start_run(name, jsonl_path=jsonl_path, resident_tap=resident_tap,
                  logger=logger, append=append)
    try:
        yield r
    finally:
        if _CURRENT is r:
            finish_run()
        else:  # someone else already replaced it; still close ours
            r.close()


def current_run() -> Optional[Run]:
    return _CURRENT


def enabled() -> bool:
    return _CURRENT is not None


# ----------------------------------------------------- hot-path entry points
# Each of these is the ONE branch a run-less process pays. They bind the
# run locally (the attach lock is for attach/detach; readers race benignly
# — an event lands in whichever run was current when it fired).

class _NullSpan:
    """Shared no-op span context manager for the disabled state."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return None


_NULL_SPAN = _NullSpan()


def span(name: str, **attrs):
    r = _CURRENT
    if r is None:
        return _NULL_SPAN
    return r.span(name, **attrs)


def count(name: str, value: float = 1.0) -> None:
    r = _CURRENT
    if r is not None:
        r.count(name, value)


def count_device(name: str, value, reduce: str = "sum",
                 scale: float = 1.0) -> None:
    """`count` for a value that is still a DEVICE array, reduced over its
    elements by ``reduce`` ("sum" or "max") on the host and multiplied
    there by ``scale``. The run keeps the reference and reads every
    pending array back in one `device_get` when a report is asked for —
    never here, so the dispatch that produced ``value`` stays
    asynchronous."""
    r = _CURRENT
    if r is not None:
        r.count_device(name, value, reduce, scale)


def gauge(name: str, value) -> None:
    r = _CURRENT
    if r is not None:
        r.gauge(name, value)


def iteration(solver: str, it: int, loss, grad_norm=None, step=None,
              trials=None, **extra) -> None:
    r = _CURRENT
    if r is not None:
        r.iteration(solver, it, loss, grad_norm=grad_norm, step=step,
                    trials=trials, **extra)


def event(kind: str, **fields) -> None:
    r = _CURRENT
    if r is not None:
        r.event(kind, **fields)


def record_signature(program: str, args) -> None:
    r = _CURRENT
    if r is not None:
        r.record_signature(program, args)


def sample_device_memory(tag: str = "") -> None:
    r = _CURRENT
    if r is not None:
        r.sample_device_memory(tag)


def device_scope(name: str):
    """`jax.named_scope(name)` for a name of `DEVICE_SCOPES`: context
    manager or decorator. The name lands in the HLO ``op_name`` of every
    op traced inside (metadata only: no primitive, no run-time cost), and
    is checked here, at trace time, so a trace reader can rely on the
    registry being the whole vocabulary."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"device scope {name!r} is not in "
                         "telemetry.DEVICE_SCOPES")
    import jax

    return jax.named_scope(name)


@contextlib.contextmanager
def device_trace(log_dir: str):
    """A profiler trace of the enclosed region under ``log_dir``, with the
    Python tracer off (its events swamp the device's) and the host tracer
    at the lowest level that still records `TraceAnnotation`s — so this
    package's spans and the `DEVICE_SCOPES` share the trace's one clock."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# The machine-readable twin of the docstring's name registry (a pure
# literal: photon_tpu.lint reads it by AST, without importing jax).
# Entries ending in ".*" / "_*" are prefix globs for dynamically
# suffixed names (per-site retry counters, per-percentile latency
# gauges, per-tag HBM watermarks). `span_families` lists the allowed
# prefix (before the first dot) of every `telemetry.span(...)` name the
# package opens — `utils.timing.PhaseTimers(span_prefix=...)` routes the
# drivers' phase blocks into the "train" and "score" families.
# `device_scopes` is the whole vocabulary of `device_scope(...)`.
TELEMETRY_REGISTRY = {
    "counters": (
        "faults.injected_kills", "faults.injected_errors",
        "faults.io_retries", "faults.io_retries.*",
        "faults.backoff_seconds",
        "checkpoint.snapshots", "checkpoint.bytes", "checkpoint.restores",
        "checkpoint.scope_restores", "checkpoint.solver_restores",
        "checkpoint.re_restores", "checkpoint.descent_restores",
        "checkpoint.gc_snapshots",
        "continual.plans", "continual.touched_entities",
        "continual.deferred_new_keys", "continual.refreshes",
        "continual.touched_buckets", "continual.skipped_buckets",
        "continual.refresh_solves", "continual.refresh_iterations",
        "continual.probe_entities", "continual.swap_refusals",
        "ingest.chunks", "ingest.rows", "ingest.device_shards",
        "ingest.chunks_skipped",
        "ingest.worker_chunks", "ingest.worker_deaths",
        "ingest.cache_hits", "ingest.cache_misses", "ingest.cache_builds",
        "ingest.cache_commits", "ingest.cache_chunks",
        "ingest.cache_bytes", "ingest.cache_invalid",
        "stream.passes", "stream.chunk_uploads", "stream.upload_bytes",
        "stream.uploads_behind_compute", "stream.stall_seconds", "stream.issue_seconds",
        "stream.compute_seconds", "stream.stalled_passes",
        "stream.prefetch_widened", "stream.prefetch_narrowed",
        "solver.iterations", "solver.evaluations",
        "solver.feature_streams", "solver.linesearch_trials",
        "solver.margin_cache.hits", "solver.margin_cache.refreshes",
        "retrace.new_signatures",
        "score.chunks", "score.rows",
        "serving.requests", "serving.batches", "serving.batch_rows",
        "serving.pad_waste", "serving.cold_misses", "serving.hot_swaps",
        "serving.quant_refusals", "serving.admitted", "serving.shed",
        "serving.deadline_expired", "serving.fleet_dispatches",
        "serving.fleet_failovers", "serving.fleet_degraded",
        "game.sweeps", "game.coordinate_updates", "game.grid_points",
        "game_re.blocks", "game_re.readback_wait_ns",
        "game_re.straggler_entities", "game_re.tail_resolves",
        "game_re.iters_saved", "game_re.fused_gate_offs",
        "game_re.block_bytes_real", "game_re.block_bytes_padded",
        "game_re.row_iterations", "game_re.block_steps",
        "game_re.moved_row_iterations", "game_re.iterations",
        "game_re.linesearch_trials",
        "game_re.block_scored_rows", "game_re.table_scored_rows",
        "game_re.warm_carried", "game_re.warm_cold", "game_re.warm_adopted",
        "game_re.variance_lanes", "game_re.prior_seen",
        "game_re.prior_unseen", "game_re.fused_prior_updates",
        "game_fixed.row_iterations",
        "layout.shard_bytes_real", "layout.shard_bytes_padded",
        "layout.tail_nnz", "layout.ell_slots", "layout.occ_slots",
        "mesh.psum_bytes",
        "game_e2e.pod_scale_runs", "game_e2e.streamed_fixed_updates",
        "game_e2e.objective_chunks",
        "game_e2e.host_offset_sums", "game_e2e.score_stream_chunks",
        "game_e2e.score_stream_rows", "game_e2e.chunked_fit_points",
        "eval.scatter_elems_saved",
        "tuning.rounds", "tuning.configs", "tuning.survivor_resolves",
    ),
    "gauges": (
        "stream.prefetch_depth", "ingest.workers",
        "train.dataset_estimate_bytes", "train.hbm_budget_bytes",
        "game_re.blocks_in_flight",
        "serving.queue_depth", "serving.batch_fill",
        "serving.latency_*", "serving.fleet_replicas",
        "hbm.bytes_in_use.max*", "hbm.peak_bytes_in_use.max*",
        "tuning.round_model_flops",
        "continual.staleness_s",
    ),
    "span_families": (
        "train", "score", "ingest", "solve",
        "game", "game_re", "serving", "checkpoint", "continual",
        "tuning", "parallel", "layout", "stream",
    ),
    "device_scopes": (
        "xpass.fwd", "xpass.fwd.hot", "xpass.fwd.tail",
        "xpass.fwd.reassemble",
        "xpass.t", "xpass.t.hot", "xpass.t.tail",
        "objective.loss",
        "lbfgs.two_loop", "lbfgs.push", "lbfgs.linesearch",
        "lbfgs.direction", "lbfgs.update",
        "solve.prologue", "solve.epilogue",
        "game_re.gather", "game_re.solve", "game_re.scatter",
        "game_re.score", "game_re.adopt", "game_re.prior",
        "game_re.variance", "game_fixed.solve", "game_fixed.variance",
        "game.objective",
        "mesh.psum",
    ),
}
DEVICE_SCOPES = TELEMETRY_REGISTRY["device_scopes"]
