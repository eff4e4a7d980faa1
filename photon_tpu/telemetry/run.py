"""The `Run` recorder: one process-wide telemetry spine for a training or
scoring run.

Reference parity: photon-ml leans on Spark's UI + event log plus its own
`PhotonLogger` / `OptimizationStatesTracker` / `util.Timer` for the "what
did this run do and where did the time go" story. The TPU-native analog is
one structured recorder with three primitives:

- **spans** — nestable host-side timed scopes (`time.perf_counter_ns`
  start/stop). Every span also enters a `jax.profiler.TraceAnnotation`,
  so the same names appear on the XProf/TensorBoard trace timeline next
  to the device ops they launched. `utils.timing.Timer`/`PhaseTimers`
  feed spans automatically, so the drivers' existing `with timers(...)`
  blocks show up without extra wiring.
- **counters / gauges** — monotonic totals (chunk uploads, stall
  seconds, evaluations, line-search trials, margin-cache hits, ...) and
  last-value gauges (prefetch depth, HBM watermarks). Thread-safe: the
  streaming prefetchers and any caller threads may bump them
  concurrently.
- **iteration stream** — one event per solver iteration (loss,
  grad_norm, step, line-search trials), emitted LIVE from the streamed/
  mesh host driver loops, and from the jitted resident solvers through
  the opt-in `jax.debug.callback` tap (`telemetry.taps` — compiled out
  by default; the `telemetry_off_is_free` ContractSpec pins that).

Sinks: the in-memory `Run.report()` dict, an optional JSONL event file
(one JSON object per line — spans, iteration events, counter/gauge
snapshot, run start/end), and a human end-of-run summary through
`photon_logger` at `Run.close()`.

The HOT-PATH contract: every instrumentation point in data/optim/game
first does a module-level ``if _CURRENT is None: return`` (see
`__init__.py`), so a run-less process pays one global load + one branch
per call site and never touches jax, locks, or files. Nothing here ever
adds a device transfer or collective at a call site: spans/counters are
host bookkeeping around already-host-side loops, the resident tap exists
only in programs traced while it is armed, and `count_device` keeps a
REFERENCE to a device array — the one `device_get` that resolves all of
them happens when a report is asked for (`report`, `report_compact`,
`close`).
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import threading
import time
from typing import Any, Optional

__all__ = ["Run", "Span"]


@dataclasses.dataclass
class Span:
    """One completed (or still-open) timed scope."""

    name: str
    path: str  # "/"-joined enclosing span names + own name
    start_ns: int
    end_ns: Optional[int] = None
    depth: int = 0
    attrs: dict = dataclasses.field(default_factory=dict)
    error: Optional[str] = None  # exception type name, when one escaped

    @property
    def seconds(self) -> float:
        end = self.end_ns if self.end_ns is not None else time.perf_counter_ns()
        return (end - self.start_ns) / 1e9

    def to_json(self, t0_ns: Optional[int] = None) -> dict:
        """The span's record; with the run's start ``t0_ns`` also ``t_s``,
        the span's start as an offset from it, so that the records are a
        timeline (name, start, length, parent path)."""
        out = {"type": "span", "name": self.name, "path": self.path,
               "seconds": round(self.seconds, 6), "depth": self.depth}
        if t0_ns is not None:
            out["t_s"] = round((self.start_ns - t0_ns) / 1e9, 6)
        if self.attrs:
            out["attrs"] = self.attrs
        if self.error:
            out["error"] = self.error
        return out


class _SpanCM:
    """The span context manager: exception-safe, nestable, and feeding
    `jax.profiler.TraceAnnotation` so spans land on XProf traces too."""

    __slots__ = ("_run", "_rec", "_ann")

    def __init__(self, run: "Run", name: str, attrs: dict):
        self._run = run
        stack = run._span_stack()
        parent = stack[-1] if stack else None
        path = (parent.path + "/" + name) if parent is not None else name
        self._rec = Span(name=name, path=path,
                         start_ns=time.perf_counter_ns(),
                         depth=len(stack), attrs=attrs)
        self._ann = None

    def __enter__(self) -> Span:
        self._run._span_stack().append(self._rec)
        try:  # profiler annotation is best-effort decoration, never load-bearing
            import jax

            # the scalar attributes ride along as the event's stats; it
            # still reads back under its bare path (PERF.md §6, PR 36)
            self._ann = jax.profiler.TraceAnnotation(
                self._rec.path, **{k: v for k, v in self._rec.attrs.items()
                                   if isinstance(v, (bool, int, float, str))})
            self._ann.__enter__()
        except Exception:
            self._ann = None
        return self._rec

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        rec = self._rec
        rec.end_ns = time.perf_counter_ns()
        if exc_type is not None:
            rec.error = exc_type.__name__
        stack = self._run._span_stack()
        # pop defensively: a mis-nested manual start/stop (Timer misuse)
        # must corrupt at most its own record, never the whole stack
        if stack and stack[-1] is rec:
            stack.pop()
        elif rec in stack:
            stack.remove(rec)
        self._run._record_span(rec)


class Run:
    """One run's telemetry state. Construct directly for an unattached
    recorder, or via `telemetry.start_run()` to make it the process-wide
    current run the instrumented hot paths report into."""

    def __init__(self, name: str = "run", jsonl_path: Optional[str] = None,
                 resident_tap: bool = False, logger=None,
                 keep_iterations: int = 100_000, append: bool = False):
        self.name = name
        self.resident_tap = bool(resident_tap)
        self.started_unix = time.time()
        self._t0_ns = time.perf_counter_ns()
        self._end_ns: Optional[int] = None
        self._lock = threading.Lock()
        # the JSONL sink gets its OWN lock: serializing file writes under
        # _lock would stall every counter bump from the serving threads
        # behind disk latency (the lint's blocking_under_lock rule)
        self._emit_lock = threading.Lock()
        self._tls = threading.local()
        self.spans: list[Span] = []
        self.counters: dict[str, float] = {}
        # (name, device array, reduce) of count_device, not yet read back
        self._pending_device: list[tuple[str, Any, str, float]] = []
        self.gauges: dict[str, Any] = {}
        self.iterations: list[dict] = []
        self._iter_cap = int(keep_iterations)
        self._n_iter_events = 0
        self._logger = logger
        self._jsonl_path = jsonl_path
        self._jsonl_file = None
        self._closed = False
        # dynamic retrace bookkeeping (analysis.TraceSignatureLog): record
        # per-program argument signatures; new ones count as (re)traces.
        from photon_tpu.analysis.rules import TraceSignatureLog

        self.trace_log = TraceSignatureLog()
        if jsonl_path is not None:
            os.makedirs(os.path.dirname(os.path.abspath(jsonl_path)),
                        exist_ok=True)
            if append:
                # a resumed run continues the dead run's event log: first
                # truncate a crash-torn final record (otherwise our first
                # write would fuse onto it and hide every later event
                # from read_jsonl), then reopen for append
                from photon_tpu.telemetry.sinks import repair_jsonl_tail

                repair_jsonl_tail(jsonl_path)
            self._jsonl_file = open(jsonl_path, "a" if append else "w")
        self._emit({"type": "run_start", "name": name,
                    "started_unix": self.started_unix})

    # ------------------------------------------------------------ plumbing
    def _span_stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _emit(self, obj: dict) -> None:
        if self._jsonl_file is None:
            return
        with self._emit_lock:
            f = self._jsonl_file
            if f is None:  # closed concurrently
                return
            # photon: allow(blocking_under_lock, _emit_lock exists to serialize exactly this one-line write — it guards no other state, so nothing can deadlock or stall behind it)
            json.dump(obj, f)
            f.write("\n")

    def _record_span(self, rec: Span) -> None:
        with self._lock:
            self.spans.append(rec)
        # t_s, the run-relative start offset: telemetry.aggregate places
        # the span on a wall clock as run_start.started_unix + t_s
        self._emit(rec.to_json(self._t0_ns))

    # ------------------------------------------------------------- primitives
    def span(self, name: str, **attrs) -> _SpanCM:
        return _SpanCM(self, name, attrs)

    def count(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    def count_device(self, name: str, value, reduce: str = "sum",
                     scale: float = 1.0) -> None:
        """A counter bump whose value is still on the device: kept by
        reference, read back, reduced over its elements ("sum" or "max")
        and multiplied by the host number ``scale`` by
        `_resolve_device_counts` when a report is asked for. No transfer
        and no dispatch happens here."""
        if reduce not in ("sum", "max"):
            raise ValueError(f"count_device: reduce is 'sum' or 'max', "
                             f"not {reduce!r}")
        with self._lock:
            self._pending_device.append((name, value, reduce, scale))

    def _resolve_device_counts(self) -> None:
        """ONE `device_get` for every pending `count_device` array."""
        with self._lock:
            pending, self._pending_device = self._pending_device, []
        if not pending:
            return
        import jax
        import numpy as np

        values = jax.device_get([p[1] for p in pending])
        for (name, _, reduce, scale), v in zip(pending, values):
            self.count(name, scale * float(np.max(v) if reduce == "max"
                                           else np.sum(v)))

    def gauge(self, name: str, value) -> None:
        with self._lock:
            self.gauges[name] = value

    def iteration(self, solver: str, it: int, loss, grad_norm=None,
                  step=None, trials=None, **extra) -> None:
        """One live solver-iteration event. Scalars coerce to float so the
        JSONL stream never carries device arrays."""
        ev = {"type": "iteration", "solver": solver, "it": int(it),
              "loss": _scalar(loss)}
        if grad_norm is not None:
            ev["grad_norm"] = _scalar(grad_norm)
        if step is not None:
            ev["step"] = _scalar(step)
        if trials is not None:
            ev["trials"] = int(trials)
        for k, v in extra.items():
            ev[k] = _scalar(v)
        with self._lock:
            self._n_iter_events += 1
            if len(self.iterations) < self._iter_cap:
                self.iterations.append(ev)
        self._emit(ev)

    def event(self, kind: str, **fields) -> None:
        """A one-off structured event (e.g. the streamed-objective
        resolution verdict) — JSONL + the in-memory iteration list's
        sibling; not counted as an iteration."""
        ev = {"type": kind}
        for k, v in fields.items():
            ev[k] = _scalar(v)
        self._emit(ev)

    def record_signature(self, program: str, args) -> None:
        """Dynamic retrace accounting: a NEW (shape, dtype, weak_type)
        signature for ``program`` means jit will (re)trace it."""
        before = len(self.trace_log.signatures(program))
        self.trace_log.record(program, args)
        if len(self.trace_log.signatures(program)) > before:
            self.count("retrace.new_signatures")

    def sample_device_memory(self, tag: str = "") -> None:
        """HBM watermark gauges from `jax.local_devices()` memory stats
        (best-effort: the CPU test backend reports nothing)."""
        try:
            import jax

            devices = jax.local_devices()
        except Exception:
            return
        in_use, peak = [], []
        for d in devices:
            try:
                stats = d.memory_stats() or {}
            except Exception:
                continue
            if "bytes_in_use" in stats:
                in_use.append(int(stats["bytes_in_use"]))
            if "peak_bytes_in_use" in stats:
                peak.append(int(stats["peak_bytes_in_use"]))
        suffix = f".{tag}" if tag else ""
        if in_use:
            self.gauge(f"hbm.bytes_in_use.max{suffix}", max(in_use))
        if peak:
            self.gauge(f"hbm.peak_bytes_in_use.max{suffix}", max(peak))

    # ---------------------------------------------------------------- sinks
    def duration_s(self) -> float:
        end = self._end_ns if self._end_ns is not None \
            else time.perf_counter_ns()
        return (end - self._t0_ns) / 1e9

    def span_totals(self) -> dict[str, float]:
        """Total seconds per span path (the PhaseTimers.summary analog)."""
        with self._lock:
            spans = list(self.spans)
        totals: dict[str, float] = {}
        for s in spans:
            totals[s.path] = totals.get(s.path, 0.0) + s.seconds
        return {k: round(v, 6) for k, v in sorted(totals.items())}

    def span_counts(self) -> dict[str, int]:
        """Closed spans per span path: what turns a total into a mean."""
        with self._lock:
            paths = [s.path for s in self.spans]
        return dict(sorted(collections.Counter(paths).items()))

    def report(self) -> dict:
        """The in-memory run report — everything the JSONL stream carries,
        as one dict (bench.py embeds a compact subset in its JSON line)."""
        self._resolve_device_counts()
        with self._lock:
            counters = dict(self.counters)
            gauges = dict(self.gauges)
            spans = [s.to_json(self._t0_ns) for s in self.spans]
            iterations = list(self.iterations)
            n_iter = self._n_iter_events
        hazards = self.trace_log.hazards()
        return {
            "name": self.name,
            "started_unix": self.started_unix,
            "duration_s": round(self.duration_s(), 6),
            "spans": spans,
            "span_totals": self.span_totals(),
            "counters": counters,
            "gauges": gauges,
            "iterations": iterations,
            "n_iteration_events": n_iter,
            "retrace": {
                "programs": len(self.trace_log._seen),
                "weak_type_hazards": [h[0] for h in hazards],
            },
        }

    def report_compact(self) -> dict:
        """Counters + span totals and counts + duration: the piece small
        enough to embed in a one-line bench JSON."""
        self._resolve_device_counts()
        with self._lock:
            counters = {k: round(v, 6) for k, v in
                        sorted(self.counters.items())}
            gauges = dict(sorted(self.gauges.items()))
            n_iter = self._n_iter_events
        return {"duration_s": round(self.duration_s(), 3),
                "counters": counters, "gauges": gauges,
                "span_totals": self.span_totals(),
                "span_counts": self.span_counts(),
                "n_iteration_events": n_iter}

    def summary_lines(self) -> list[str]:
        """The human end-of-run summary photon_logger prints at close()."""
        lines = [f"run '{self.name}': {self.duration_s():.3f}s, "
                 f"{len(self.spans)} span(s), "
                 f"{self._n_iter_events} iteration event(s)"]
        totals = self.span_totals()
        if totals:
            top = sorted(totals.items(), key=lambda kv: -kv[1])[:8]
            lines.append("  time: " + ", ".join(
                f"{k}={v:.3f}s" for k, v in top))
        with self._lock:
            counters = sorted(self.counters.items())
        if counters:
            lines.append("  counters: " + ", ".join(
                f"{k}={v:g}" for k, v in counters))
        hazards = self.trace_log.hazards()
        if hazards:
            lines.append("  RETRACE HAZARDS: " + ", ".join(
                sorted({h[0] for h in hazards})))
        return lines

    def close(self) -> dict:
        """Finalize: stamp the end time, snapshot counters/gauges into the
        JSONL stream, log the human summary, close the file. Idempotent;
        returns the final report."""
        if self._closed:
            return self.report()
        self._closed = True
        self._end_ns = time.perf_counter_ns()
        self._resolve_device_counts()
        self.sample_device_memory("final")
        with self._lock:
            snapshot = {"type": "run_end",
                        "duration_s": round(self.duration_s(), 6),
                        "counters": dict(self.counters),
                        "gauges": dict(self.gauges),
                        "n_iteration_events": self._n_iter_events}
        self._emit(snapshot)
        log = self._logger
        if log is None:
            from photon_tpu.utils.logging import photon_logger

            log = photon_logger("photon_tpu.telemetry")
        for line in self.summary_lines():
            log.info("%s", line)
        with self._emit_lock:
            if self._jsonl_file is not None:
                self._jsonl_file.close()
                self._jsonl_file = None
        return self.report()


def _scalar(v):
    """Host-scalar coercion: numpy/jax 0-d arrays -> float, small arrays ->
    lists (the vmapped tap hands batched values), strings/bools pass
    through."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    try:
        import numpy as np

        a = np.asarray(v)
        if a.ndim == 0:
            return a.item()
        return a.tolist()
    except Exception:
        return repr(v)
