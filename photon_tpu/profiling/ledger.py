"""The performance attribution ledger: fuses the STATIC plane (PR 3's
jaxpr walker, `profiling.model`'s cost estimates, XLA's own
cost_analysis) with the RUNTIME plane (PR 4's span/counter recorder) so
a run can answer "what fraction of its modeled roofline did each program
achieve, and where did the compile time go".

Mirrors `telemetry.Run`'s spine exactly: one process-wide `Ledger`
attached via `start_ledger()` / `ledger(...)`, and every hot-path entry
point below (``measure``/``attribute``/``note_program``/``dispatch``/
``record_signature``/``sample_hbm``) begins with a module-global load +
one branch — a ledger-less process pays nothing, and NOTHING here ever
enters a traced program (the ``ledger_off_is_free`` ContractSpec at the
bottom makes that law: the full resident L-BFGS solve traced with the
ledger disarmed contains zero transfer/callback primitives).

Three accounts:

- **Attribution** — measured wall seconds per (program, phase), fed by
  `measure(...)` context managers wrapped around the hot paths' already-
  synchronized regions (a streamed pass closes with a host readback, so
  its wall time IS device time + stream stalls). Combined with the
  program's static FLOP/byte estimate this yields achieved FLOP/s,
  achieved bytes/s, and a roofline-utilization fraction in (0, 1] —
  achieved/peak on whichever axis (compute or bandwidth) the program
  loads more, clamped at 1 (the model is an estimate, not a simulator).
- **Compile** — per-program trace/lower/compile wall time from explicit
  probes (`note_program(..., probe=True)` times the three stages
  separately), plus the cheap always-on proxy: a `dispatch(...)` whose
  argument signature is NEW (riding `analysis.TraceSignatureLog`, the
  same registry telemetry's retrace counter uses) books its wall time as
  ``dispatch_compile_s`` — the first call of a jit program pays
  trace+lower+compile inline, later calls hit the executable cache.
- **HBM** — `sample_hbm(phase)` records per-phase device high-water
  marks from `memory_stats()` (best-effort; the CPU test backend
  reports none).

Peaks default per backend and are operator-overridable via
``PHOTON_TPU_PEAK_FLOPS`` / ``PHOTON_TPU_PEAK_BYTES_PER_S`` — they are
modeled ceilings for the utilization denominator, not measurements.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Optional

from photon_tpu.profiling.model import StaticCost, estimate_fn, xla_cost
from photon_tpu.utils import env as env_knobs

__all__ = [
    "Ledger", "ProgramRecord", "start_ledger", "finish_ledger", "ledger",
    "current_ledger", "enabled", "measure", "attribute", "note_program",
    "needs_note", "dispatch", "record_signature", "sample_hbm",
    "ledger_disabled", "resolve_peaks", "DevicePeaks", "DEVICE_PEAKS",
    "device_peaks",
]

@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """One chip's ceilings: the denominators of every utilization and the
    fallback HBM size for devices that do not report ``bytes_limit``."""

    flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


# THE device table, keyed by ``jax.Device.device_kind``. A device that is
# not here is an ERROR on any path that reports a device metric — never a
# default: a utilization priced against another machine's peak is wrong
# by an unknown factor.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        1.97e14, 8.19e11, 16 << 30,
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "819 GB/s HBM bandwidth, 16 GB HBM per chip"),
    # TEST STAND-IN, describes no machine: lets the CPU suite exercise
    # the utilization arithmetic and the HBM-budget auto-trip.
    "cpu": DevicePeaks(1.0e11, 5.0e10, 16 << 30,
                       "test stand-in (not a measurement)"),
}


def device_peaks(device=None) -> DevicePeaks:
    """`DEVICE_PEAKS` row of ``device`` (default: ``jax.devices()[0]``);
    an unknown ``device_kind`` raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise LookupError(
            f"device kind {device.device_kind!r} is not in "
            f"profiling.ledger.DEVICE_PEAKS (known: "
            f"{sorted(DEVICE_PEAKS)}); add its published peaks with "
            f"their source before reporting a device metric on it"
        ) from None


def resolve_peaks() -> tuple[float, float]:
    """(peak_flops_per_s, peak_bytes_per_s): env override first, else
    the attached device's `DEVICE_PEAKS` row (unknown device: error)."""
    env_f = env_knobs.get_raw("PHOTON_TPU_PEAK_FLOPS")
    env_b = env_knobs.get_raw("PHOTON_TPU_PEAK_BYTES_PER_S")
    if env_f and env_b:
        return float(env_f), float(env_b)
    peaks = device_peaks()
    return (float(env_f) if env_f else peaks.flops_per_s,
            float(env_b) if env_b else peaks.hbm_bytes_per_s)


@dataclasses.dataclass
class ProgramRecord:
    """One program's static-plane account."""

    name: str
    static: Optional[StaticCost] = None
    trace_s: float = 0.0  # probe: make_jaxpr wall
    lower_s: float = 0.0  # probe: jit(...).lower wall
    compile_s: float = 0.0  # probe: lowered.compile wall
    dispatch_compile_s: float = 0.0  # new-signature dispatch wall (proxy)
    retraces: int = 0  # NEW argument signatures seen (first trace included)
    xla: Optional[dict] = None  # compiled.cost_analysis view (probe only)
    note_error: Optional[str] = None

    def to_json(self) -> dict:
        out = {"retraces": self.retraces}
        if self.static is not None:
            out["static"] = self.static.to_json()
        for k in ("trace_s", "lower_s", "compile_s", "dispatch_compile_s"):
            v = getattr(self, k)
            if v:
                out[k] = round(v, 6)
        if self.xla is not None:
            out["xla"] = self.xla
        if self.note_error:
            out["note_error"] = self.note_error
        return out


class _MeasureCM:
    """Times a block and attributes it to (program, phase); optionally
    books the elapsed wall as compile time (new-signature dispatches)."""

    __slots__ = ("_ledger", "_program", "_phase", "_calls", "_compile",
                 "_t0")

    def __init__(self, ledger: "Ledger", program: str, phase: str,
                 calls: int, book_compile: bool):
        self._ledger = ledger
        self._program = program
        self._phase = phase
        self._calls = calls
        self._compile = book_compile
        self._t0 = 0

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        seconds = (time.perf_counter_ns() - self._t0) / 1e9
        self._ledger.attribute(self._program, self._phase, seconds,
                               calls=self._calls)
        if self._compile:
            self._ledger._book_dispatch_compile(self._program, seconds)


class _NullCM:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return None


_NULL_CM = _NullCM()


class Ledger:
    """One run's attribution state. Construct directly for an unattached
    ledger, or via `start_ledger()` for the process-wide one the
    instrumented hot paths report into."""

    def __init__(self, name: str = "ledger",
                 peaks: Optional[tuple] = None):
        from photon_tpu.analysis.rules import TraceSignatureLog

        self.name = name
        self.peak_flops, self.peak_bytes = (peaks if peaks is not None
                                            else resolve_peaks())
        self._t0_ns = time.perf_counter_ns()
        self._lock = threading.Lock()
        self.programs: dict[str, ProgramRecord] = {}
        # (program, phase) -> {"seconds", "calls"}
        self.attributions: dict[tuple, dict] = {}
        self.trace_log = TraceSignatureLog()
        self.hbm: dict[str, dict] = {}  # phase -> watermark gauges

    # ------------------------------------------------------------ programs
    def _record(self, program: str) -> ProgramRecord:
        rec = self.programs.get(program)
        if rec is None:
            rec = self.programs[program] = ProgramRecord(program)
        return rec

    def note_program(self, program: str, fn, args, while_trips: int = 1,
                     probe: bool = False) -> ProgramRecord:
        """Register ``program``'s static cost (once per name): a TIMED
        make_jaxpr trace + `model.estimate_jaxpr`. ``probe=True`` also
        times lower/compile separately and records XLA's own
        cost_analysis — compiles, so probes belong in CLIs and benches,
        never inside solver loops."""
        with self._lock:
            rec = self._record(program)
            if rec.static is not None or rec.note_error is not None:
                return rec
        try:
            import jax

            t0 = time.perf_counter_ns()
            closed = jax.make_jaxpr(fn)(*args)
            t1 = time.perf_counter_ns()
            from photon_tpu.profiling.model import estimate_jaxpr

            static = estimate_jaxpr(closed, while_trips=while_trips)
            trace_s = (t1 - t0) / 1e9
            lower_s = compile_s = 0.0
            xla = None
            if probe:
                t2 = time.perf_counter_ns()
                lowered = jax.jit(fn).lower(*args)
                t3 = time.perf_counter_ns()
                compiled = lowered.compile()
                t4 = time.perf_counter_ns()
                lower_s = (t3 - t2) / 1e9
                compile_s = (t4 - t3) / 1e9
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                if ca:
                    xla = {"flops": float(ca.get("flops", 0.0)),
                           "bytes_accessed":
                               float(ca.get("bytes accessed", 0.0))}
            with self._lock:
                rec.static = static
                rec.trace_s += trace_s
                rec.lower_s += lower_s
                rec.compile_s += compile_s
                if xla is not None:
                    rec.xla = xla
        except Exception as e:  # noqa: BLE001 — a probe must never kill a run
            with self._lock:
                rec.note_error = f"{type(e).__name__}: {e}"
            return rec
        # the note's trace is a real (first) trace of this program: its
        # signature enters the retrace account like any dispatch's
        self.record_signature(program, args)
        return rec

    def record_signature(self, program: str, args) -> bool:
        """Retrace accounting (the TraceSignatureLog face): True iff the
        signature is NEW for this program — i.e. jit will (re)trace."""
        with self._lock:
            before = len(self.trace_log.signatures(program))
            self.trace_log.record(program, args)
            new = len(self.trace_log.signatures(program)) > before
            if new:
                self._record(program).retraces += 1
        return new

    def _book_dispatch_compile(self, program: str, seconds: float) -> None:
        with self._lock:
            self._record(program).dispatch_compile_s += seconds

    # --------------------------------------------------------- attribution
    def attribute(self, program: str, phase: str, seconds: float,
                  calls: int = 1) -> None:
        key = (program, phase)
        with self._lock:
            slot = self.attributions.get(key)
            if slot is None:
                slot = self.attributions[key] = {"seconds": 0.0, "calls": 0}
            slot["seconds"] += float(seconds)
            slot["calls"] += int(calls)

    def measure(self, program: str, phase: str, calls: int = 1) -> _MeasureCM:
        return _MeasureCM(self, program, phase, calls, False)

    def dispatch(self, program: str, args, phase: str = "dispatch"
                 ) -> _MeasureCM:
        """Measure one jit dispatch; a NEW argument signature books the
        elapsed wall as compile time too (first-call = trace+lower+
        compile inline). NOTE: jit returns asynchronously — for resident
        programs this measures dispatch (and compile) wall, not device
        time; utilization is only meaningful where the measured region
        is closed by a readback (the streamed/serving paths)."""
        new = self.record_signature(program, args)
        return _MeasureCM(self, program, phase, 1, new)

    def sample_hbm(self, phase: str) -> None:
        """Per-phase HBM high-water attribution (best-effort, mirrors
        `telemetry.Run.sample_device_memory`)."""
        try:
            import jax

            devices = jax.local_devices()
        except Exception:  # noqa: BLE001
            return
        in_use, peak = [], []
        for d in devices:
            try:
                stats = d.memory_stats() or {}
            except Exception:  # noqa: BLE001
                continue
            if "bytes_in_use" in stats:
                in_use.append(int(stats["bytes_in_use"]))
            if "peak_bytes_in_use" in stats:
                peak.append(int(stats["peak_bytes_in_use"]))
        if not in_use and not peak:
            return
        with self._lock:
            slot = self.hbm.setdefault(phase, {})
            if in_use:
                slot["bytes_in_use.max"] = max(
                    max(in_use), slot.get("bytes_in_use.max", 0))
            if peak:
                slot["peak_bytes_in_use.max"] = max(
                    max(peak), slot.get("peak_bytes_in_use.max", 0))

    # --------------------------------------------------------------- report
    def _entry(self, program: str, phase: str, slot: dict) -> dict:
        rec = self.programs.get(program)
        out = {"program": program, "phase": phase,
               "seconds": round(slot["seconds"], 6),
               "calls": slot["calls"]}
        static = rec.static if rec is not None else None
        if static is None or slot["seconds"] <= 0.0:
            return out
        total_flops = static.flops * slot["calls"]
        total_bytes = static.bytes * slot["calls"]
        out["flops_modeled"] = total_flops
        out["bytes_modeled"] = total_bytes
        out["achieved_flops_per_s"] = total_flops / slot["seconds"]
        out["achieved_bytes_per_s"] = total_bytes / slot["seconds"]
        f_frac = (out["achieved_flops_per_s"] / self.peak_flops
                  if self.peak_flops > 0 else 0.0)
        b_frac = (out["achieved_bytes_per_s"] / self.peak_bytes
                  if self.peak_bytes > 0 else 0.0)
        util = max(f_frac, b_frac)
        if util > 0.0:
            # the binding-axis fraction, clamped into (0, 1]: the model
            # is a ceiling estimate, so >1 means the estimate was loose
            out["utilization"] = min(util, 1.0)
            out["bound"] = "bandwidth" if b_frac >= f_frac else "compute"
        if static.collective_bytes:
            out["collective_bytes_modeled"] = (static.collective_bytes
                                               * slot["calls"])
        return out

    def duration_s(self) -> float:
        return (time.perf_counter_ns() - self._t0_ns) / 1e9

    def report(self) -> dict:
        """The full ledger: attribution entries (top programs by
        measured time first), per-program static/compile accounts, the
        compile share, HBM watermarks, and retrace hazards."""
        with self._lock:
            attrs = {k: dict(v) for k, v in self.attributions.items()}
            programs = dict(self.programs)
            hbm = {k: dict(v) for k, v in self.hbm.items()}
        entries = [self._entry(p, ph, slot)
                   for (p, ph), slot in attrs.items()]
        entries.sort(key=lambda e: -e["seconds"])
        measured = sum(e["seconds"] for e in entries)
        compile_s = sum(r.compile_s + r.lower_s + r.trace_s
                        + r.dispatch_compile_s for r in programs.values())
        hazards = self.trace_log.hazards()
        return {
            "name": self.name,
            "duration_s": round(self.duration_s(), 6),
            "peaks": {"flops_per_s": self.peak_flops,
                      "bytes_per_s": self.peak_bytes},
            "attribution": entries,
            "programs": {n: r.to_json()
                         for n, r in sorted(programs.items())},
            "compile": {
                "wall_s": round(compile_s, 6),
                "retraces": sum(r.retraces for r in programs.values()),
                "share_of_measured": round(
                    compile_s / measured, 4) if measured > 0 else None,
            },
            "hbm": hbm,
            "retrace_hazards": sorted({h[0] for h in hazards}),
        }

    def summary_lines(self, top: int = 8) -> list[str]:
        rep = self.report()
        lines = [f"ledger '{self.name}': "
                 f"{len(rep['attribution'])} attribution entr(ies), "
                 f"{len(rep['programs'])} program(s), compile "
                 f"{rep['compile']['wall_s']:.3f}s"]
        for e in rep["attribution"][:top]:
            util = e.get("utilization")
            extra = ""
            if util is not None:
                extra = (f", {100.0 * util:.1f}% of roofline "
                         f"({e['bound']}-bound)")
            lines.append(f"  {e['program']} [{e['phase']}]: "
                         f"{e['seconds']:.3f}s / {e['calls']} call(s)"
                         + extra)
        return lines


# ----------------------------------------------------- process-wide state
_CURRENT: Optional[Ledger] = None
_ATTACH_LOCK = threading.Lock()


def start_ledger(name: str = "ledger",
                 peaks: Optional[tuple] = None) -> Ledger:
    """Attach a fresh process-wide Ledger (closing any previous one),
    mirroring `telemetry.start_run`."""
    global _CURRENT
    with _ATTACH_LOCK:
        led = Ledger(name=name, peaks=peaks)
        _CURRENT = led
    return led


def finish_ledger() -> Optional[dict]:
    """Detach the current ledger; returns its final report."""
    global _CURRENT
    with _ATTACH_LOCK:
        led, _CURRENT = _CURRENT, None
    return led.report() if led is not None else None


@contextlib.contextmanager
def ledger(name: str = "ledger", peaks: Optional[tuple] = None):
    """``with profiling.ledger(...) as led:`` — scoped attach/detach."""
    led = start_ledger(name, peaks=peaks)
    try:
        yield led
    finally:
        global _CURRENT
        with _ATTACH_LOCK:
            if _CURRENT is led:
                _CURRENT = None


def current_ledger() -> Optional[Ledger]:
    return _CURRENT


def enabled() -> bool:
    return _CURRENT is not None


@contextlib.contextmanager
def ledger_disabled():
    """Force the ledger detached inside the block (the
    `ledger_off_is_free` contract builder's trace-time scoping, the
    `telemetry.tap_disabled` analog — host-only state, no cache
    interaction needed since the ledger never enters a trace)."""
    global _CURRENT
    with _ATTACH_LOCK:
        was, _CURRENT = _CURRENT, None
    try:
        yield
    finally:
        with _ATTACH_LOCK:
            _CURRENT = was


# ------------------------------------------------ hot-path entry points
# One module-global load + one branch each when no ledger is attached —
# the same off-state contract as telemetry's helpers.

def measure(program: str, phase: str, calls: int = 1):
    led = _CURRENT
    if led is None:
        return _NULL_CM
    return led.measure(program, phase, calls=calls)


def attribute(program: str, phase: str, seconds: float,
              calls: int = 1) -> None:
    led = _CURRENT
    if led is not None:
        led.attribute(program, phase, seconds, calls=calls)


def note_program(program: str, fn, args, while_trips: int = 1,
                 probe: bool = False) -> None:
    led = _CURRENT
    if led is not None:
        led.note_program(program, fn, args, while_trips=while_trips,
                         probe=probe)


def needs_note(program: str) -> bool:
    """True iff a ledger is attached and ``program`` has no static cost
    yet — the guard hot paths use before PREPARING note_program args
    that cost anything (e.g. a device re-shard)."""
    led = _CURRENT
    if led is None:
        return False
    rec = led.programs.get(program)
    return rec is None or (rec.static is None and rec.note_error is None)


def dispatch(program: str, args, phase: str = "dispatch"):
    led = _CURRENT
    if led is None:
        return _NULL_CM
    return led.dispatch(program, args, phase=phase)


def record_signature(program: str, args) -> None:
    led = _CURRENT
    if led is not None:
        led.record_signature(program, args)


def sample_hbm(phase: str) -> None:
    led = _CURRENT
    if led is not None:
        led.sample_hbm(phase)


# ----------------------------------------------------------------- contracts
# The ledger-off guarantee as enforced law, the exact discipline of
# `telemetry_off_is_free` / `checkpoint_off_is_free`: the full resident
# margin-cached L-BFGS solve, traced with the ledger forced detached,
# contains zero callbacks/transfers and zero collectives — attribution
# is host bookkeeping around host loops, never traced code. Registered
# into the same registry as the PR-3 specs (analysis/registry.py imports
# this module).
from photon_tpu.analysis.contracts import register_contract  # noqa: E402
from photon_tpu.analysis.walker import TRANSFER_PRIMITIVES  # noqa: E402


@register_contract(
    name="ledger_off_is_free",
    description="resident L-BFGS solve traced with the attribution "
                "ledger disarmed: zero debug callbacks, zero transfers, "
                "zero collectives — profiling adds NO primitives to "
                "jitted solver programs",
    collectives={}, forbid=TRANSFER_PRIMITIVES,
    tags=("resident", "profiling"))
def _contract_ledger_off_is_free():
    import jax.numpy as jnp
    import numpy as np

    from photon_tpu.data.dataset import make_batch
    from photon_tpu.models.training import (_static_config, _train_run,
                                            make_objective)
    from photon_tpu.models.variance import VarianceComputationType
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    rng = np.random.default_rng(0)
    n, d = 40, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    cfg = OptimizerConfig(max_iters=5, tolerance=1e-7, reg=l2(),
                          reg_weight=0.3, history=4)
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d)

    def fn(b, w, o):
        with ledger_disabled():
            return _train_run(b, w, o, None, _static_config(cfg),
                              VarianceComputationType.NONE)

    return fn, (make_batch(X, y), jnp.zeros((d,), jnp.float32), obj)
