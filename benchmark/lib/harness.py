"""What every cell shares: the device's identity, the measured window, the
traced run, and the final line.

A traffic module (benchmark/traffic/<module>.py) supplies the work:

    setup(config, params, seed, dirs) -> state       data, layout, placement
    unit(state, keep=False) -> dict   ONE whole piece of work, closed by a
        small readback: {"work": float, "failed": bool, ...}; with
        ``keep`` also "evidence" for `check`, already on the host
    metrics(state, units, elapsed_s) -> {end-to-end metric: value}
    check(state, evidence) -> {"ok": bool, ...}      against plain references
    traced_sections(state) -> [(name, fn)]           optional micro sections
    GAP_LABELS: {host annotation name: label}        for the idle gaps
"""
from __future__ import annotations

import contextlib
import json
import time


def device_info(jax) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(jax):
    """Peak bytes in use on the fullest chip; None where the backend
    reports no memory stats (the CPU rehearsal)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_window(unit, state, seconds: float) -> tuple:
    """Whole units back to back; a new one starts while the window is
    open, and one that has started finishes. Returns ([(wall_s, result)],
    elapsed_s): elapsed runs from the window's opening to the end of the
    last unit, so every second between units is inside it."""
    units = []
    opened = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        result = unit(state)
        t1 = time.perf_counter()
        units.append((t1 - t0, result))
        if t1 - opened >= seconds:
            return units, t1 - opened


@contextlib.contextmanager
def profiler_trace(jax, trace_dir: str):
    """A device trace with the Python tracer off and the host tracer at the
    lowest level that still records `TraceAnnotation`s."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def run_traced(jax, traffic, state, sections, n_units: int,
               trace_dir: str) -> dict:
    """The traced run: the module's micro sections (already compiled), then
    `n_units` whole units, each under a ``bench.section.<name>``
    annotation, with the program's telemetry attached. Returns the section
    results and the telemetry report; the trace is left under `trace_dir`."""
    from photon_tpu import telemetry

    from benchmark.lib.trace_reduce import SECTION_PREFIX

    results: dict = {}
    with telemetry.run("benchmark") as trun, profiler_trace(jax, trace_dir):
        for name, fn in sections:
            with jax.profiler.TraceAnnotation(SECTION_PREFIX + name):
                results.setdefault(name, []).append(fn())
        for _ in range(n_units):
            with jax.profiler.TraceAnnotation(SECTION_PREFIX + "unit"):
                results.setdefault("unit", []).append(traffic.unit(state))
        report = trun.report_compact()
    return {"results": results, "telemetry": report}


def final_line(correct: bool, attempted: int, failed: int, metrics: dict,
               units_of: dict, device: dict, **extra) -> str:
    """The one JSON object the driver reads, values as measured."""
    return json.dumps({
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": float(v), "unit": units_of[k]}
                    for k, v in metrics.items()},
        "device": device, **extra})


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def span_seconds_per_unit(ctx: dict, path: str):
    """Seconds of the program's span `path` per traced whole unit, from
    the traced run's telemetry; None where the span was not recorded."""
    total = ctx["telemetry"]["span_totals"].get(path)
    n = len(ctx["results"].get("unit", []))
    return None if total is None or not n else total / n
