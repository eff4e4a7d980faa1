"""What the HOST was doing, from the program's own spans: `telemetry.span`
enters a `jax.profiler.TraceAnnotation` under the span's path
("solve.lbfgs_streamed/stream.pass/stream.upload"), so every span of the
program is a host event of the traced run, on the one clock the device's
operations are on. Two reductions over the traced whole units (the
``bench.section.unit`` windows), each printed once as a log line:

``host_spans`` — per span NAME (the last component of the path): count,
total, min, median and max seconds. For a streamed solve this is the
per-chunk log: how long an upload call, a hand-out wait, a release, a
dispatch, a readback, a host step took, and how far the slowest lay from
the median.

``idle_by_span`` — every idle gap of device plane 0 inside the windows,
SPLIT BY OVERLAP (one 0.3 s gap covers several spans; its midpoint would
give it to one) among the innermost program span open at each instant,
``unattributed`` where none is. The values add up to the windows' idle
time. A span that ENCLOSES other spans (``solve.lbfgs_streamed``,
``stream.pass``: its path is a proper prefix of another's) is a frame, not
a statement of what the host did: idle whose innermost span is a frame is
listed under the frame's name, and counted with ``unattributed`` in
``uncovered_s`` — the idle no leaf span accounts for.

A program's span is told from the runtime's own host events by its family
(the last component is dotted and its prefix before the first dot is one
of `photon_tpu.telemetry.TELEMETRY_REGISTRY["span_families"]`); the
benchmark's ``bench.*`` annotations are not the program's and are left to
`trace_reduce`. `summarize` and `idle_by_span` are plain arithmetic on
(start, duration, name) tuples, checked on synthetic events by
tests/test_host_spans.py. With a program that opens no such span (the
parent of PR 36) the tables are empty and the readers under
``layer_metrics/`` leave their metric out.
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import statistics

from benchmark.lib.scope_reduce import TRACE_ROOT
from benchmark.lib.trace_reduce import (
    SECTION_PREFIX,
    gaps,
    load,
    merge,
    newest_xplane,
)

UNATTRIBUTED = "unattributed"


# ------------------------------------------------------------- arithmetic
def last(path: str) -> str:
    """A span's name: the last component of its "/"-joined path."""
    return path.rsplit("/", 1)[-1]


def program_spans(host, windows, families) -> list:
    """The (start, duration, path) host events that are spans of the
    program — the name's family is one of `families` — and start inside
    `windows` (disjoint (start, end) pairs)."""
    out = []
    for s, d, path in host:
        family, dot, _ = last(path).partition(".")
        if dot and family in families and any(
                lo <= s < hi for lo, hi in windows):
            out.append((s, d, path))
    return out


def summarize(spans) -> dict:
    """{name: {"count", "total_s", "min_s", "median_s", "max_s"}} of
    (start_ns, duration_ns, path) events, by the path's last component."""
    by_name: dict = {}
    for _, d, path in spans:
        by_name.setdefault(last(path), []).append(d / 1e9)
    return {name: {"count": len(v), "total_s": sum(v), "min_s": min(v),
                   "median_s": statistics.median(v), "max_s": max(v)}
            for name, v in sorted(by_name.items())}


def frames(spans) -> set:
    """The names of the spans that enclose other spans: a path that is a
    proper prefix of another event's."""
    paths = {path for _, _, path in spans}
    return {last(p) for p in paths
            if any(q.startswith(p + "/") for q in paths)}


def innermost_segments(spans) -> list:
    """Disjoint, sorted (start, end, name) pieces of the time some span
    is open, each named by the innermost one: of the spans open there, the
    one that started last."""
    edges = sorted({t for s, d, _ in spans for t in (s, s + d)})
    by_start = sorted(spans, key=lambda ev: ev[0])
    open_, at, out = [], 0, []
    for lo, hi in zip(edges, edges[1:]):
        while at < len(by_start) and by_start[at][0] <= lo:
            open_.append(by_start[at])
            at += 1
        open_ = [ev for ev in open_ if ev[0] + ev[1] > lo]
        if not open_:
            continue
        out.append((lo, hi, last(max(open_, key=lambda ev: ev[0])[2])))
    return out


def idle_by_span(idle, spans) -> dict:
    """{name: seconds} of the `idle` gaps (disjoint (start, end) pairs,
    nanoseconds) split by overlap among the innermost span of `spans`
    open at each instant, `UNATTRIBUTED` where none is; the values add up
    to the gaps' total."""
    segments = innermost_segments(spans)
    starts = [s for s, _, _ in segments]
    out: dict = {}
    for lo, hi in idle:
        covered = 0.0
        at = max(bisect.bisect_right(starts, lo) - 1, 0)
        while at < len(segments) and segments[at][0] < hi:
            s, e, name = segments[at]
            piece = min(e, hi) - max(s, lo)
            if piece > 0:
                out[name] = out.get(name, 0.0) + piece / 1e9
                covered += piece
            at += 1
        rest = (hi - lo) - covered
        if rest > 0:
            out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) + rest / 1e9
    return out


def reduce_host(trace: dict, families) -> dict:
    """{"spans": `summarize`'s table, "idle_s", "idle_by_span",
    "frames": [names], "uncovered_s"} of a loaded trace's whole units;
    the idle keys are left out where the trace holds no device
    operation."""
    windows = merge((s, s + d) for s, d, name in trace["host"]
                    if name == SECTION_PREFIX + "unit")
    spans = program_spans(trace["host"], windows, families)
    out = {"spans": summarize(spans)}
    if not trace["devices"] or not trace["devices"][0] or not spans:
        return out
    busy = merge((ev[0], ev[0] + ev[1]) for ev in trace["devices"][0])
    idle = [gap for lo, hi in windows for gap in gaps(busy, lo, hi)]
    split = idle_by_span(idle, spans)
    framing = sorted(frames(spans))
    return {**out, "idle_s": sum(e - s for s, e in idle) / 1e9,
            "idle_by_span": dict(sorted(split.items(),
                                        key=lambda kv: -kv[1])),
            "frames": framing,
            "uncovered_s": sum(split.get(k, 0.0)
                               for k in [UNATTRIBUTED, *framing])}


# ------------------------------------------------- what the readers call
@functools.lru_cache(maxsize=None)
def unit_host_spans(rehearse: bool):
    """`reduce_host` over the traced whole units of THIS run (the newest
    ``.xplane.pb`` under ``benchmark/.cache/trace/*/``, which `run.py`
    wrote just before the readers are called), read once per process and
    printed as the ``host_spans`` and ``idle_by_span`` log lines — the
    first with the trace's inventory of planes and lines, which is where a
    transfer line would show if the device's plane had one. ``None``
    where there is no trace or the program opened no span in the units."""
    try:
        from photon_tpu.telemetry import TELEMETRY_REGISTRY
    except ImportError:  # a program from before the registry
        return None
    traces = sorted(glob.glob(os.path.join(TRACE_ROOT, "*")),
                    key=os.path.getmtime)
    if not traces:
        return None
    try:
        trace = load(newest_xplane(traces[-1]), rehearse=rehearse)
    except FileNotFoundError:
        return None
    table = reduce_host(trace, set(TELEMETRY_REGISTRY["span_families"]))
    if not table["spans"]:
        return None
    print(json.dumps({"event": "host_spans", "spans": table["spans"],
                      "inventory": trace["inventory"]}), flush=True)
    if "idle_by_span" in table:
        print(json.dumps({"event": "idle_by_span",
                          **{k: table[k] for k in (
                              "idle_s", "idle_by_span", "frames",
                              "uncovered_s")}}), flush=True)
    return table


def span_sum(ctx: dict, name: str, under: str = "") -> tuple:
    """(total seconds, count) of the program's closed spans called `name`
    in the traced run's telemetry report, whatever encloses them — or,
    with `under`, only those whose parent span is called that. (0.0, 0)
    for a program whose report carries no ``span_counts``."""
    want = (under + "/" + name) if under else name
    report = ctx["telemetry"]
    paths = [p for p in report.get("span_counts", {})
             if p == want or p.endswith("/" + want)]
    return (sum(report["span_totals"][p] for p in paths),
            sum(report["span_counts"][p] for p in paths))
