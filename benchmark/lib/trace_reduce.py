"""From a profiler trace to numbers: device busy time per section, time per
device operation, idle gaps named by what the host was doing.

`load` turns an ``.xplane.pb`` into plain tuples (jax.profiler.ProfileData
is the only reader); everything after it is arithmetic on tuples, checked
on synthetic events by `selfcheck.py`. All times are the trace's own clock,
in nanoseconds, host and device alike, so nothing is aligned across clocks.

A device operation is an event of the line "XLA Ops" of a plane
"/device:TPU:<n>". One line nests: a `while` op covers the ops of its body.
Busy time is therefore the UNION of the intervals, and an operation's time
is its SELF time (its duration less the events nested inside it).
"""
from __future__ import annotations

import glob
import os

SECTION_PREFIX = "bench.section."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
OP_NAME_CHARS = 160  # an op's name is its HLO text: result, kind, operands


# ------------------------------------------------------------- arithmetic
def merge(intervals) -> list:
    """Sorted, disjoint (start, end) pairs covering the same points."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(merged, lo, hi) -> float:
    """Length of the part of disjoint sorted `merged` inside [lo, hi]."""
    return float(sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged
                     if e > lo and s < hi))


def gaps(merged, lo, hi) -> list:
    """The (start, end) pieces of [lo, hi] that `merged` does not cover."""
    out, at = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def self_times(events) -> dict:
    """{name: seconds of self time} for (start, duration, name) events of
    ONE line, where an event may lie wholly inside another."""
    totals: dict = {}
    stack: list = []  # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + own / 1e9

    for s, d, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, name, d])
    close(float("inf"))
    return totals


def label_at(t, annotations, labels: dict) -> str:
    """The label of the innermost host annotation open at time t, among
    those `labels` names; "between" when none is."""
    best, best_start = "between", None
    for s, d, name in annotations:
        if name in labels and s <= t < s + d and (
                best_start is None or s >= best_start):
            best, best_start = labels[name], s
    return best


# ------------------------------------------------------------------ trace
def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, rehearse: bool = False) -> dict:
    """{"devices": [[(start, duration, name), ...] per device plane],
    "host": [(start, duration, name), ...], "inventory": {plane: {line:
    event count}}}. In a CPU rehearsal there is no device plane: the
    events that carry an `hlo_op` stat stand in as ONE device, so the same
    arithmetic runs (and nothing it yields is a device number)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, stand_in, inventory = [], [], [], {}
    for plane in data.planes:
        lines = inventory.setdefault(plane.name, {})
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        ops = []
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = len(events)
            if is_device:
                if line.name == OP_LINE:
                    ops.extend((e.start_ns, e.duration_ns,
                                e.name[:OP_NAME_CHARS]) for e in events)
                continue
            for e in events:
                if rehearse and e.duration_ns > 0 and any(
                        k == "hlo_op" for k, _ in e.stats):
                    stand_in.append((e.start_ns, e.duration_ns, e.name))
                elif e.duration_ns > 0:
                    host.append((e.start_ns, e.duration_ns, e.name))
        if is_device:
            devices.append(ops)
    if rehearse and not devices:
        devices = [stand_in]
    return {"devices": devices, "host": host, "inventory": inventory}


def reduce_trace(trace: dict, steady: set, labels: dict) -> dict:
    """Sections, the steady window's busy time, operation totals and idle
    gaps of a loaded trace.

    A section is every host annotation named ``bench.section.<name>``; the
    steady window is the union of the sections named in `steady`. Busy time
    is averaged over the device planes; idle gaps are read on the first.
    """
    host, devices = trace["host"], trace["devices"]
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operation: "
                         f"{trace['inventory']}")
    merged = [merge((s, s + d) for s, d, _ in ops) for ops in devices]
    spans: dict = {}
    for s, d, name in host:
        if name.startswith(SECTION_PREFIX):
            spans.setdefault(name[len(SECTION_PREFIX):], []).append(
                (s, s + d))
    sections = {}
    for name, ivs in spans.items():
        busy = sum(overlap(m, lo, hi) for m in merged
                   for lo, hi in ivs) / len(merged)
        sections[name] = {"calls": len(ivs), "busy_s": busy / 1e9,
                          "wall_s": sum(hi - lo for lo, hi in ivs) / 1e9}
    window = merge(iv for name in steady for iv in spans.get(name, []))
    window_s = sum(hi - lo for lo, hi in window) / 1e9
    busy_s = sum(overlap(m, lo, hi) for m in merged
                 for lo, hi in window) / len(merged) / 1e9
    op_totals: dict = {}
    for ops in devices:
        inside = [ev for ev in ops
                  if any(lo <= ev[0] < hi for lo, hi in window)]
        for name, secs in self_times(inside).items():
            op_totals[name] = op_totals.get(name, 0.0) + secs / len(devices)
    idle: dict = {}
    named = [ev for ev in host if ev[2] in labels]
    for lo, hi in window:
        for s, e in gaps(merged[0], lo, hi):
            name = label_at((s + e) / 2, named, labels)
            idle[name] = idle.get(name, 0.0) + (e - s) / 1e9

    def top(totals):
        return [[k, v] for k, v in sorted(totals.items(),
                                          key=lambda kv: -kv[1])[:10]]

    return {"sections": sections, "window_s": window_s, "busy_s": busy_s,
            "n_devices": len(devices),
            "n_device_ops": sum(len(ops) for ops in devices),
            "device_ops": top(op_totals), "idle_gaps": top(idle)}
