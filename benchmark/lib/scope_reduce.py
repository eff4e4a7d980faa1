"""Device time per named scope INSIDE the compiled solve: the program's
`photon_tpu.telemetry.DEVICE_SCOPES` (`jax.named_scope`, written into each
op's HLO ``op_name``) read back from the traced run's ``.xplane.pb``.

Where the scope path comes from (looked at on the chip, PR 25): through
`jax.profiler.ProfileData` an "XLA Ops" event of `/device:TPU:0` shows its
name (the op's whole HLO text, which holds no ``metadata={...}``) and three
per-event stats (``device_offset_ps``, ``device_duration_ps``, ``Time Scale
Multiplier``) — no ``op_name``. The raw XSpace has it: each event points at
an event-METADATA record whose stat ``tf_op`` is the ``op_name``, e.g.
``jit(_train_run)/while/body/lbfgs.linesearch/while/body/objective.loss/mul:``
(the route the issue calls c). So this module reads the protobuf itself —
the few fields it needs, by the wire format, with no import outside the
standard library — and takes times as `trace_reduce` does: a line's
``timestamp_ns`` plus the event's ``offset_ps``, host and device on the
trace's one clock.

An event's scope CHAIN is every component of its ``op_name`` that contains
a `DEVICE_SCOPES` name, in order — autodiff wraps components, so
``transpose(jvp(xpass.fwd))`` counts as ``xpass.fwd``; where one component
holds several names the longest is taken. The event's SCOPE is the last of
the chain, and ``unscoped`` when the chain is empty. A fusion is ONE event
carrying ONE ``op_name``, that of the op it is named by (its root): ops
fused across a scope boundary are all put to the root's scope, which this
reduction cannot see behind. The compiler also makes operations of its own
— the ``copy`` / ``reshape`` that changes a gather's layout — and gives
them NO ``op_name``: such an event takes the chain of the operation that
produced its first operand (``%copy.233 = ... copy(bf16[...] %fusion.160)``
is put where ``%fusion.160`` is), the latest event of that name before it
on the line. One whose operand no event produced (a loop-carried buffer)
stays ``unscoped``.

Times are SELF times (`trace_reduce.self_times`: a ``while`` covers its
body's ops on the one "XLA Ops" line), summed over the events that start
inside the ``bench.section.unit`` annotations and averaged over the device
planes, so the scopes and ``unscoped`` add up to the units' busy time.

`reduce_events` is plain arithmetic on tuples and is checked on synthetic
events by tests/test_device_scopes.py; `unit_scopes` is what the readers
under ``layer_metrics/`` call. With a program that has no scopes (the
parent of PR 25), a CPU rehearsal (no device plane, no ``tf_op``) or no
trace at all, `unit_scopes` returns ``None`` and the readers leave their
metric out.
"""
from __future__ import annotations

import functools
import glob
import json
import os
import re

from benchmark.lib.trace_reduce import (
    DEVICE_PLANE_PREFIX,
    OP_LINE,
    SECTION_PREFIX,
    merge,
    newest_xplane,
    self_times,
)

UNSCOPED = "unscoped"
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache", "trace")


# ----------------------------------------------------- protobuf wire format
def _varint(buf, at: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varint and fixed
    fields, a memoryview for a length-delimited one."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 1:
            value, at = int.from_bytes(buf[at:at + 8], "little"), at + 8
        elif wire == 5:
            value, at = int.from_bytes(buf[at:at + 4], "little"), at + 4
        else:
            raise ValueError(f"xplane: wire type {wire} is not read here")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view) -> tuple:
    """(key, value bytes) of one map<int64, message> entry."""
    key, value = 0, b""
    for number, v in _fields(view):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


# XSpace.planes=1 · XPlane: name=2 lines=3 event_metadata=4 stat_metadata=5
# XLine: name=2 timestamp_ns=3 events=4 · XEvent: metadata_id=1 offset_ps=2
# duration_ps=3 · XEventMetadata: name=2 stats=5 · XStatMetadata: name=2
# XStat: metadata_id=1 str_value=5 ref_value=7
def _plane(view) -> dict:
    """{"name", "lines": {line name: [(start_ps, duration_ps, metadata
    id)]}, "events": {metadata id: (name, tf_op or "")}}; starts are whole
    picoseconds since the epoch, too many digits for a float."""
    name, lines, event_md, stat_names = "", [], [], {}
    for number, v in _fields(view):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            event_md.append(_map_entry(v))
        elif number == 5:
            key, md = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for n, x in _fields(md) if n == 2), "")
    events = {}
    for key, md in event_md:
        md_name, tf_op = "", ""
        for number, v in _fields(md):
            if number == 2:
                md_name = _text(v)
            elif number == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                if 5 in stat:
                    tf_op = _text(stat[5])
                elif 7 in stat:  # a reference into the stat names
                    tf_op = stat_names.get(stat[7], "")
        events[key] = (md_name, tf_op)
    out_lines: dict = {}
    for line in lines:
        line_name, t0, evs = "", 0, []
        for number, v in _fields(line):
            if number == 2:
                line_name = _text(v)
            elif number == 3:
                t0 = v
            elif number == 4:
                evs.append(v)
        rows = out_lines.setdefault(line_name, [])
        for ev in evs:
            f = dict(_fields(ev))
            rows.append((t0 * 1000 + f.get(2, 0), f.get(3, 0), f.get(1, 0)))
    return {"name": name, "lines": out_lines, "events": events}


def load(path: str) -> dict:
    """{"devices": [[(start_ns, duration_ns, op_name path, HLO text)] per
    device plane], "host": [(start_ns, duration_ns, name)]} of an
    ``.xplane.pb``; times count from the trace's earliest event."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = [_plane(v) for number, v in _fields(space) if number == 1]
    base = min((s for p in planes for rows in p["lines"].values()
                for s, _, _ in rows), default=0)
    devices, host = [], []
    for plane in planes:
        names = plane["events"]
        if plane["name"].startswith(DEVICE_PLANE_PREFIX):
            ops = []
            for s, d, m in plane["lines"].get(OP_LINE, []):
                text, tf_op = names.get(m, ("", ""))
                ops.append(((s - base) / 1e3, d / 1e3, tf_op, text))
            devices.append(ops)
        else:
            host.extend(((s - base) / 1e3, d / 1e3,
                         names.get(m, ("", ""))[0])
                        for rows in plane["lines"].values()
                        for s, d, m in rows if d > 0)
    return {"devices": devices, "host": host}


# ------------------------------------------------------------- arithmetic
@functools.lru_cache(maxsize=None)  # a solve repeats a few hundred paths
def scope_chain(op_name: str, scopes: tuple) -> tuple:
    """The `scopes` names met along an ``op_name`` path, outermost first."""
    chain = []
    for part in op_name.split("/"):
        found = [s for s in scopes if s in part]
        if found:
            chain.append(max(found, key=len))
    return tuple(chain)


UNSCOPED_OPS = 8      # how many unscoped operations the table names
UNSCOPED_OP_CHARS = 100
_HLO_NAME = re.compile(r"%([\w.\-]+)")


def event_chains(ops, scopes) -> list:
    """[(start, duration, chain tuple, HLO text)] of one line's (start,
    duration, op_name path[, HLO text]) events, in start order; an event
    with no ``op_name`` at all takes its first operand's producer's."""
    produced: dict = {}   # HLO result name -> the chain of its latest event
    out = []
    for s, d, path, *rest in sorted(ops, key=lambda ev: (ev[0], -ev[1])):
        text = rest[0] if rest else ""
        chain = scope_chain(path, scopes)
        names = _HLO_NAME.findall(text)
        if not path and len(names) > 1:
            chain = produced.get(names[1], ())
        if names:
            produced[names[0]] = chain
        out.append((s, d, chain, text))
    return out


def reduce_events(devices: list, windows: list, scopes) -> dict:
    """{"scopes": {scope: seconds}, "chains": {"a>b": seconds}, "busy_s",
    "unscoped_ops": [[HLO text, seconds]]}: self time of the events that
    start inside `windows` (disjoint (start, end) pairs), by innermost
    scope and by whole chain, averaged over the device planes; ``busy_s``
    is the union of those events' intervals; ``unscoped_ops`` names the
    operations with most unscoped self time, for whoever adds the next
    scope."""
    chains: dict = {}
    loose: dict = {}
    busy = 0.0
    for ops in devices:
        inside = [(s, d, (">".join(chain), "") if chain
                   else (UNSCOPED, text[:UNSCOPED_OP_CHARS]))
                  for s, d, chain, text in event_chains(ops, scopes)
                  if any(lo <= s < hi for lo, hi in windows)]
        busy += sum(e - s for s, e in merge(
            (s, s + d) for s, d, _ in inside)) / 1e9 / len(devices)
        for (chain, text), secs in self_times(inside).items():
            chains[chain] = chains.get(chain, 0.0) + secs / len(devices)
            if chain == UNSCOPED:
                loose[text] = loose.get(text, 0.0) + secs / len(devices)
    by_scope: dict = {}
    for key, secs in chains.items():
        last = key.rsplit(">", 1)[-1]
        by_scope[last] = by_scope.get(last, 0.0) + secs
    return {"scopes": by_scope, "chains": chains, "busy_s": busy,
            "unscoped_ops": [[k, v] for k, v in sorted(
                loose.items(), key=lambda kv: -kv[1])[:UNSCOPED_OPS]]}


# ------------------------------------------------- what the readers call
@functools.lru_cache(maxsize=1)
def unit_scopes():
    """The per-scope table of the traced whole units of THIS run (the
    newest ``.xplane.pb`` under ``benchmark/.cache/trace/*/``, which
    `run.py` wrote just before the readers are called), read once per
    process and printed as one ``scopes`` log line; ``None`` where there is
    nothing to read."""
    try:
        from photon_tpu.telemetry import DEVICE_SCOPES
    except ImportError:  # a program from before the scopes
        return None
    traces = sorted(glob.glob(os.path.join(TRACE_ROOT, "*")),
                    key=os.path.getmtime)
    if not traces:
        return None
    try:
        trace = load(newest_xplane(traces[-1]))
    except FileNotFoundError:
        return None
    windows = merge((s, s + d) for s, d, name in trace["host"]
                    if name == SECTION_PREFIX + "unit")
    if not trace["devices"] or not windows:
        return None
    table = reduce_events(trace["devices"], windows, DEVICE_SCOPES)
    if set(table["scopes"]) <= {UNSCOPED}:
        return None  # the compiled program carries no scope
    print(json.dumps({"event": "scopes", **table}), flush=True)
    return table


def scope_ms_per_iteration(ctx: dict, wanted):
    """Milliseconds per lock-step solver iteration of the traced units
    (`sum(r["steps"])`, as ``solve_iter_device_ms`` divides) spent in the
    chains that `wanted(chain tuple)` accepts; ``None`` where there is no
    scope table or no iteration."""
    iterations = sum(r.get("steps", 0)
                     for r in ctx["results"].get("unit", []))
    table = unit_scopes()
    if table is None or not iterations:
        return None
    secs = sum(v for key, v in table["chains"].items()
               if wanted(tuple(key.split(">"))))
    return secs / iterations * 1e3
