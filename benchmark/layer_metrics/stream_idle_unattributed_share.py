"""Layer: stream. The share of the traced whole solves' device idle time
that the program's spans do NOT account for: `lib/host_spans.py` splits
every idle gap of device plane 0 by overlap among the innermost program
span open at each instant; what falls under no span, or under one that
only frames others (``solve.lbfgs_streamed``, ``stream.pass``), ÷ all the
idle. It is the coverage of the instrumentation — near 0 when every
stretch in which the host keeps the device waiting has a span that says
what the host was doing — and the ``idle_by_span`` log line names where
the rest of the idle went. Nothing to report where the trace holds no
program span (the parent of PR 36) or no device operation."""
from benchmark.lib.host_spans import unit_host_spans


def read(ctx):
    table = unit_host_spans(rehearse=ctx["peaks"] is None)
    if (table is None or "stream.upload" not in table["spans"]
            or not table.get("idle_s")):
        return None
    return 100.0 * table["uncovered_s"] / table["idle_s"]
