"""Layer: stream. The share of the traced whole solves' wall time the host
loop spent on getting chunks to the chip and not on anything else: the
program's counters ``stream.issue_seconds`` (`DeviceChunkRing`: the host
seconds inside the calls that hand a chunk to the runtime — on the v5e an
upload in row pieces, which returns only when all but its last pieces have
landed) plus ``stream.stall_seconds`` (the seconds inside
`block_until_ready` on the chunk about to be handed out), ÷ the wall
seconds of the ``unit`` sections. It is the device's idle share plus the
device time that runs WHILE the host issues (the in-place assembly of the
chunk being uploaded); a program without ``stream.issue_seconds`` reports
the ring's waits alone."""


def read(ctx):
    counters = ctx["telemetry"]["counters"]
    stall = counters.get("stream.stall_seconds")
    unit = ctx["trace"]["sections"].get("unit")
    if stall is None or not unit or not unit["wall_s"]:
        return None
    waited = stall + counters.get("stream.issue_seconds", 0.0)
    return 100.0 * waited / unit["wall_s"]
