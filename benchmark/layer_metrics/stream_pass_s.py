"""Layer: stream. Mean seconds of ONE pass of the chunk ladder over the
host link in the traced whole solves: the program's host spans
``stream.pass`` (one around every pass a streamed solver makes, first
chunk asked for to the readback that closes it) ÷ its counter
``stream.passes``. Host clock. A program without the span reports
nothing."""


def pass_seconds(ctx):
    """Total seconds under ``stream.pass`` spans, whatever encloses them;
    None where there is none."""
    found = [v for k, v in ctx["telemetry"]["span_totals"].items()
             if k.split("/")[-1] == "stream.pass"]
    return sum(found) if found else None


def read(ctx):
    seconds = pass_seconds(ctx)
    passes = ctx["telemetry"]["counters"].get("stream.passes")
    if seconds is None or not passes:
        return None
    return seconds / passes
