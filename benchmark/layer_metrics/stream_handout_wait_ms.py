"""Layer: stream. Mean milliseconds a consumed chunk waits at its
hand-out in the traced whole solves: the program's spans
``stream.handout`` (`DeviceChunkRing.stream_pass`: `block_until_ready` on
the chunk about to be yielded — its last pieces' transfers and in-place
writes) ÷ its counter ``stream.chunk_uploads`` (the chunks the passes
consumed). A program without the span reports nothing."""
from benchmark.lib.host_spans import span_sum


def read(ctx):
    seconds, count = span_sum(ctx, "stream.handout")
    chunks = ctx["telemetry"]["counters"].get("stream.chunk_uploads")
    return seconds / chunks * 1e3 if count and chunks else None
