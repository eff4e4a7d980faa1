"""Layer: stream. Mean milliseconds the host spends inside ONE chunk's
upload call in the traced whole solves: the program's spans
``stream.upload`` (`DeviceChunkRing._top_up`, one around every call that
hands a chunk to the runtime), total ÷ count. An upload in row pieces
returns when all but its last sixteen pieces have crossed the link, so on
a link-bound stream this is the time the host's other work waited for the
link; the chunks a solve primes and drops are in it, as the link carried
them. A program without the span reports nothing."""
from benchmark.lib.host_spans import span_sum


def read(ctx):
    seconds, count = span_sum(ctx, "stream.upload")
    return seconds / count * 1e3 if count else None
