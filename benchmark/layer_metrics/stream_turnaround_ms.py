"""Layer: stream. The host's own milliseconds a consumed chunk, with NO
upload call open and no wait for a chunk: (the ``stream.pass`` spans −
the ``stream.upload`` spans directly under them − the ``stream.handout``
spans) ÷ the counter ``stream.chunk_uploads``. What is left is the chunk
program's dispatch, the margin readback, the release of the chunk before,
the partial sums' `_acc`, the pass's closing readback and the generator's
own steps: the time per chunk the link has nothing to carry because the
host has not asked yet. With ``stream_upload_call_ms`` and
``stream_handout_wait_ms`` it adds up to ``stream_pass_s`` ÷ the chunks a
pass (an upload primed at a solve's end apart). A program without the
spans reports nothing."""
from benchmark.lib.host_spans import span_sum


def read(ctx):
    passes, n = span_sum(ctx, "stream.pass")
    uploads, _ = span_sum(ctx, "stream.upload", under="stream.pass")
    waits, handed = span_sum(ctx, "stream.handout", under="stream.pass")
    chunks = ctx["telemetry"]["counters"].get("stream.chunk_uploads")
    if not n or not handed or not chunks:
        return None
    return (passes - uploads - waits) / chunks * 1e3
