"""Layer: stream. The chunk uploads' share of the host link's roofline:
the bytes the passes consumed (the program's counter
``stream.upload_bytes``: every leaf of every chunk handed to `device_put`,
`lib/stream_bytes.py` counts the same from the ladder's shapes and
`check` holds the two equal) ÷ the seconds under the ``stream.pass`` spans
÷ the most a program has moved over the chip's host link
(`lib/link_peaks.py`: a MEASURED ceiling — bare row pieces with nothing
computing — until a sourced peak of the interface is in the repository).
The time between passes (directions, margin-cached trials) is in neither,
so this is what the link reaches WHILE a pass streams, chunk programs and
all, against what it reaches with nothing else to do."""
from benchmark.layer_metrics.stream_pass_s import pass_seconds
from benchmark.lib.link_peaks import link_peak


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def read(ctx):
    moved = ctx["telemetry"]["counters"].get("stream.upload_bytes")
    seconds = pass_seconds(ctx)
    if not moved or not seconds or ctx["peaks"] is None:
        return None
    peak = link_peak(_device_kind())["host_to_device_bytes_per_s"]
    return 100.0 * moved / seconds / peak
