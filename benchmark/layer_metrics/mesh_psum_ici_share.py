"""Layer: mesh. The gradient all-reduces' share of their roofline: the
bytes a chip must send for a ring all-reduce of the payload
(`lib/psum_bytes.py` of the program's counter ``mesh.psum_bytes``, over the
configuration's shards) ÷ the device self time under ``mesh.psum`` of the
traced solves ÷ the chip's interconnect peak (`lib/ici_peaks.py`). The
scope's time also holds the line search's scalar all-reduces, which add
time and no counted byte."""
from benchmark.layer_metrics.mesh_psum_ms import SCOPE
from benchmark.lib.ici_peaks import ici_peak
from benchmark.lib.psum_bytes import ring_all_reduce_sent_bytes
from benchmark.lib.scope_reduce import unit_scopes


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def read(ctx):
    payload = ctx["telemetry"]["counters"].get("mesh.psum_bytes")
    table = unit_scopes()
    if not payload or table is None or ctx["peaks"] is None:
        return None
    seconds = table["scopes"].get(SCOPE)
    if not seconds:
        return None
    peak = ici_peak(_device_kind())["ici_bytes_per_s"]
    sent = ring_all_reduce_sent_bytes(payload, int(ctx["config"]["n_shards"]))
    return 100.0 * sent / seconds / peak
