"""Inter-chip interconnect peak of one chip, keyed by `device_kind`, for
the share of its roofline a collective reaches (`mesh_psum_ici_share`).
Beside `peaks.py` (which no later PR edits); a device that is not in the
table is an error, not a default.
"""
from __future__ import annotations

ICI_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of inter-chip
    # interconnect bandwidth a chip = 200 GB/s, all four ports of the 2-D
    # torus together. Written from memory with no network to confirm it:
    # listed under `assumed` in configs/glm-sparse10m-mesh4.json. A 2x2
    # host wires two of a chip's four ports, so ~50 % of this figure is
    # that topology's ceiling.
    "TPU v5 lite": {"ici_bytes_per_s": 200e9},
}


def ici_peak(device_kind: str) -> dict:
    if device_kind not in ICI_PEAKS:
        raise KeyError(f"no interconnect peak for device kind "
                       f"{device_kind!r}; add a sourced row to "
                       "benchmark/lib/ici_peaks.py")
    return ICI_PEAKS[device_kind]
