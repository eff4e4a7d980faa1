"""The one table of device peaks the benchmark divides by, keyed by
`device_kind`. A device that is not in it is an error, not a default.

The row is copied from photon_tpu/profiling/ledger.py::DEVICE_PEAKS.
"""
from __future__ import annotations

DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM bandwidth, 16 GB HBM per chip.
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add a "
                       "sourced row to benchmark/lib/peaks.py")
    return DEVICE_PEAKS[device_kind]
