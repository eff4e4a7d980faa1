"""The most bytes a second a program has MOVED from the host to one chip,
keyed by `device_kind`: the yardstick of `stream_link_share`. Beside
`peaks.py` and `ici_peaks.py`; a device that is not in the table is an
error, not a default.

This is a MEASURED ceiling, not a documented peak: no public document in
this repository says what a v5e chip's host interface is (a PCI Express
generation and width written here from memory would be a guess, and every
later share would be held against it). Until a `benchmark` PR brings a
sourced figure, the share is of the fastest rate any probe of this
repository has reached on the chip with nothing else running — so a
change that beats the probe's way of uploading can read over 100 %, and
has then found a new ceiling to write here.
"""
from __future__ import annotations

LINK_PEAKS = {
    # MEASURED (chip runs of PR 34, PERF.md §6): bare `device_put` of 32 MB
    # row pieces of a bf16 block with nothing computing. From PINNED host
    # memory 14.116–14.150 GB/s over 96 repetitions, four or sixteen pieces
    # in flight, beside four memory-streaming neighbours or none
    # (`pinned_probe.py`): the link by DMA alone. From pageable numpy
    # memory, through the runtime's staging copy, 13.35–14.04 on a quiet
    # host from one, two or four threads alike (`rss_probe.py`,
    # `link_threads.py`) and 9.8–11.8 beside those neighbours.
    "TPU v5 lite": {"host_to_device_bytes_per_s": 14.15e9,
                    "basis": "measured"},
}


def link_peak(device_kind: str) -> dict:
    if device_kind not in LINK_PEAKS:
        raise KeyError(f"no host-link peak for device kind "
                       f"{device_kind!r}; add a row with its basis to "
                       "benchmark/lib/link_peaks.py")
    return LINK_PEAKS[device_kind]
