"""Bytes a chip must SEND for an all-reduce of a payload over S chips.

A ring all-reduce is a reduce-scatter and an all-gather: in each of the
2·(S − 1) steps a chip sends one S-th of the payload to its neighbour, so
it sends 2·(S − 1)/S payloads in all — 1.5 for S = 4, → 2 as S grows. No
algorithm that leaves the whole sum on every chip sends less from the
busiest chip, so this is the least traffic the collective allows, as
`xpass_bytes` is for an evaluation's HBM traffic.
"""
from __future__ import annotations


def ring_all_reduce_sent_bytes(payload_bytes: float, n_chips: int) -> float:
    """Bytes ONE chip sends to all-reduce ``payload_bytes`` over
    ``n_chips`` chips; 0 for a single chip (nothing crosses a link)."""
    if n_chips < 1:
        raise ValueError(f"an all-reduce over {n_chips} chips")
    return 2.0 * (n_chips - 1) / n_chips * float(payload_bytes)
