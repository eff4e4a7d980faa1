"""Bytes the per-entity solves of one random-effect update of a
regularization grid must move when its G lanes share every pass over a
bucket's block, from the buckets' shapes and the solver's own step count.

A bucket is a dense (entities, rows, width) float32 block, read ONCE a pass
for all G lanes of an entity; beside it each lane reads and writes its own
vectors a pass: the rows' offsets and margins (entities × rows each) and
the coefficients and the gradient (entities × width each). The margin-
cached L-BFGS makes two passes (forward and transposed) a lock-step
iteration and two before the first (`re_bytes`); the lanes of a bucket step
together with their entities, so a bucket's passes follow its slowest
(entity, lane): ``game_re.block_steps`` ÷ ``game_re.blocks``. Nothing is
counted for the solver's state, labels and weights, or temporaries: the
least the algorithm allows, so the share of the roofline it gives is a
floor.
"""
from __future__ import annotations

from benchmark.lib.re_bytes import F32, PASSES_PER_STEP


def pass_bytes(blocks, lanes: int) -> int:
    """One pass over ``blocks`` ((entities, rows, width) shapes) for
    ``lanes`` lanes: each block once, each lane's four vectors."""
    return sum(int(e) * (int(m) * int(p) + lanes * 2 * (int(m) + int(p)))
               * F32 for e, m, p in blocks)


def update_bytes(coordinates: dict, steps: float, lanes: int) -> float:
    """The mean over the random-effect coordinates of one update's bytes,
    all ``lanes`` lanes of it: `pass_bytes` × 2 passes × (``steps`` + 1).
    ``coordinates``: {name: [(entities, rows, width)]}."""
    if not coordinates:
        return 0.0
    return sum(pass_bytes(b, lanes) * PASSES_PER_STEP * (float(steps) + 1.0)
               for b in coordinates.values()) / len(coordinates)
