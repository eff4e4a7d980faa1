"""Bytes the per-entity solves of one random-effect coordinate update must
move, from the buckets' shapes and the solver's own step count.

A bucket is a dense (entities, rows, width) float32 block. The margin-cached
L-BFGS the solves run makes two passes over the block for every lock-step
iteration — one forward (the direction's margins) and one transposed (the
gradient) — and two more before the first (the margins and the gradient at
the warm start); line-search trials run on cached margins and read no block.
The lanes of a bucket step together, so a bucket's passes follow its
SLOWEST lane: the program counts that as ``game_re.block_steps`` (Σ over
blocks of the largest iteration count over the block's lanes). Nothing is
counted for the solver's state, the labels, weights and offsets, or
temporaries: the least the algorithm allows, so the share of the roofline
it gives is a floor.
"""
from __future__ import annotations

F32 = 4
PASSES_PER_STEP = 2


def block_bytes(blocks) -> int:
    """Σ entities × rows × width × 4 over (entities, rows, width) shapes."""
    return sum(int(e) * int(m) * int(p) * F32 for e, m, p in blocks)


def solve_bytes(blocks, steps: float) -> float:
    """Bytes of one update's solves over ``blocks`` when every block takes
    ``steps`` lock-step iterations (the mean over the blocks, where they
    differ)."""
    return block_bytes(blocks) * PASSES_PER_STEP * (float(steps) + 1.0)


def update_bytes(coordinates: dict, steps: float) -> float:
    """The mean over the random-effect coordinates of `solve_bytes`: what
    an average random-effect update moves when each coordinate is updated
    equally often. ``coordinates``: {name: [(entities, rows, width)]}."""
    if not coordinates:
        return 0.0
    return sum(solve_bytes(b, steps) for b in coordinates.values()) \
        / len(coordinates)
