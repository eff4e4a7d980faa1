"""Floating-point operations of one random-effect update's FULL variances,
from the buckets' padded shapes.

An entity's variances are the diagonal of the inverse of its (w, w)
Hessian over a bucket's (m rows, w columns) block: the weighted Gram
Xᵀ·diag(D)·X, 2·m·w² (the product as the MXU runs it, both triangles), the
Cholesky factor, w³/3, and the diagonal of the inverse from the factor's
triangular inverse, w³/3. Lanes are the bucket's entities, each counted
once; nothing is counted for the curvature D, the regularization's
diagonal or the reduction of the inverse's squares.
"""
from __future__ import annotations


def lane_flops(m: int, w: int) -> float:
    return 2.0 * m * w * w + 2.0 * w ** 3 / 3.0


def coordinate_flops(blocks) -> float:
    """Σ over (entities, rows, width) buckets of entities × `lane_flops`."""
    return sum(float(e) * lane_flops(int(m), int(w)) for e, m, w in blocks)


def update_flops(coordinates: dict) -> float:
    """The mean over the random-effect coordinates of `coordinate_flops`:
    what an average random-effect update computes when each coordinate is
    updated equally often. ``coordinates``: {name: [(entities, rows,
    width)]}."""
    if not coordinates:
        return 0.0
    return sum(coordinate_flops(b) for b in coordinates.values()) \
        / len(coordinates)
