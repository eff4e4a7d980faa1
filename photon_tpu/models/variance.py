"""Coefficient variance computation.

Reference parity: com.linkedin.photon.ml.optimization.VarianceComputationType
{NONE, SIMPLE, FULL} and DistributedOptimizationProblem.computeVariances:
- SIMPLE: var_j = 1 / H_jj (inverse of the Hessian diagonal)
- FULL:   var = diag(H^{-1}) via Cholesky (small feature spaces only)

FULL factors H = Xᵀ·diag(w·ℓ″)·X + diag((l2 + τ)·mask) as L·Lᵀ and reads
diag(H⁻¹) = diag(L⁻ᵀ·L⁻¹) as the column sums of squares of L⁻¹: one
factorization and one triangular inverse, no (d, d) solve against the
identity. H carries the L2 weight and any prior precision, so it is
positive definite as it stands and takes no jitter. The Gram and the factor
run at HIGHEST precision: a default-precision product on the TPU rounds its
operands to bf16 (2.4e-3 relative, PERF.md section 6), far over what a
variance is compared with.
"""
from __future__ import annotations

import enum

import jax
import jax.numpy as jnp

from photon_tpu.data.dataset import GLMBatch
from photon_tpu.ops.objective import Objective


class VarianceComputationType(enum.Enum):
    NONE = "none"
    SIMPLE = "simple"
    FULL = "full"


def inverse_diagonal(H: jax.Array) -> jax.Array:
    """diag(H⁻¹) of a symmetric positive definite H by its Cholesky factor:
    Σ_i (L⁻¹)_ij² over the rows of the factor's inverse."""
    with jax.default_matmul_precision("highest"):
        L = jnp.linalg.cholesky(H)
        Linv = jax.lax.linalg.triangular_solve(
            L, jnp.eye(H.shape[0], dtype=H.dtype), left_side=True,
            lower=True)
    return jnp.sum(Linv * Linv, axis=0)


def compute_variances(
    obj: Objective, w: jax.Array, batch: GLMBatch, kind: VarianceComputationType
):
    if kind is VarianceComputationType.NONE:
        return None
    if kind is VarianceComputationType.SIMPLE:
        return 1.0 / jnp.maximum(obj.hess_diag(w, batch), 1e-12)
    with jax.default_matmul_precision("highest"):
        H = obj.full_hessian(w, batch)
    return inverse_diagonal(H)
