"""Lane-stacked GLM objective: G regularization lanes solved lock-step in
LANE-MINOR layout — coefficients (d, G), margins (n, G), scalars (G,).

Reference parity: the reference's grid mode trains each regularization
weight as its own Spark job (GameEstimator.fit over a λ grid). The
TPU-native form runs every lane in one program; this module is the layout
that makes that form actually FAST. The earlier lane-major route —
`jax.vmap` over a (G, d) leading lane axis (models.training._train_run_grid)
— multiplies per-lane cost instead of sharing it: batched gathers/scatters
on a (G, d) array touch G scattered cache lines per index and JAX's
batching rules control the internal layout, not us. Lane-minor turns:

- the hot-block matvec into ONE (n, d_sel) × (d_sel, G) MXU matmul,
- every tail gather/scatter into the SAME number of random accesses as a
  single lane, each moving G contiguous floats (a native 128-lane vector
  when G ≥ 8 or padded),
- every O(d) solver-state pass into an O(d·G) coalesced pass that amortizes
  the per-op dispatch floor across the sweep.

Functions mirror ops.objective.Objective's margin-space API; the base
``Objective`` supplies task/axis_name/reg_mask/normalization, and per-lane
L2 weights arrive as an explicit ``l2s: (G,)`` array. Priors are not
supported here (the grid API never passes them; models.training routes
prior solves to the single-lane path).
"""
from __future__ import annotations

import jax.numpy as jnp

from photon_tpu.data.dataset import GLMBatch
from photon_tpu.data.matrix import layout_matvec_lanes, rmatvec_lanes
from photon_tpu.ops.losses import loss_fns
from photon_tpu.ops.objective import Objective
from photon_tpu.telemetry import device_scope


def supports_lanes(obj: Objective) -> bool:
    """Whether the lane-minor path can run this objective (no priors; the
    fused single-lane pallas kernel is irrelevant here)."""
    return (obj.prior_mean is None and obj.prior_precision is None
            and obj.prior_full_precision is None)


def _per_lane(v):
    """A batch's per-row column as an (n, G)-broadcastable array: a row's
    one value for every lane, or — where it is already (n, G) — each
    lane's own (`data.matrix.EntityBlocks`' lanes, each an entity's)."""
    return v if v.ndim == 2 else v[:, None]


def _eff_w_lanes(obj: Objective, W):
    return W if obj.norm_factors is None else W * obj.norm_factors[:, None]


def margin_lanes(obj: Objective, W, batch: GLMBatch):
    """z(W): (n, G) per-row margins, LOCAL to this shard."""
    Wt = _eff_w_lanes(obj, W)
    z = layout_matvec_lanes(batch.X, Wt) + _per_lane(batch.offsets)
    if obj.norm_shifts is not None:
        z = z - (obj.norm_shifts @ Wt)[None, :]
    return z


def direction_margin_lanes(obj: Objective, P, batch: GLMBatch):
    """dz = ∂z/∂w · p per lane (offset-free), LOCAL: (n, G)."""
    Pt = _eff_w_lanes(obj, P)
    dz = layout_matvec_lanes(batch.X, Pt)
    if obj.norm_shifts is not None:
        dz = dz - (obj.norm_shifts @ Pt)[None, :]
    return dz


def _backprop_lanes(obj: Objective, batch: GLMBatch, Gm):
    """Pull an (n, G) per-row cotangent back to (d, G); returns the LOCAL
    (pre-psum) pieces, as Objective._backprop does."""
    gX = rmatvec_lanes(batch.X, Gm)
    gsum = jnp.sum(Gm, axis=0) if obj.norm_shifts is not None else None
    return gX, gsum


def _finish_backprop_lanes(obj: Objective, gX, gsum=None):
    out = gX
    if obj.norm_shifts is not None:
        out = out - obj.norm_shifts[:, None] * gsum[None, :]
    if obj.norm_factors is not None:
        out = out * obj.norm_factors[:, None]
    return out


def _reg_terms_lanes(obj: Objective, l2s, W):
    """(value (G,), grad (d, G)) of the per-lane L2 regularizer."""
    masked = W if obj.reg_mask is None else W * obj.reg_mask[:, None]
    value = 0.5 * l2s * jnp.sum(masked * W, axis=0)
    grad = l2s[None, :] * masked
    return value, grad


def ray_reg_coeffs_lanes(obj: Objective, l2s, W, P):
    """Per-lane (c0, c1, c2), each (G,): reg value along W + a∘P is exactly
    c0 + a·c1 + a²/2·c2 (quadratic in a, per lane)."""
    mask = 1.0 if obj.reg_mask is None else obj.reg_mask[:, None]
    mW = mask * W
    c0 = 0.5 * l2s * jnp.sum(mW * W, axis=0)
    c1 = l2s * jnp.sum(mW * P, axis=0)
    c2 = l2s * jnp.sum(mask * P * P, axis=0)
    return c0, c1, c2


def phi_at_ray_lanes(obj: Objective, z, dz, a, coeffs, batch: GLMBatch):
    """(φ(a), φ'(a)) per lane from cached margins — one (n, G) elementwise
    pass + two (G,)-vector psums; zero passes over X. ``a``: (G,)."""
    loss, d1, _ = loss_fns(obj.task)
    with device_scope("objective.loss"):
        za = z + a[None, :] * dz
        y = _per_lane(batch.y)
        wt = _per_lane(batch.weights)
        wl = wt * loss(za, y)
        wd = wt * d1(za, y) * dz
        f, dphi = obj._psum_many(jnp.sum(wl, axis=0), jnp.sum(wd, axis=0))
    c0, c1, c2 = coeffs
    return f + c0 + a * (c1 + 0.5 * a * c2), dphi + c1 + a * c2


def hvp_at_margin_lanes(obj: Objective, l2s, z, batch: GLMBatch, V,
                        dZv=None):
    """H·v per lane with the margin z cached (Gauss-Newton form, exact for
    GLMs): the d2 curve is evaluated on z, so an HVP is two shared X
    passes — one (or zero, when the caller passes ``dZv``) for the
    directions' margins and one lane-stacked backprop. V: (d, G);
    dZv: (n, G) if already computed (TRON's CG has it)."""
    _, _, d2 = loss_fns(obj.task)
    if dZv is None:
        dZv = direction_margin_lanes(obj, V, batch)
    r = _per_lane(batch.weights) * d2(z, _per_lane(batch.y)) * dZv
    gX, gsum = _backprop_lanes(obj, batch, r)
    hv = _finish_backprop_lanes(obj, *obj._psum_many(gX, gsum))
    masked = V if obj.reg_mask is None else obj.reg_mask[:, None] * V
    return hv + l2s[None, :] * masked


def value_at_margin_lanes(obj: Objective, l2s, W, z, batch: GLMBatch):
    """Per-lane SMOOTH objective value (data loss + L2) from cached
    margins — one (n, G) elementwise pass + one (G,)-vector psum, no X
    pass and no gradient. The lane OWL-QN's backtracking trials only need
    values (its Armijo test uses the pseudo-gradient computed once per
    iteration), so paying value_and_grad's Xᵀ pass per trial would double
    the line search's X traffic for nothing."""
    loss, _, _ = loss_fns(obj.task)
    with device_scope("objective.loss"):
        y = _per_lane(batch.y)
        wt = _per_lane(batch.weights)
        value = obj._psum_many(jnp.sum(wt * loss(z, y), axis=0))[0]
    rv, _ = _reg_terms_lanes(obj, l2s, W)
    return value + rv


def grad_at_margin_lanes(obj: Objective, l2s, W, z, batch: GLMBatch):
    """Per-lane gradient from cached margins — ONE lane-stacked Xᵀ pass."""
    _, d1, _ = loss_fns(obj.task)
    with device_scope("objective.loss"):
        r = _per_lane(batch.weights) * d1(z, _per_lane(batch.y))
    gX, gsum = _backprop_lanes(obj, batch, r)
    grad = _finish_backprop_lanes(obj, *obj._psum_many(gX, gsum))
    _, rg = _reg_terms_lanes(obj, l2s, W)
    return grad + rg


def value_and_grad_at_margin_lanes(obj: Objective, l2s, W, z,
                                   batch: GLMBatch):
    """(f (G,), g (d, G)) from cached margins."""
    loss, d1, _ = loss_fns(obj.task)
    with device_scope("objective.loss"):
        y = _per_lane(batch.y)
        wt = _per_lane(batch.weights)
        r = wt * d1(z, y)
        local_value = jnp.sum(wt * loss(z, y), axis=0)
    gX, gsum = _backprop_lanes(obj, batch, r)
    value, gX, gsum = obj._psum_many(local_value, gX, gsum)
    grad = _finish_backprop_lanes(obj, gX, gsum)
    rv, rg = _reg_terms_lanes(obj, l2s, W)
    return value + rv, grad + rg
