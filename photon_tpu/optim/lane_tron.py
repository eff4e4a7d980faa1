"""Margin-cached TRON over G regularization lanes in LANE-MINOR layout.

Reference parity: com.linkedin.photon.ml.optimization.TRON (LIBLINEAR's
tron.cpp) driven once per grid point by the reference's sweep. Completes
the lane-minor grid story (optim.lane_lbfgs for smooth L-BFGS sweeps,
optim.lane_owlqn for L1): a TRON reg sweep runs as ONE lock-step program
where every Steihaug-CG Hessian-vector product and every trial-margin
pass over X is SHARED by all lanes.

Same savings as the scalar margin-cached TRON (optim.tron.
minimize_tron_margin), per lane:
- Gauss-Newton d2 on the cached z: each CG HVP is one lane-stacked
  backprop (the direction's margin dz is reused from the CG state);
- CG accumulates the candidate step's margin zp alongside p, so a
  trust-region trial is elementwise — a rejected step costs zero X
  passes;
- Hp for the predicted reduction comes from the CG residual invariant.

Lock-step masking: the CG inner loop runs until every lane's subproblem
terminates (boundary hit / residual tolerance), converged lanes' carries
frozen; the outer loop freezes converged/stuck lanes exactly as
optim.lane_lbfgs does. Trust-region acceptance and radius updates reuse
optim.tron's elementwise `_tr_update` / `_tr_stops` on (G,) arrays.

Numerics per lane match the scalar margin-cached TRON to f32 reduction
noise (pinned by tests/test_lane_solver.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.ops import lane_objective as lo
from photon_tpu.optim.tron import _tr_stops, _tr_update
from photon_tpu.optim.config import stop_state
from photon_tpu.optim.tracker import OptResult

_Z_REFRESH = 64  # as optim.tron: accept-chained margin re-derivation period


def _cg_step_geometry_lanes(p, dvec, Hd, rsq, delta):
    """Per-lane Steihaug step geometry (optim.tron._cg_step_geometry with
    axis-0 contractions): (step (G,), take_boundary (G,))."""
    dHd = jnp.sum(dvec * Hd, axis=0)
    alpha = rsq / jnp.maximum(dHd, 1e-20)
    pa = p + alpha[None, :] * dvec
    over = jnp.sqrt(jnp.sum(pa * pa, axis=0)) >= delta
    pd = jnp.sum(p * dvec, axis=0)
    dd = jnp.sum(dvec * dvec, axis=0)
    pp = jnp.sum(p * p, axis=0)
    rad = jnp.sqrt(jnp.maximum(pd * pd + dd * (delta * delta - pp), 0.0))
    theta = (rad - pd) / jnp.maximum(dd, 1e-20)
    take_boundary = over | (dHd <= 0.0)
    return jnp.where(take_boundary, theta, alpha), take_boundary


class _CGLaneState(NamedTuple):
    p: jax.Array    # (d, G) solution accumulator
    zp: jax.Array   # (n, G) margin of p
    r: jax.Array    # (d, G) residual
    dvec: jax.Array
    dz: jax.Array   # (n, G) margin of dvec
    rsq: jax.Array  # (G,)
    it: jax.Array
    done: jax.Array  # (G,)


def _cg_trust_margin_lanes(obj, l2s, z, batch, g, delta, max_cg: int,
                           tol_factor=0.1, done0=None):
    """Lock-step per-lane Steihaug-CG on the margin-cached Hessian.
    Returns (p, zp, r): per-lane step, its margin, and the final residual
    (Hp = -g - r for lanes whose subproblem ran).

    ``done0``: outer-converged lanes, seeded as CG-done so a frozen lane's
    discarded subproblem can't drag the lock-step loop to ITS residual
    tolerance after every active lane terminated (the wolfe_line_search_
    lanes done0 hazard, CG-shaped). A seeded lane returns p = 0, r = -g
    ⇒ Hp = 0 ⇒ pred = 0 ⇒ rejected — and the caller's step mask discards
    it anyway."""
    gnorm = jnp.sqrt(jnp.sum(g * g, axis=0))
    cg_tol = tol_factor * gnorm

    def cond(s: _CGLaneState):
        return jnp.any(~s.done) & (s.it < max_cg)

    def body(s: _CGLaneState):
        act = ~s.done
        Hd = lo.hvp_at_margin_lanes(obj, l2s, z, batch, s.dvec, dZv=s.dz)
        step, take_boundary = _cg_step_geometry_lanes(
            s.p, s.dvec, Hd, s.rsq, delta)
        step = jnp.where(act, step, 0.0)
        p_new = s.p + step[None, :] * s.dvec
        zp_new = s.zp + step[None, :] * s.dz
        r_new = jnp.where(act[None, :], s.r - step[None, :] * Hd, s.r)
        rsq_new = jnp.where(act, jnp.sum(r_new * r_new, axis=0), s.rsq)
        small = jnp.sqrt(rsq_new) <= cg_tol
        beta = rsq_new / jnp.maximum(s.rsq, 1e-20)
        d_new = jnp.where(act[None, :],
                          r_new + beta[None, :] * s.dvec, s.dvec)
        done_new = s.done | (act & (take_boundary | small))
        # One shared X pass refreshes every continuing lane's dz; skipped
        # entirely on the terminating iteration (scalar-pred cond — this
        # solver is never vmapped).
        dz_new = lax.cond(
            jnp.all(done_new),
            lambda: s.dz,
            lambda: lo.direction_margin_lanes(obj, d_new, batch),
        )
        return _CGLaneState(
            p=p_new, zp=zp_new, r=r_new, dvec=d_new, dz=dz_new,
            rsq=rsq_new, it=s.it + 1, done=done_new,
        )

    r0 = -g
    done_init = (jnp.zeros((g.shape[1],), bool) if done0 is None
                 else jnp.asarray(done0))
    init = _CGLaneState(
        p=jnp.zeros_like(g), zp=jnp.zeros_like(z), r=r0, dvec=r0,
        dz=lo.direction_margin_lanes(obj, r0, batch),
        rsq=jnp.sum(r0 * r0, axis=0),
        it=jnp.zeros((), jnp.int32),
        done=done_init,
    )
    out = lax.while_loop(cond, body, init)
    return out.p, out.zp, out.r


class _LaneState(NamedTuple):
    W: jax.Array      # (d, G)
    z: jax.Array      # (n, G) cached margins, shard-local
    f: jax.Array      # (G,)
    g: jax.Array      # (d, G)
    delta: jax.Array  # (G,) per-lane trust radius
    it: jax.Array
    its: jax.Array    # (G,)
    done: jax.Array   # (G,)
    converged: jax.Array
    failed: jax.Array
    hist: jax.Array   # (max_iters + 1, G)
    ghist: jax.Array


def minimize_tron_margin_lanes(
    obj,              # ops.objective.Objective (l2 field unused; see l2s)
    l2s: jax.Array,   # (G,) per-lane smooth L2 weights
    batch,
    W0: jax.Array,    # (d, G)
    max_iters: int = 100,
    tolerance: float = 1e-7,
    cg_max_iters: int = 20,
) -> OptResult:
    """Lock-step lane-minor margin-cached TRON; same return convention as
    optim.lane_lbfgs.minimize_lbfgs_margin_lanes (lane axis LAST)."""
    W0 = jnp.asarray(W0, jnp.float32)
    d, G = W0.shape
    dtype = W0.dtype

    z0 = lo.margin_lanes(obj, W0, batch)
    f0, g0 = lo.value_and_grad_at_margin_lanes(obj, l2s, W0, z0, batch)
    g0norm = jnp.sqrt(jnp.sum(g0 * g0, axis=0))
    hist0 = jnp.full((max_iters + 1, G), jnp.nan, dtype).at[0].set(f0)
    ghist0 = jnp.full((max_iters + 1, G), jnp.nan, dtype).at[0].set(g0norm)

    def cond(s: _LaneState):
        return jnp.any(~s.done) & (s.it < max_iters)

    def body(s: _LaneState):
        active = ~s.done
        p, zp, r = _cg_trust_margin_lanes(obj, l2s, s.z, batch, s.g,
                                          s.delta, cg_max_iters,
                                          done0=s.done)
        Hp = -s.g - r
        pred = -(jnp.sum(s.g * p, axis=0) + 0.5 * jnp.sum(p * Hp, axis=0))
        z_try = s.z + zp
        f_try = lo.value_at_margin_lanes(obj, l2s, s.W + p, z_try, batch)
        pnorm = jnp.sqrt(jnp.sum(p * p, axis=0))
        accept, actual, delta_new = _tr_update(s.f, f_try, pred, pnorm,
                                               s.delta)

        step = active & accept
        W_new = jnp.where(step[None, :], s.W + p, s.W)
        z_new = jnp.where(step[None, :], z_try, s.z)
        z_new = lax.cond(
            (s.it + 1) % _Z_REFRESH == 0,
            lambda: lo.margin_lanes(obj, W_new, batch),
            lambda: z_new,
        )
        f_new = jnp.where(step, f_try, s.f)
        # One shared X^T pass when ANY lane accepted; an all-rejected
        # iteration costs zero X passes, as in the scalar solver.
        g_new = lax.cond(
            jnp.any(step),
            lambda: jnp.where(
                step[None, :],
                lo.grad_at_margin_lanes(obj, l2s, W_new, z_new, batch), s.g),
            lambda: s.g,
        )

        gnorm = jnp.sqrt(jnp.sum(g_new * g_new, axis=0))
        converged, stuck = _tr_stops(accept, actual, pred, s.f, f_new,
                                     gnorm, g0norm, delta_new, tolerance,
                                     dtype)
        done, converged, failed = stop_state(
            tolerance, (s.done, s.converged, s.failed),
            jnp.where(active, converged, s.converged),
            active & (converged | stuck), active & stuck & ~converged)
        it = s.it + 1
        its = jnp.where(active, s.its + 1, s.its)
        return _LaneState(
            W=W_new, z=z_new, f=f_new, g=g_new,
            delta=jnp.where(active, delta_new, s.delta), it=it, its=its,
            done=done, converged=converged, failed=failed,
            hist=s.hist.at[it].set(jnp.where(active, f_new, s.hist[it])),
            ghist=s.ghist.at[it].set(jnp.where(active, gnorm, s.ghist[it])),
        )

    init = _LaneState(
        W=W0, z=z0, f=f0, g=g0,
        delta=jnp.maximum(g0norm, 1.0).astype(dtype),
        it=jnp.zeros((), jnp.int32), its=jnp.zeros((G,), jnp.int32),
        done=g0norm <= 1e-14, converged=g0norm <= 1e-14,
        failed=jnp.zeros((G,), bool),
        hist=hist0, ghist=ghist0,
    )
    out = lax.while_loop(cond, body, init)
    return OptResult(
        w=out.W, value=out.f,
        grad_norm=jnp.sqrt(jnp.sum(out.g * out.g, axis=0)),
        iterations=out.its, converged=out.converged, failed=out.failed,
        loss_history=out.hist, grad_norm_history=out.ghist,
    )
