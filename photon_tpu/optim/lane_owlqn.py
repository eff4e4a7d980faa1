"""OWL-QN over G regularization lanes in LANE-MINOR layout.

Reference parity: com.linkedin.photon.ml.optimization.OWLQN driven once per
grid point by the reference's hyperparameter sweep (its forced optimizer for
any L1 term). Like optim.lane_lbfgs, the whole sweep is ONE compiled
lock-step solver with a trailing lane axis — and the payoff is the same:
every backtracking line-search trial's margin is one shared
(n, d_sel) × (d_sel, G) pass over X for ALL lanes, where the vmapped
lane-major fallback pays the full X traffic per lane (measured ~5× per
lane at d = 10M for the L-BFGS analog, docs/PERF.md).

Differences from the scalar solver (optim/owlqn.py), all masked per lane:

- the backtracking Armijo search runs lock-step with sticky per-lane
  success freezing (a successful lane keeps its step length while the rest
  keep halving);
- OWL-QN's projected trial point breaks margin linearity (the orthant
  projection zeroes a data-dependent coordinate set), so unlike the
  margin-cached L-BFGS there is no z + a·dz shortcut — each trial pays
  one SHARED X pass; the accepted lane's trial margin is carried out of
  the search, so the outer step adds only the gradient's Xᵀ pass;
- the (s, y) history is the lane L-BFGS's (optim/lane_lbfgs.LaneHistory:
  globally rotating slot, per-(slot, lane) validity masks, carried inner
  products, f32 steering scalars from the unrounded pair, optional bf16
  storage); its products are taken with the PSEUDO-gradient at the new
  point, inside the push's one reduction pass.

Numerics per lane match the scalar OWL-QN to f32 reduction noise (pinned
by tests/test_lane_solver.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.ops import lane_objective as lo
from photon_tpu.optim.lane_lbfgs import (LaneHistory, _push_lanes,
                                          empty_lane_history,
                                          two_loop_lanes)
from photon_tpu.optim.config import stop_state
from photon_tpu.optim.tracker import OptResult


def pseudo_gradient_lanes(W, g, l1s, mask):
    """∂F selection per lane: for W_dj = 0 pick the one-sided derivative
    closest to 0 (Andrew & Gao). W/g: (d, G); l1s: (G,); mask: (d,) or
    scalar 1.0."""
    lam = jnp.asarray(mask)[..., None] * l1s[None, :] \
        if jnp.ndim(mask) else mask * l1s[None, :]
    right = g + lam
    left = g - lam
    pg_zero = jnp.where(right < 0.0, right, jnp.where(left > 0.0, left, 0.0))
    return jnp.where(W != 0.0, g + lam * jnp.sign(W), pg_zero)


class _LaneState(NamedTuple):
    W: jax.Array       # (d, G)
    z: jax.Array       # (n, G) margin at W, shard-local (no chaining:
    #                    every accepted column came fresh from its trial's
    #                    margin_lanes(W_try), so there is no f32 drift to
    #                    refresh away)
    f: jax.Array       # (G,) smooth part (data loss + L2)
    F: jax.Array       # (G,) f + L1
    g: jax.Array       # (d, G) smooth gradient
    h: LaneHistory     # its sv / yv are against the pseudo-gradient at W
    it: jax.Array
    evals: jax.Array   # () lock-step line-search evaluations so far
    its: jax.Array     # (G,)
    done: jax.Array    # (G,)
    converged: jax.Array
    failed: jax.Array
    hist: jax.Array    # (max_iters + 1, G)
    ghist: jax.Array


class _LaneLS(NamedTuple):
    a: jax.Array     # (G,) current/accepted step length
    F: jax.Array     # (G,) objective at the accepted point
    z: jax.Array     # (n, G) margin at the accepted point (trial reuse)
    succ: jax.Array  # (G,) sticky per-lane success
    i: jax.Array


def minimize_owlqn_lanes(
    obj,              # ops.objective.Objective (smooth part; l2 via l2s)
    l2s: jax.Array,   # (G,) per-lane smooth L2 weights
    l1s: jax.Array,   # (G,) per-lane L1 weights
    batch,
    W0: jax.Array,    # (d, G)
    max_iters: int = 100,
    tolerance: float = 1e-7,
    history: int = 10,
    max_ls_evals: int = 20,
    reg_mask=None,
    history_dtype=None,
) -> OptResult:
    """Lock-step lane-minor OWL-QN; same return convention as
    optim.lane_lbfgs.minimize_lbfgs_margin_lanes (lane axis LAST)."""
    W0 = jnp.asarray(W0, jnp.float32)
    d, G = W0.shape
    m = history
    dtype = W0.dtype
    hdtype = jnp.dtype(history_dtype) if history_dtype is not None else dtype
    mask = 1.0 if reg_mask is None else jnp.asarray(reg_mask, dtype)
    c1 = 1e-4

    def l1_term(W):
        absw = jnp.abs(W) if reg_mask is None else mask[:, None] * jnp.abs(W)
        return l1s * jnp.sum(absw, axis=0)

    z0 = lo.margin_lanes(obj, W0, batch)
    f0, g0 = lo.value_and_grad_at_margin_lanes(obj, l2s, W0, z0, batch)
    F0 = f0 + l1_term(W0)
    pg0 = pseudo_gradient_lanes(W0, g0, l1s, mask)
    pg0norm = jnp.sqrt(jnp.sum(pg0 * pg0, axis=0))
    hist0 = jnp.full((max_iters + 1, G), jnp.nan, dtype).at[0].set(F0)
    ghist0 = jnp.full((max_iters + 1, G), jnp.nan, dtype).at[0].set(pg0norm)

    def cond(s: _LaneState):
        return jnp.any(~s.done) & (s.it < max_iters)

    def body(s: _LaneState):
        active = ~s.done
        pg = pseudo_gradient_lanes(s.W, s.g, l1s, mask)
        D = -two_loop_lanes(s.h, pg)
        # Orthant constraint on the direction (Andrew & Gao p_k).
        D = jnp.where(D * pg < 0.0, D, 0.0)
        dphi0 = jnp.sum(D * pg, axis=0)
        bad_dir = dphi0 >= 0.0
        D = jnp.where(bad_dir[None, :], -pg, D)
        dphi0 = jnp.where(bad_dir, -jnp.sum(pg * pg, axis=0), dphi0)

        xi = jnp.where(s.W != 0.0, jnp.sign(s.W), jnp.sign(-pg))

        def project(W):
            return jnp.where(W * xi > 0.0, W, 0.0)

        def F_at(a):
            """One SHARED X pass for all lanes' projected trial points.
            Also returns the trial margins: the accepted lane's column is
            exactly the margin the outer step needs, so the caller never
            re-derives it (saves one full X pass per iteration)."""
            W_try = project(s.W + a[None, :] * D)
            z_try = lo.margin_lanes(obj, W_try, batch)
            f_try = lo.value_at_margin_lanes(obj, l2s, W_try, z_try, batch)
            dec = jnp.sum(pg * (W_try - s.W), axis=0)
            return f_try + l1_term(W_try), dec, z_try

        has_hist = jnp.any(s.h.valid, axis=0)
        dnorm = jnp.sqrt(jnp.sum(D * D, axis=0))
        a0 = jnp.where(has_hist, 1.0, 1.0 / jnp.maximum(dnorm, 1.0))

        frozen = s.done  # outer-done lanes never move

        def ls_cond(t: _LaneLS):
            return jnp.any(~t.succ & ~frozen) & (t.i < max_ls_evals)

        def ls_body(t: _LaneLS):
            F_try, dec, z_try = F_at(t.a)
            ok_now = ((F_try <= s.F + c1 * dec) & (dec < 0.0)
                      & jnp.isfinite(F_try))
            moved = ~t.succ & ~frozen  # lanes this trial actually probed
            acc = moved & ok_now
            return _LaneLS(
                a=jnp.where(moved & ~ok_now, 0.5 * t.a, t.a),
                F=jnp.where(acc, F_try, t.F),
                z=jnp.where(acc[None, :], z_try, t.z),
                succ=t.succ | acc,
                i=t.i + 1,
            )

        ls = lax.while_loop(
            ls_cond, ls_body,
            _LaneLS(a=jnp.asarray(a0, dtype), F=s.F, z=s.z,
                    succ=jnp.zeros((G,), bool), i=jnp.zeros((), jnp.int32)))

        step = active & ls.succ
        W_new = jnp.where(step[None, :],
                          project(s.W + ls.a[None, :] * D), s.W)
        # The accepted margins were already computed by the line search
        # (ls.z; rejected/frozen lanes keep s.z), so the outer step pays
        # ONE lane-stacked X^T pass for the gradient — no margin re-derive.
        z_new = jnp.where(step[None, :], ls.z, s.z)
        f_new, g_new = lo.value_and_grad_at_margin_lanes(
            obj, l2s, W_new, z_new, batch)
        f_new = jnp.where(step, f_new, s.f)
        g_new = jnp.where(step[None, :], g_new, s.g)
        F_new = jnp.where(step, ls.F, s.F)

        # the push takes the history's products with the NEXT direction's
        # vector — the pseudo-gradient at W_new (as optim.owlqn)
        pg_new = pseudo_gradient_lanes(W_new, g_new, l1s, mask)
        h = _push_lanes(s.h, W_new - s.W, g_new - s.g, step, pg_new)
        pgnorm = jnp.sqrt(jnp.sum(pg_new * pg_new, axis=0))
        grad_conv = pgnorm <= tolerance * jnp.maximum(1.0, pg0norm)
        f_conv = ls.succ & (
            jnp.abs(s.F - F_new)
            <= tolerance * jnp.maximum(
                jnp.maximum(jnp.abs(s.F), jnp.abs(F_new)), 1e-12))
        noise = 4.0 * jnp.finfo(dtype).eps * jnp.maximum(jnp.abs(s.F), 1.0)
        precision_limited = (~ls.succ) & (jnp.abs(dphi0) <= noise)
        converged = grad_conv | f_conv | precision_limited

        done, converged, failed = stop_state(
            tolerance, (s.done, s.converged, s.failed),
            jnp.where(active, converged, s.converged),
            active & (converged | ~ls.succ), active & ~ls.succ & ~converged)
        it = s.it + 1
        its = jnp.where(active, s.its + 1, s.its)
        return _LaneState(
            W=W_new, z=z_new, f=f_new, F=F_new, g=g_new, h=h, it=it,
            evals=s.evals + ls.i, its=its,
            done=done, converged=converged, failed=failed,
            hist=s.hist.at[it].set(jnp.where(active, F_new, s.hist[it])),
            ghist=s.ghist.at[it].set(jnp.where(active, pgnorm, s.ghist[it])),
        )

    init = _LaneState(
        W=W0, z=z0, f=f0, F=F0, g=g0,
        h=empty_lane_history(m, d, G, hdtype),
        it=jnp.zeros((), jnp.int32),
        evals=jnp.zeros((), jnp.int32),
        its=jnp.zeros((G,), jnp.int32),
        done=pg0norm <= 1e-14, converged=pg0norm <= 1e-14,
        failed=jnp.zeros((G,), bool),
        hist=hist0, ghist=ghist0,
    )
    out = lax.while_loop(cond, body, init)
    pg_fin = pseudo_gradient_lanes(out.W, out.g, l1s, mask)
    return OptResult(
        w=out.W, value=out.F,
        grad_norm=jnp.sqrt(jnp.sum(pg_fin * pg_fin, axis=0)),
        iterations=out.its, converged=out.converged, failed=out.failed,
        loss_history=out.hist, grad_norm_history=out.ghist,
        evaluations=out.evals,
    )
