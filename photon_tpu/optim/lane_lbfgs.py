"""Margin-cached L-BFGS over G regularization lanes in LANE-MINOR layout.

Reference parity: com.linkedin.photon.ml.optimization.LBFGS driven once per
grid point by the reference's hyperparameter sweep; here the whole sweep is
ONE compiled solver whose state carries a trailing lane axis — coefficients
(d, G), margins (n, G), history (m, d, G), scalars (G,).

Why not `jax.vmap(minimize_lbfgs_margin)`: vmap stacks lanes on a LEADING
axis and JAX's batching rules own the internal layout, so every tail
gather/scatter and every O(d) state pass multiplies per lane (measured
~5× cost at G=4 on the 10M-feature problem — worse than sequential).
Lane-minor keeps the lane axis where the TPU wants it: minor-most, 128-wide
vector lanes. See ops.lane_objective for the layout argument.

Differences from the scalar solver (optim/lbfgs.py), all masked per lane:
- the Wolfe search runs lock-step with sticky per-lane `done` freezing,
- the (s, y) history (`LaneHistory`) uses a globally rotating slot +
  per-slot per-lane validity masks instead of per-lane idx/count (a lane
  that skips a push — failed line search or failed curvature — just leaves
  its slot invalid); like the scalar `History` it carries its inner
  products, so an iteration reads the (m, d, G) buffers twice (one fused
  reduction pass in `_push_lanes`, one combination pass in
  `two_loop_lanes`) and writes one slot of each,
- converged/failed lanes freeze: their state stops updating while the
  remaining lanes run to their own convergence.

Numerics per lane match the scalar margin-cached solver to f32 reduction
noise (pinned by tests/test_lane_solver.py).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.ops import lane_objective as lo
from photon_tpu.optim.lbfgs import (_coefficients, _combine, _convergence,
                                     _pushed, _pushed_block, _recency,
                                     _to_recency, _to_slots)
from photon_tpu.optim.linesearch import C1, C2, _cubic_min
from photon_tpu.optim.config import stop_state
from photon_tpu.optim.tracker import OptResult
from photon_tpu.telemetry import device_scope

_Z_REFRESH = 64  # as optim.lbfgs: margin re-derivation period


class _LaneLSState(NamedTuple):
    phase: jax.Array   # (G,) 0 = bracketing, 1 = zoom
    done: jax.Array    # (G,) sticky
    i: jax.Array       # () global eval counter
    a: jax.Array       # (G,) next step length
    a_prev: jax.Array
    f_prev: jax.Array
    d_prev: jax.Array
    a_lo: jax.Array
    f_lo: jax.Array
    d_lo: jax.Array
    a_hi: jax.Array
    f_hi: jax.Array
    d_hi: jax.Array
    a_star: jax.Array
    f_star: jax.Array


def wolfe_line_search_lanes(
    phi: Callable,  # (G,) alphas -> ((G,) f, (G,) dphi)
    f0, dphi0, a_init, max_evals: int = 12, done0=None,
):
    """Per-lane strong-Wolfe search, lock-step: every loop iteration
    evaluates phi once for ALL lanes (one (n, G) elementwise pass); lanes
    that satisfy Wolfe freeze while the rest keep bracketing/zooming.
    Returns (alpha, f_alpha, ok), each (G,), and the lock-step number of
    `phi` evaluations taken, ().

    ``done0``: lanes already finished in the OUTER solver — seeded as done
    so a converged lane's frozen state can't drag every remaining search to
    max_evals on f32 noise (its a_star stays 0 → ok=False → the solver's
    own done mask keeps it frozen)."""
    f0 = jnp.asarray(f0)
    dtype = f0.dtype
    G = f0.shape[0]
    dphi0 = jnp.asarray(dphi0, dtype)
    zero = jnp.zeros((G,), dtype)

    def armijo(a, f):
        return f <= f0 + C1 * a * dphi0

    def body(s: _LaneLSState) -> _LaneLSState:
        f, d = phi(s.a)
        bad = jnp.isnan(f) | jnp.isinf(f)

        first = s.i == 0
        to_zoom_hi = bad | (~armijo(s.a, f)) | (~first & (f >= s.f_prev))
        wolfe_ok = (~to_zoom_hi) & (jnp.abs(d) <= -C2 * dphi0)
        to_zoom_rev = (~to_zoom_hi) & (~wolfe_ok) & (d >= 0.0)
        expand = (~to_zoom_hi) & (~wolfe_ok) & (~to_zoom_rev)

        br_phase = jnp.where(to_zoom_hi | to_zoom_rev, 1, 0)
        br_a_lo = jnp.where(to_zoom_hi, s.a_prev, s.a)
        br_f_lo = jnp.where(to_zoom_hi, s.f_prev, f)
        br_d_lo = jnp.where(to_zoom_hi, s.d_prev, d)
        br_a_hi = jnp.where(to_zoom_hi, s.a, s.a_prev)
        br_f_hi = jnp.where(to_zoom_hi, f, s.f_prev)
        br_d_hi = jnp.where(to_zoom_hi, d, s.d_prev)

        z_shrink_hi = bad | (~armijo(s.a, f)) | (f >= s.f_lo)
        z_wolfe_ok = (~z_shrink_hi) & (jnp.abs(d) <= -C2 * dphi0)
        z_flip = (~z_shrink_hi) & (d * (s.a_hi - s.a_lo) >= 0.0)
        z_a_lo = jnp.where(z_shrink_hi, s.a_lo, s.a)
        z_f_lo = jnp.where(z_shrink_hi, s.f_lo, f)
        z_d_lo = jnp.where(z_shrink_hi, s.d_lo, d)
        z_a_hi = jnp.where(z_shrink_hi, s.a, jnp.where(z_flip, s.a_lo, s.a_hi))
        z_f_hi = jnp.where(z_shrink_hi, f, jnp.where(z_flip, s.f_lo, s.f_hi))
        z_d_hi = jnp.where(z_shrink_hi, d, jnp.where(z_flip, s.d_lo, s.d_hi))

        in_zoom = s.phase == 1
        newly_done = jnp.where(in_zoom, z_wolfe_ok, wolfe_ok)
        a_lo = jnp.where(in_zoom, z_a_lo, br_a_lo)
        f_lo = jnp.where(in_zoom, z_f_lo, br_f_lo)
        d_lo = jnp.where(in_zoom, z_d_lo, br_d_lo)
        a_hi = jnp.where(in_zoom, z_a_hi, br_a_hi)
        f_hi = jnp.where(in_zoom, z_f_hi, br_f_hi)
        d_hi = jnp.where(in_zoom, z_d_hi, br_d_hi)
        interp_a = _cubic_min(a_lo, f_lo, d_lo, a_hi, f_hi, d_hi)
        interp_a = jnp.where(jnp.isfinite(f_hi) & jnp.isfinite(d_hi),
                             interp_a, 0.5 * (a_lo + a_hi))
        next_a = jnp.where(in_zoom | ~expand, interp_a, 2.0 * s.a)
        phase = jnp.where(in_zoom, 1, br_phase)

        better = armijo(s.a, f) & (f < s.f_star) & ~bad
        a_star = jnp.where(newly_done | better, s.a, s.a_star)
        f_star = jnp.where(newly_done | better, f, s.f_star)

        # Sticky freeze: lanes that were already done keep every field.
        frz = lambda old, new: jnp.where(s.done, old, new)
        return _LaneLSState(
            phase=frz(s.phase, phase), done=s.done | newly_done, i=s.i + 1,
            a=frz(s.a, next_a), a_prev=frz(s.a_prev, s.a),
            f_prev=frz(s.f_prev, f), d_prev=frz(s.d_prev, d),
            a_lo=frz(s.a_lo, a_lo), f_lo=frz(s.f_lo, f_lo),
            d_lo=frz(s.d_lo, d_lo), a_hi=frz(s.a_hi, a_hi),
            f_hi=frz(s.f_hi, f_hi), d_hi=frz(s.d_hi, d_hi),
            a_star=frz(s.a_star, a_star), f_star=frz(s.f_star, f_star),
        )

    def cond(s: _LaneLSState):
        return jnp.any(~s.done) & (s.i < max_evals)

    inf = jnp.full((G,), jnp.inf, dtype)
    done_init = (jnp.zeros((G,), bool) if done0 is None
                 else jnp.asarray(done0))
    init = _LaneLSState(
        phase=jnp.zeros((G,), jnp.int32), done=done_init,
        i=jnp.zeros((), jnp.int32), a=jnp.asarray(a_init, dtype),
        a_prev=zero, f_prev=f0, d_prev=dphi0,
        a_lo=zero, f_lo=f0, d_lo=dphi0, a_hi=inf, f_hi=inf, d_hi=inf,
        a_star=zero, f_star=f0,
    )
    out = lax.while_loop(cond, body, init)
    # Seeded-done lanes stay ok=False (alpha 0, nothing accepted) — the
    # caller's own done mask is what keeps them frozen.
    ok = (out.done & ~done_init) | (out.a_star > 0.0)
    return out.a_star, out.f_star, ok, out.i


class LaneHistory(NamedTuple):
    """The rotating (s, y) history of G lanes and the inner products the
    recursion needs (optim.lbfgs.History with a trailing lane axis: S, Y
    in slot order, the small blocks flat and in recency order, index 0 the
    newest slot).

    ``sy`` / ``yy`` are products of the STORED vectors — with a narrower
    ``history_dtype``, of the rounded ones, so the coefficient recursion
    is the vector-space recursion over what the slots really hold. What
    STEERS (``rho``, ``gamma``, curvature acceptance) is f32 from the
    UNROUNDED pair: with f32 storage that is the blocks' diagonals to the
    bit, with bf16 storage it is deliberately more accurate than they are
    (the guarantee the bf16 quality test pins)."""
    S: jax.Array      # (m, d, G) history dtype
    Y: jax.Array      # (m, d, G)
    sy: jax.Array     # (m·m, G) S[i]·Y[k] at i·m + k, per lane, f32
    yy: jax.Array     # (m·m, G) Y[i]·Y[k]
    sv: jax.Array     # (m, G) S[i]·v for the vector the next direction is of
    yv: jax.Array     # (m, G) Y[i]·v
    rho: jax.Array    # (m, G) 1 / sᵀy of the unrounded pair
    gamma: jax.Array  # (m, G) sᵀy / yᵀy of the unrounded pair
    valid: jax.Array  # (m, G)
    idx: jax.Array    # () rotating write slot


def empty_lane_history(m: int, d: int, G: int, history_dtype) -> LaneHistory:
    f32 = jnp.float32
    return LaneHistory(
        S=jnp.zeros((m, d, G), history_dtype),
        Y=jnp.zeros((m, d, G), history_dtype),
        sy=jnp.zeros((m * m, G), f32), yy=jnp.zeros((m * m, G), f32),
        sv=jnp.zeros((m, G), f32), yv=jnp.zeros((m, G), f32),
        rho=jnp.zeros((m, G), f32), gamma=jnp.zeros((m, G), f32),
        valid=jnp.zeros((m, G), bool), idx=jnp.zeros((), jnp.int32))


@device_scope("lbfgs.two_loop")
def two_loop_lanes(h: LaneHistory, v):
    """H·v per lane over the rotating history, for the ``v`` (d, G) the
    last `_push_lanes` was given — the same recursion as
    optim.lbfgs.two_loop per lane. Invalid (slot, lane) pairs are masked
    out, so a lane's effective history is its valid slots in recency
    order; gamma comes from each lane's newest VALID pair (holes shift it
    to the next older valid one).

    Reads: the coefficients come from the carried (m, m, G) products
    (no d-sized operand), then ONE pass combines gamma·v + cy·Y + cs·S:
    S, Y (upcast in registers when stored narrower) and v read once, the
    result written once. The vector-space recursion this replaces read a
    slot and re-read and re-wrote the (d, G) working array in each of 2m
    dependent steps (PERF.md §6, PR 28)."""
    m = h.sv.shape[0]
    gamma = jnp.ones_like(h.gamma[0])
    for i in reversed(range(m)):  # the newest valid pair wins
        gamma = jnp.where(h.valid[i], h.gamma[i], gamma)
    cy, cs = _coefficients(h.sy, h.yy, h.sv, h.yv, h.rho, gamma, h.valid)
    slots = _recency(h.idx, m)
    return _combine(gamma * v, _to_slots(slots, cy), h.Y,
                    _to_slots(slots, cs), h.S)


@device_scope("lbfgs.push")
def _push_lanes(h: LaneHistory, s, y, accept, v) -> LaneHistory:
    """Write (s, y) into the rotating slot for lanes where ``accept``
    holds AND the curvature condition passes; other lanes' slot goes
    invalid and is zeroed. The slot index rotates globally (one dynamic-
    update-slice per array instead of per-lane scatters). ``v`` (d, G) is
    what the NEXT `two_loop_lanes` will be applied to.

    Reads: ONE fused pass over S and Y for their products with the
    written pair and with ``v`` (the new pair's row and column of the
    Gram blocks, the next direction's sv / yv); writes one slot of each,
    cast to the storage dtype at the write. Acceptance, rho and gamma are
    f32 from the unrounded pair."""
    m = h.sv.shape[0]
    f32 = jnp.float32
    sy = jnp.sum(s * y, axis=0)
    yy = jnp.sum(y * y, axis=0)
    acc = accept & (sy > 1e-10 * jnp.maximum(yy, 1e-20))
    s_w = jnp.where(acc[None, :], s, 0.0).astype(h.S.dtype)
    y_w = jnp.where(acc[None, :], y, 0.0).astype(h.Y.dtype)
    s_r, y_r = s_w.astype(f32), y_w.astype(f32)  # what the slot will hold

    slots = _recency(h.idx, m)

    def dots(A, x):  # (m, d, G) · (d, G) -> (m, G), f32, slot order;
        # spelled per slot, the form the compiler fuses into one pass here
        return jnp.stack([jnp.sum(A[a] * x, axis=0) for a in range(m)])

    S_y, S_v, Y_s, Y_y, Y_v = _to_recency(slots, jnp.stack([
        dots(h.S, y_r), dots(h.S, v),
        dots(h.Y, s_r), dots(h.Y, y_r), dots(h.Y, v)], axis=1)
    ).swapaxes(0, 1)
    return LaneHistory(
        S=h.S.at[h.idx].set(s_w), Y=h.Y.at[h.idx].set(y_w),
        sy=_pushed_block(h.sy, m, Y_s, S_y, jnp.sum(s_r * y_r, axis=0)),
        yy=_pushed_block(h.yy, m, Y_y, Y_y, jnp.sum(y_r * y_r, axis=0)),
        sv=_pushed(S_v, jnp.sum(s_r * v, axis=0)),
        yv=_pushed(Y_v, jnp.sum(y_r * v, axis=0)),
        rho=_pushed(h.rho, 1.0 / jnp.maximum(sy, 1e-20)),
        gamma=_pushed(h.gamma, sy / jnp.maximum(yy, 1e-20)),
        valid=_pushed(h.valid, acc),
        idx=jnp.mod(h.idx + 1, m))


class _LaneState(NamedTuple):
    W: jax.Array       # (d, G)
    z: jax.Array       # (n, G) cached margins, shard-local
    f: jax.Array       # (G,)
    g: jax.Array       # (d, G)
    h: LaneHistory     # its sv / yv are against g
    it: jax.Array      # () global iteration counter
    evals: jax.Array   # () lock-step line-search evaluations so far
    its: jax.Array     # (G,) per-lane iterations taken
    done: jax.Array    # (G,)
    converged: jax.Array
    failed: jax.Array
    hist: jax.Array    # (max_iters + 1, G)
    ghist: jax.Array


def minimize_lbfgs_margin_lanes(
    obj,              # ops.objective.Objective (l2 field unused; see l2s)
    l2s: jax.Array,   # (G,) per-lane smooth L2 weights
    batch,
    W0: jax.Array,    # (d, G) per-lane starting points
    max_iters: int = 100,
    tolerance: float = 1e-7,
    history: int = 10,
    max_ls_evals: int = 12,
    history_dtype=None,
) -> OptResult:
    """Margin-cached L-BFGS over G lanes, lock-step, lane-minor.

    Returns an OptResult whose leaves carry the lane axis LAST: w (d, G),
    value/grad_norm/iterations/converged/failed (G,), histories
    (max_iters + 1, G). models.training transposes to the public
    lane-major convention at the jit boundary.

    ``history_dtype`` (e.g. ``jnp.bfloat16``): storage dtype for the
    (m, d, G) S/Y buffers — the dominant solver-state HBM traffic at
    large d×G. Inner products that steer the algorithm (rho, gamma,
    curvature acceptance) are computed f32 from the unrounded pair at
    push time and cached, so rounding touches only the two-loop
    direction, which the Wolfe search then vets as usual.
    """
    W0 = jnp.asarray(W0, jnp.float32)
    d, G = W0.shape
    m = history
    dtype = W0.dtype
    hdtype = jnp.dtype(history_dtype) if history_dtype is not None else dtype

    with device_scope("solve.prologue"):
        z0 = lo.margin_lanes(obj, W0, batch)
        f0, g0 = lo.value_and_grad_at_margin_lanes(obj, l2s, W0, z0, batch)
        g0norm = jnp.sqrt(jnp.sum(g0 * g0, axis=0))
        hist0 = jnp.full((max_iters + 1, G), jnp.nan, dtype).at[0].set(f0)
        ghist0 = jnp.full((max_iters + 1, G), jnp.nan,
                          dtype).at[0].set(g0norm)

    def cond(s: _LaneState):
        return jnp.any(~s.done) & (s.it < max_iters)

    def body(s: _LaneState):
        active = ~s.done
        hg = two_loop_lanes(s.h, s.g)
        with device_scope("lbfgs.direction"):
            D = -hg
            dphi0 = jnp.sum(D * s.g, axis=0)
            bad_dir = dphi0 >= 0.0
            D = jnp.where(bad_dir[None, :], -s.g, D)
            dphi0 = jnp.where(bad_dir, -jnp.sum(s.g * s.g, axis=0), dphi0)
            has_hist = jnp.any(s.h.valid, axis=0)
            dnorm = jnp.sqrt(jnp.sum(D * D, axis=0))
            a_init = jnp.where(has_hist, 1.0, 1.0 / jnp.maximum(dnorm, 1.0))
            ray = lo.ray_reg_coeffs_lanes(obj, l2s, s.W, D)

        dz = lo.direction_margin_lanes(obj, D, batch)      # X pass 1

        def phi(a):
            return lo.phi_at_ray_lanes(obj, s.z, dz, a, ray, batch)

        with device_scope("lbfgs.linesearch"):
            alpha, f_star, ok, ls_evals = wolfe_line_search_lanes(
                phi, s.f, dphi0, a_init, max_ls_evals, done0=s.done)

        with device_scope("lbfgs.update"):
            step = active & ok
            W_new = jnp.where(step[None, :], s.W + alpha[None, :] * D, s.W)
            z_new = jnp.where(step[None, :], s.z + alpha[None, :] * dz, s.z)
        # Periodic margin re-derivation (f32 drift control): a scalar-pred
        # cond — this solver is never vmapped, so the branch stays a real
        # branch and non-refresh iterations pay nothing.
        z_new = lax.cond(
            (s.it + 1) % _Z_REFRESH == 0,
            lambda: lo.margin_lanes(obj, W_new, batch),
            lambda: z_new,
        )
        g_new = lo.grad_at_margin_lanes(obj, l2s, W_new, z_new,
                                        batch)              # X pass 2
        with device_scope("lbfgs.update"):
            f_new = jnp.where(step, f_star, s.f)
            g_new = jnp.where(step[None, :], g_new, s.g)

        h = _push_lanes(s.h, W_new - s.W, g_new - s.g, step, g_new)

        with device_scope("lbfgs.update"):
            gnorm = jnp.sqrt(jnp.sum(g_new * g_new, axis=0))
            converged = _convergence(ok, s.f, f_new, gnorm, g0norm, dphi0,
                                     tolerance, dtype)
            done, converged, failed = stop_state(
                tolerance, (s.done, s.converged, s.failed),
                jnp.where(active, converged, s.converged),
                active & (converged | ~ok), active & ~ok & ~converged)
            it = s.it + 1
            its = jnp.where(active, s.its + 1, s.its)
            return _LaneState(
                W=W_new, z=z_new, f=f_new, g=g_new, h=h, it=it,
                evals=s.evals + ls_evals, its=its,
                done=done, converged=converged, failed=failed,
                hist=s.hist.at[it].set(
                    jnp.where(active, f_new, s.hist[it])),
                ghist=s.ghist.at[it].set(
                    jnp.where(active, gnorm, s.ghist[it])),
            )

    with device_scope("solve.prologue"):
        init = _LaneState(
            W=W0, z=z0, f=f0, g=g0,
            h=empty_lane_history(m, d, G, hdtype),
            it=jnp.zeros((), jnp.int32),
            evals=jnp.zeros((), jnp.int32),
            its=jnp.zeros((G,), jnp.int32),
            done=g0norm <= 1e-14, converged=g0norm <= 1e-14,
            failed=jnp.zeros((G,), bool),
            hist=hist0, ghist=ghist0,
        )
    out = lax.while_loop(cond, body, init)
    with device_scope("solve.epilogue"):
        return OptResult(
            w=out.W, value=out.f,
            grad_norm=jnp.sqrt(jnp.sum(out.g * out.g, axis=0)),
            iterations=out.its, converged=out.converged, failed=out.failed,
            loss_history=out.hist, grad_norm_history=out.ghist,
            evaluations=out.evals,
        )
