"""Strong-Wolfe line search (Nocedal & Wright Alg. 3.5/3.6) as a single
bounded `lax.while_loop`.

Reference parity: the reference's LBFGS delegates to Breeze's
StrongWolfeLineSearch; this is the same bracket+zoom scheme expressed as a
state machine so it jits and vmaps. One objective evaluation per loop
iteration, hard-capped at `max_evals` (each evaluation is a full pass over
the sharded data, so the cap bounds communication too).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp
from jax import lax

from photon_tpu.parallel.mesh import vary_like

C1 = 1e-4
C2 = 0.9


class LSState(NamedTuple):
    phase: jnp.ndarray  # 0 = bracketing, 1 = zoom
    done: jnp.ndarray
    failed: jnp.ndarray
    i: jnp.ndarray
    a: jnp.ndarray  # next step length to evaluate
    a_prev: jnp.ndarray
    f_prev: jnp.ndarray
    d_prev: jnp.ndarray
    a_lo: jnp.ndarray
    f_lo: jnp.ndarray
    d_lo: jnp.ndarray
    a_hi: jnp.ndarray
    f_hi: jnp.ndarray
    d_hi: jnp.ndarray
    a_star: jnp.ndarray
    f_star: jnp.ndarray


def _cubic_min(a_lo, f_lo, d_lo, a_hi, f_hi, d_hi):
    """Minimizer of the cubic Hermite interpolant (Nocedal & Wright eq. 3.59),
    safeguarded: falls back to bisection when the cubic is degenerate or its
    minimizer falls outside the bracket's interior (10% margin each end).
    Each rejected trial costs a full data pass + all-reduce, so good trial
    points are directly a distributed-perf win."""
    span = a_hi - a_lo
    d1 = d_lo + d_hi - 3.0 * (f_lo - f_hi) / jnp.where(span == 0.0, 1.0, -span)
    disc = d1 * d1 - d_lo * d_hi
    d2 = jnp.sign(span) * jnp.sqrt(jnp.maximum(disc, 0.0))
    denom = d_hi - d_lo + 2.0 * d2
    a_c = a_hi - span * (d_hi + d2 - d1) / jnp.where(denom == 0.0, 1.0, denom)
    lo_m = a_lo + 0.1 * span
    hi_m = a_hi - 0.1 * span
    inside = jnp.where(span > 0.0, (a_c >= lo_m) & (a_c <= hi_m),
                       (a_c <= lo_m) & (a_c >= hi_m))
    ok = (disc >= 0.0) & (denom != 0.0) & jnp.isfinite(a_c) & inside
    return jnp.where(ok, a_c, 0.5 * (a_lo + a_hi))


def wolfe_line_search(
    phi: Callable,  # alpha -> (f, dphi)  [f and slope along the ray]
    f0,
    dphi0,
    a_init=1.0,
    max_evals: int = 12,
):
    """Returns (alpha, f_alpha, ok, evals). alpha = 0 and ok = False on
    failure; evals is the number of `phi` evaluations taken."""
    f0 = jnp.asarray(f0)
    dtype = f0.dtype
    dphi0 = jnp.asarray(dphi0, dtype)
    zero = jnp.zeros((), dtype)

    def armijo(a, f):
        return f <= f0 + C1 * a * dphi0

    def body(s: LSState) -> LSState:
        f, d = phi(s.a)
        bad = jnp.isnan(f) | jnp.isinf(f)

        # --- bracketing phase transitions (Alg 3.5)
        to_zoom_hi = bad | (~armijo(s.a, f)) | ((s.i > 0) & (f >= s.f_prev))
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

        # --- zoom phase update (Alg 3.6); s.a is the trial point in [lo, hi]
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
        done = jnp.where(in_zoom, z_wolfe_ok, wolfe_ok)
        a_lo = jnp.where(in_zoom, z_a_lo, br_a_lo)
        f_lo = jnp.where(in_zoom, z_f_lo, br_f_lo)
        d_lo = jnp.where(in_zoom, z_d_lo, br_d_lo)
        a_hi = jnp.where(in_zoom, z_a_hi, br_a_hi)
        f_hi = jnp.where(in_zoom, z_f_hi, br_f_hi)
        d_hi = jnp.where(in_zoom, z_d_hi, br_d_hi)
        # Trial point: cubic Hermite minimizer over the bracket (bisection
        # fallback inside _cubic_min); bracketing keeps doubling.
        interp_a = _cubic_min(a_lo, f_lo, d_lo, a_hi, f_hi, d_hi)
        # A bad (non-finite) hi endpoint has meaningless (f, d): bisect.
        interp_a = jnp.where(jnp.isfinite(f_hi) & jnp.isfinite(d_hi),
                             interp_a, 0.5 * (a_lo + a_hi))
        next_a = jnp.where(in_zoom | ~expand, interp_a, 2.0 * s.a)
        phase = jnp.where(in_zoom, 1, br_phase)

        # best Armijo-satisfying point seen so far (fallback on cap).
        better = armijo(s.a, f) & (f < s.f_star) & ~bad
        a_star = jnp.where(done, s.a, jnp.where(better, s.a, s.a_star))
        f_star = jnp.where(done, f, jnp.where(better, f, s.f_star))

        return LSState(
            phase=phase, done=done, failed=s.failed, i=s.i + 1,
            a=next_a, a_prev=s.a, f_prev=f, d_prev=d,
            a_lo=a_lo, f_lo=f_lo, d_lo=d_lo, a_hi=a_hi, f_hi=f_hi, d_hi=d_hi,
            a_star=a_star, f_star=f_star,
        )

    def cond(s: LSState):
        return (~s.done) & (s.i < max_evals)

    init = vary_like(LSState(
        phase=jnp.zeros((), jnp.int32), done=jnp.zeros((), bool),
        failed=jnp.zeros((), bool), i=jnp.zeros((), jnp.int32),
        a=jnp.asarray(a_init, dtype),
        a_prev=zero, f_prev=f0, d_prev=dphi0,
        a_lo=zero, f_lo=f0, d_lo=dphi0,
        a_hi=jnp.asarray(jnp.inf, dtype), f_hi=jnp.asarray(jnp.inf, dtype),
        d_hi=jnp.asarray(jnp.inf, dtype),
        a_star=zero, f_star=f0,
    ), f0, dphi0)
    out = lax.while_loop(cond, body, init)
    ok = out.done | (out.a_star > 0.0)
    return out.a_star, out.f_star, ok, out.i
