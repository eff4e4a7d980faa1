"""TRON: trust-region Newton with (Steihaug) conjugate-gradient subproblem,
pure JAX.

Reference parity: com.linkedin.photon.ml.optimization.TRON, itself a port of
LIBLINEAR's tron.cpp (Lin, Weng, Keerthi 2008). Each Newton step solves
H p = -g by CG using Hessian-vector products (Gauss-Newton form, exact for
GLMs) — on a mesh each HVP is one data pass + one psum over ICI.

Trust-region update follows the reference's constants:
eta0=1e-4 (acceptance), sigma1=0.25, sigma2=0.5, sigma3=4.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.optim.config import stop_state
from photon_tpu.optim.tracker import OptResult
from photon_tpu.parallel.mesh import vary_like
# Opt-in in-loop iteration telemetry; compiled out by default (see
# optim/lbfgs.py and the telemetry_off_is_free contract).
from photon_tpu.telemetry.taps import solver_tap
from photon_tpu.checkpoint.taps import snapshot_tap

ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA2, SIGMA3 = 0.25, 0.5, 4.0


class _CGState(NamedTuple):
    p: jax.Array  # solution accumulator
    r: jax.Array  # residual (-g - Hp)
    dvec: jax.Array  # search direction
    rsq: jax.Array
    it: jax.Array
    done: jax.Array
    boundary: jax.Array


def _cg_step_geometry(p, dvec, Hd, rsq, delta):
    """One Steihaug step's shared geometry: the CG step length, or the
    projection to the trust-region boundary on overshoot/negative curvature.
    Returns (step, take_boundary) — p_new = p + step·dvec either way, which
    is what lets the margin variant accumulate zp with the same step."""
    dHd = jnp.dot(dvec, Hd)
    alpha = rsq / jnp.maximum(dHd, 1e-20)
    over = jnp.linalg.norm(p + alpha * dvec) >= delta
    # project to the trust-region boundary along dvec
    pd = jnp.dot(p, dvec)
    dd = jnp.dot(dvec, dvec)
    pp = jnp.dot(p, p)
    rad = jnp.sqrt(jnp.maximum(pd * pd + dd * (delta * delta - pp), 0.0))
    theta = (rad - pd) / jnp.maximum(dd, 1e-20)
    take_boundary = over | (dHd <= 0.0)
    return jnp.where(take_boundary, theta, alpha), take_boundary


def _cg_trust(hvp, g, delta, max_cg: int, tol_factor=0.1):
    """Steihaug-CG: approximately solve H p = -g s.t. ||p|| <= delta."""
    gnorm = jnp.linalg.norm(g)
    cg_tol = tol_factor * gnorm

    def cond(s: _CGState):
        return (~s.done) & (s.it < max_cg)

    def body(s: _CGState):
        Hd = hvp(s.dvec)
        step, take_boundary = _cg_step_geometry(s.p, s.dvec, Hd, s.rsq, delta)
        p_new = s.p + step * s.dvec
        r_new = s.r - step * Hd
        rsq_new = jnp.dot(r_new, r_new)
        small = jnp.sqrt(rsq_new) <= cg_tol
        beta = rsq_new / jnp.maximum(s.rsq, 1e-20)
        d_new = r_new + beta * s.dvec
        return _CGState(
            p=p_new, r=r_new, dvec=d_new, rsq=rsq_new, it=s.it + 1,
            done=take_boundary | small, boundary=s.boundary | take_boundary,
        )

    r0 = -g
    init = vary_like(_CGState(
        p=jnp.zeros_like(g), r=r0, dvec=r0, rsq=jnp.dot(r0, r0),
        it=jnp.zeros((), jnp.int32), done=jnp.zeros((), bool),
        boundary=jnp.zeros((), bool),
    ), g)
    out = lax.while_loop(cond, body, init)
    return out.p, out.boundary


class _State(NamedTuple):
    w: jax.Array
    f: jax.Array
    g: jax.Array
    delta: jax.Array
    it: jax.Array
    done: jax.Array
    converged: jax.Array
    failed: jax.Array
    hist: jax.Array
    ghist: jax.Array


def _tr_update(f, f_try, pred, pnorm, delta):
    """Shared trust-region acceptance + radius update (both TRON drivers).

    A non-finite trial (NaN/inf loss) must count as a hard rejection:
    rho = -inf forces the shrink branch (a NaN rho would compare False to
    every threshold and silently GROW delta). Returns (accept, actual,
    pred-valid rho's delta_new)."""
    actual = f - f_try
    rho = jnp.where(
        jnp.isfinite(f_try) & (pred > 0.0),
        actual / jnp.maximum(pred, 1e-20),
        -jnp.inf,
    )
    accept = rho > ETA0
    delta_new = jnp.where(
        rho < ETA1,
        jnp.maximum(SIGMA1 * jnp.minimum(pnorm, delta), 1e-12),
        jnp.where(rho < ETA2, delta, jnp.minimum(SIGMA3 * delta, 1e10)),
    )
    return accept, actual, delta_new


def _tr_stops(accept, actual, pred, f_old, f_new, gnorm, g0norm, delta_new,
              tolerance, dtype):
    """Shared stop tests: gradient tolerance, relative-f progress on
    accepted steps, the LIBLINEAR precision-limited stop (predicted
    reduction below the f32 noise floor), and the stuck case (radius
    collapsed without acceptance). Returns (converged, stuck)."""
    grad_conv = gnorm <= tolerance * jnp.maximum(1.0, g0norm)
    f_conv = accept & (
        jnp.abs(actual)
        <= tolerance * jnp.maximum(
            jnp.maximum(jnp.abs(f_old), jnp.abs(f_new)), 1e-12)
    )
    noise = 4.0 * jnp.finfo(dtype).eps * jnp.maximum(jnp.abs(f_old), 1.0)
    precision_limited = (~accept) & (pred <= noise)
    stuck = (~accept) & (delta_new <= 1e-12)
    return grad_conv | f_conv | precision_limited, stuck


def minimize_tron(
    value_and_grad: Callable,
    hvp_at: Callable,  # (w, v) -> H(w) v
    w0: jax.Array,
    max_iters: int = 100,
    tolerance: float = 1e-7,
    cg_max_iters: int = 20,
) -> OptResult:
    w0 = jnp.asarray(w0)
    if not jnp.issubdtype(w0.dtype, jnp.floating):
        w0 = w0.astype(jnp.float32)
    dtype = w0.dtype
    f0, g0 = value_and_grad(w0)
    g0norm = jnp.linalg.norm(g0)
    hist0 = jnp.full((max_iters + 1,), jnp.nan, dtype).at[0].set(f0)
    ghist0 = jnp.full((max_iters + 1,), jnp.nan, dtype).at[0].set(g0norm)

    def cond(s: _State):
        return (~s.done) & (s.it < max_iters)

    def body(s: _State):
        p, _ = _cg_trust(lambda v: hvp_at(s.w, v), s.g, s.delta, cg_max_iters)
        Hp = hvp_at(s.w, p)
        pred = -(jnp.dot(s.g, p) + 0.5 * jnp.dot(p, Hp))
        f_try, g_try = value_and_grad(s.w + p)
        accept, actual, delta = _tr_update(s.f, f_try, pred,
                                           jnp.linalg.norm(p), s.delta)

        w_new = jnp.where(accept, s.w + p, s.w)
        f_new = jnp.where(accept, f_try, s.f)
        g_new = jnp.where(accept, g_try, s.g)

        gnorm = jnp.linalg.norm(g_new)
        converged, stuck = _tr_stops(accept, actual, pred, s.f, f_new, gnorm,
                                     g0norm, delta, tolerance, dtype)
        done, converged, failed = stop_state(
            tolerance, (s.done, s.converged, s.failed), converged,
            converged | stuck, stuck & ~converged)
        it = s.it + 1
        solver_tap("tron", it, f_new, gnorm, delta)
        snapshot_tap("tron", it, w_new, f_new, gnorm, aux=delta)
        return _State(
            w=w_new, f=f_new, g=g_new, delta=delta, it=it,
            done=done, converged=converged, failed=failed,
            hist=s.hist.at[it].set(f_new),
            ghist=s.ghist.at[it].set(gnorm),
        )

    solver_tap("tron", 0, f0, g0norm)
    init = vary_like(_State(
        w=w0, f=f0, g=g0, delta=jnp.maximum(g0norm, 1.0).astype(dtype),
        it=jnp.zeros((), jnp.int32),
        done=g0norm <= 1e-14, converged=g0norm <= 1e-14,
        failed=jnp.zeros((), bool), hist=hist0, ghist=ghist0,
    ), w0, g0)
    out = lax.while_loop(cond, body, init)
    return OptResult(
        w=out.w, value=out.f, grad_norm=jnp.linalg.norm(out.g),
        iterations=out.it, converged=out.converged, failed=out.failed,
        loss_history=out.hist, grad_norm_history=out.ghist,
    )


class _CGZState(NamedTuple):
    p: jax.Array
    zp: jax.Array  # margin of p (accumulated alongside p, same steps)
    r: jax.Array
    dvec: jax.Array
    dz: jax.Array  # margin of dvec (reused between Hd and the zp update)
    rsq: jax.Array
    it: jax.Array
    done: jax.Array


def _cg_trust_margin(obj, w, z, batch, g, delta, max_cg: int,
                     tol_factor=0.1):
    """Steihaug-CG over the margin-cached Hessian. Also accumulates zp (the
    step's margin) from the dz vectors the HVPs need anyway, and returns the
    final residual r = -g - Hp, so the caller gets BOTH the trial margin and
    Hp without any extra pass over X."""
    gnorm = jnp.linalg.norm(g)
    cg_tol = tol_factor * gnorm

    def cond(s: _CGZState):
        return (~s.done) & (s.it < max_cg)

    def body(s: _CGZState):
        Hd = obj.hvp_at_margin(w, z, batch, s.dvec, dz_v=s.dz)
        step, take_boundary = _cg_step_geometry(s.p, s.dvec, Hd, s.rsq, delta)
        p_new = s.p + step * s.dvec
        zp_new = s.zp + step * s.dz
        r_new = s.r - step * Hd
        rsq_new = jnp.dot(r_new, r_new)
        small = jnp.sqrt(rsq_new) <= cg_tol
        beta = rsq_new / jnp.maximum(s.rsq, 1e-20)
        d_new = r_new + beta * s.dvec
        done_new = take_boundary | small
        # The terminating iteration's next direction is never used: skip its
        # X pass. (Under vmap cond degrades to always-on — same tradeoff as
        # the _Z_REFRESH cond; vmapped per-entity solves are tiny.)
        dz_new = lax.cond(
            done_new,
            lambda: s.dz,
            lambda: obj.direction_margin(d_new, batch),
        )
        return _CGZState(
            p=p_new, zp=zp_new, r=r_new, dvec=d_new, dz=dz_new,
            rsq=rsq_new, it=s.it + 1, done=done_new,
        )

    r0 = -g
    init = vary_like(_CGZState(
        p=jnp.zeros_like(g), zp=jnp.zeros_like(z), r=r0, dvec=r0,
        dz=obj.direction_margin(r0, batch), rsq=jnp.dot(r0, r0),
        it=jnp.zeros((), jnp.int32), done=jnp.zeros((), bool),
    ), g)
    out = lax.while_loop(cond, body, init)
    return out.p, out.zp, out.r


class _MarginState(NamedTuple):
    w: jax.Array
    z: jax.Array
    f: jax.Array
    g: jax.Array
    delta: jax.Array
    it: jax.Array
    done: jax.Array
    converged: jax.Array
    failed: jax.Array
    hist: jax.Array
    ghist: jax.Array


# Refresh the chained margin from w every this many iterations (f32 drift
# bound on the accept-chained z), mirroring optim.lbfgs._Z_REFRESH.
_Z_REFRESH = 64


def minimize_tron_margin(
    obj,  # ops.objective.Objective
    batch,
    w0: jax.Array,
    max_iters: int = 100,
    tolerance: float = 1e-7,
    cg_max_iters: int = 20,
) -> OptResult:
    """TRON over a GLM objective with a CACHED margin.

    Savings vs the generic `minimize_tron` (same math, same LIBLINEAR
    constants and stop rules):
    - the Gauss-Newton d2 curve is evaluated on the cached z, so each CG
      HVP is two X passes instead of three;
    - CG accumulates the candidate step's margin zp from the dz vectors it
      computes anyway, so the trial f(w + p) is ELEMENTWISE (a rejected
      trust-region step costs zero passes over X);
    - Hp for the predicted reduction comes from the CG residual invariant
      (Hp = -g - r), not an extra HVP.
    """
    w0 = jnp.asarray(w0)
    if not jnp.issubdtype(w0.dtype, jnp.floating):
        w0 = w0.astype(jnp.float32)
    dtype = w0.dtype
    z0 = obj.margin(w0, batch)
    f0, g0 = obj.value_and_grad_at_margin(w0, z0, batch)
    g0norm = jnp.linalg.norm(g0)
    hist0 = jnp.full((max_iters + 1,), jnp.nan, dtype).at[0].set(f0)
    ghist0 = jnp.full((max_iters + 1,), jnp.nan, dtype).at[0].set(g0norm)

    def cond(s: _MarginState):
        return (~s.done) & (s.it < max_iters)

    def body(s: _MarginState):
        p, zp, r = _cg_trust_margin(obj, s.w, s.z, batch, s.g, s.delta,
                                    cg_max_iters)
        Hp = -s.g - r
        pred = -(jnp.dot(s.g, p) + 0.5 * jnp.dot(p, Hp))
        z_try = s.z + zp
        f_try = obj.value_at_margin(s.w + p, z_try, batch)  # elementwise
        accept, actual, delta = _tr_update(s.f, f_try, pred,
                                           jnp.linalg.norm(p), s.delta)

        w_new = jnp.where(accept, s.w + p, s.w)
        z_new = jnp.where(accept, z_try, s.z)
        z_new = lax.cond(
            (s.it + 1) % _Z_REFRESH == 0,
            lambda: obj.margin(w_new, batch),
            lambda: z_new,
        )
        f_new = jnp.where(accept, f_try, s.f)
        # cond, not where: a rejected step must not pay the X^T r pass.
        g_new = lax.cond(
            accept,
            lambda: obj.grad_at_margin(w_new, z_new, batch),
            lambda: s.g,
        )

        gnorm = jnp.linalg.norm(g_new)
        converged, stuck = _tr_stops(accept, actual, pred, s.f, f_new, gnorm,
                                     g0norm, delta, tolerance, dtype)
        done, converged, failed = stop_state(
            tolerance, (s.done, s.converged, s.failed), converged,
            converged | stuck, stuck & ~converged)
        it = s.it + 1
        solver_tap("tron_margin", it, f_new, gnorm, delta)
        snapshot_tap("tron_margin", it, w_new, f_new, gnorm, aux=delta)
        return _MarginState(
            w=w_new, z=z_new, f=f_new, g=g_new, delta=delta, it=it,
            done=done, converged=converged, failed=failed,
            hist=s.hist.at[it].set(f_new),
            ghist=s.ghist.at[it].set(gnorm),
        )

    solver_tap("tron_margin", 0, f0, g0norm)
    init = vary_like(_MarginState(
        w=w0, z=z0, f=f0, g=g0,
        delta=jnp.maximum(g0norm, 1.0).astype(dtype),
        it=jnp.zeros((), jnp.int32),
        done=g0norm <= 1e-14, converged=g0norm <= 1e-14,
        failed=jnp.zeros((), bool), hist=hist0, ghist=ghist0,
    ), w0, g0)
    out = lax.while_loop(cond, body, init)
    return OptResult(
        w=out.w, value=out.f, grad_norm=jnp.linalg.norm(out.g),
        iterations=out.it, converged=out.converged, failed=out.failed,
        loss_history=out.hist, grad_norm_history=out.ghist,
    )
