"""OWL-QN (Orthant-Wise Limited-memory Quasi-Newton) for L1-regularized
objectives, pure JAX.

Reference parity: com.linkedin.photon.ml.optimization.OWLQN (which wraps
breeze.optimize.OWLQN); algorithm of Andrew & Gao 2007. The smooth part f
comes from the Objective; this solver owns the L1 term  λ Σ m_j |w_j|
(per-coordinate mask m for intercept exclusion), exactly as Breeze's OWLQN
owns it in the reference.

Pieces:
- pseudo-gradient of F = f + λ|w|₁  (subgradient choice per Andrew & Gao)
- L-BFGS direction of the pseudo-gradient (optim.lbfgs.two_loop: the
  coefficient recursion over the history's carried inner products, then
  one combination pass), projected to agree in sign with the
  steepest-descent direction; the history's products with the pseudo-
  gradient come out of the push's one reduction pass, which is handed the
  pseudo-gradient at the NEW point
- backtracking line search with orthant projection π(·; ξ)
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.optim.lbfgs import (History, _push, empty_history,
                                     two_loop)
from photon_tpu.optim.config import stop_state
from photon_tpu.optim.tracker import OptResult
from photon_tpu.parallel.mesh import vary_like
# Opt-in in-loop iteration telemetry; compiled out by default (see
# optim/lbfgs.py and the telemetry_off_is_free contract).
from photon_tpu.telemetry.taps import solver_tap
from photon_tpu.checkpoint.taps import snapshot_tap


def pseudo_gradient(w, g, l1, mask):
    """∂F selection: for w_j = 0 pick the one-sided derivative closest to 0."""
    lam = l1 * mask
    right = g + lam
    left = g - lam
    pg_zero = jnp.where(right < 0.0, right, jnp.where(left > 0.0, left, 0.0))
    return jnp.where(w != 0.0, g + lam * jnp.sign(w), pg_zero)


class _State(NamedTuple):
    w: jax.Array
    f: jax.Array  # smooth part
    F: jax.Array  # f + L1
    g: jax.Array  # smooth gradient
    h: History  # its sv / yv are against the PSEUDO-gradient at w
    it: jax.Array
    evals: jax.Array  # line-search evaluations taken so far
    done: jax.Array
    converged: jax.Array
    failed: jax.Array
    hist: jax.Array
    ghist: jax.Array


def minimize_owlqn(
    value_and_grad: Callable,  # smooth part only
    w0: jax.Array,
    l1_weight: float,
    max_iters: int = 100,
    tolerance: float = 1e-7,
    history: int = 10,
    max_ls_evals: int = 20,
    reg_mask: Optional[jax.Array] = None,
) -> OptResult:
    w0 = jnp.asarray(w0)
    if not jnp.issubdtype(w0.dtype, jnp.floating):
        w0 = w0.astype(jnp.float32)
    dtype = w0.dtype
    d = w0.shape[0]
    m = history
    mask = jnp.ones_like(w0) if reg_mask is None else jnp.asarray(reg_mask, dtype)

    def l1_term(w):
        return l1_weight * jnp.sum(mask * jnp.abs(w))

    f0, g0 = value_and_grad(w0)
    F0 = f0 + l1_term(w0)
    pg0 = pseudo_gradient(w0, g0, l1_weight, mask)
    pg0norm = jnp.linalg.norm(pg0)
    hist0 = jnp.full((max_iters + 1,), jnp.nan, dtype).at[0].set(F0)
    ghist0 = jnp.full((max_iters + 1,), jnp.nan, dtype).at[0].set(pg0norm)

    def cond(s: _State):
        return (~s.done) & (s.it < max_iters)

    def body(s: _State):
        pg = pseudo_gradient(s.w, s.g, l1_weight, mask)
        direction = -two_loop(s.h, pg)
        # Constrain direction to the quasi-Newton orthant: any component that
        # disagrees in sign with -pg is zeroed (Andrew & Gao eq. for p_k).
        direction = jnp.where(direction * pg < 0.0, direction, 0.0)
        dphi0 = jnp.dot(direction, pg)
        bad_dir = dphi0 >= 0.0
        direction = jnp.where(bad_dir, -pg, direction)
        dphi0 = jnp.where(bad_dir, -jnp.dot(pg, pg), dphi0)

        # Orthant for projection: sign(w), or sign(-pg) where w = 0.
        xi = jnp.where(s.w != 0.0, jnp.sign(s.w), jnp.sign(-pg))

        def project(w):
            return jnp.where(w * xi > 0.0, w, 0.0)

        a0 = jnp.where(s.h.count > 0, 1.0,
                       1.0 / jnp.maximum(jnp.linalg.norm(direction), 1.0))

        class LS(NamedTuple):
            a: jax.Array
            F: jax.Array
            ok: jax.Array
            i: jax.Array

        c1 = 1e-4

        def ls_cond(t: LS):
            return (~t.ok) & (t.i < max_ls_evals)

        def ls_body(t: LS):
            w_try = project(s.w + t.a * direction)
            f_try, _ = value_and_grad(w_try)
            F_try = f_try + l1_term(w_try)
            # Armijo on F with the projected step (Andrew & Gao eq. 5).
            dec = jnp.dot(pg, w_try - s.w)
            ok = (F_try <= s.F + c1 * dec) & (dec < 0.0) & jnp.isfinite(F_try)
            return LS(a=jnp.where(ok, t.a, 0.5 * t.a), F=F_try, ok=ok, i=t.i + 1)

        ls = lax.while_loop(
            ls_cond, ls_body,
            vary_like(LS(a=jnp.asarray(a0, dtype), F=s.F,
                         ok=jnp.zeros((), bool),
                         i=jnp.zeros((), jnp.int32)), s.w, s.g),
        )
        w_new = project(s.w + ls.a * direction)
        f_new, g_new = value_and_grad(w_new)
        F_new = f_new + l1_term(w_new)
        ok = ls.ok
        w_new = jnp.where(ok, w_new, s.w)
        f_new = jnp.where(ok, f_new, s.f)
        F_new = jnp.where(ok, F_new, s.F)
        g_new = jnp.where(ok, g_new, s.g)

        # History uses smooth gradients (Andrew & Gao): y = Δg, s = Δw —
        # but the next direction is of the PSEUDO-gradient at w_new, so
        # that is the vector the push takes the history's products with
        # (the same reduction pass; the direction needs none of its own).
        pg_new = pseudo_gradient(w_new, g_new, l1_weight, mask)
        h = _push(s.h, w_new - s.w, g_new - s.g, pg_new)
        pgnorm = jnp.linalg.norm(pg_new)
        grad_conv = pgnorm <= tolerance * jnp.maximum(1.0, pg0norm)
        # Gate f_conv on an accepted step: a rejected step leaves F unchanged
        # and would trivially pass the relative-F test.
        f_conv = ok & (
            jnp.abs(s.F - F_new)
            <= tolerance * jnp.maximum(jnp.maximum(jnp.abs(s.F), jnp.abs(F_new)), 1e-12)
        )
        # Precision-limited stop: failed projected line search with expected
        # decrease below the float noise floor of F — machine-precision
        # convergence, not a failure.
        noise = 4.0 * jnp.finfo(dtype).eps * jnp.maximum(jnp.abs(s.F), 1.0)
        precision_limited = (~ok) & (jnp.abs(dphi0) <= noise)
        converged = grad_conv | f_conv | precision_limited
        done, converged, failed = stop_state(
            tolerance, (s.done, s.converged, s.failed), converged,
            converged | ~ok, ~ok & ~converged)
        it = s.it + 1
        solver_tap("owlqn", it, F_new, pgnorm, jnp.where(ok, ls.a, 0.0))
        snapshot_tap("owlqn", it, w_new, F_new, pgnorm)
        return _State(
            w=w_new, f=f_new, F=F_new, g=g_new, h=h, it=it,
            evals=s.evals + ls.i,
            done=done, converged=converged, failed=failed,
            hist=s.hist.at[it].set(F_new),
            ghist=s.ghist.at[it].set(pgnorm),
        )

    solver_tap("owlqn", 0, F0, pg0norm)
    init = vary_like(_State(
        w=w0, f=f0, F=F0, g=g0,
        h=empty_history(m, d, dtype),
        it=jnp.zeros((), jnp.int32), evals=jnp.zeros((), jnp.int32),
        done=pg0norm <= 1e-14, converged=pg0norm <= 1e-14,
        failed=jnp.zeros((), bool), hist=hist0, ghist=ghist0,
    ), w0, g0)
    out = lax.while_loop(cond, body, init)
    pg_fin = pseudo_gradient(out.w, out.g, l1_weight, mask)
    return OptResult(
        w=out.w, value=out.F, grad_norm=jnp.linalg.norm(pg_fin),
        iterations=out.it, converged=out.converged, failed=out.failed,
        loss_history=out.hist, grad_norm_history=out.ghist,
        evaluations=out.evals,
    )
