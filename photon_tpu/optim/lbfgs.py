"""L-BFGS in pure JAX: bounded `lax.while_loop`, circular (s, y) history,
strong-Wolfe line search.

Reference parity: com.linkedin.photon.ml.optimization.LBFGS (which wraps
breeze.optimize.LBFGS). Differences are deliberate TPU choices:
- the whole solve is one compiled XLA program — no host round-trips between
  iterations; on a mesh, gradient psums ride the ICI inside the same program.
- fixed-shape history + masked two-loop recursion instead of a deque, so the
  solver `vmap`s over thousands of per-entity problems (GAME random effects).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.optim.config import stop_state
from photon_tpu.optim.linesearch import wolfe_line_search
from photon_tpu.optim.tracker import OptResult
from photon_tpu.parallel.mesh import vary_like
# Opt-in per-iteration telemetry from inside the jitted loop: a pure
# no-op (absent from the jaxpr) unless a Run(resident_tap=True) is
# attached at trace time — the telemetry_off_is_free contract pins that.
from photon_tpu.telemetry.taps import solver_tap
# Named device scopes (op metadata only, always on): a profiler trace puts
# device time to these phases inside the one compiled solve.
from photon_tpu.telemetry import device_scope
# Opt-in resident last-iterate checkpoint tap: same compiled-out-by-
# default story (the checkpoint_off_is_free contract pins it).
from photon_tpu.checkpoint.taps import snapshot_tap


class _State(NamedTuple):
    w: jax.Array
    f: jax.Array
    g: jax.Array
    S: jax.Array  # (m, d) s-history
    Y: jax.Array  # (m, d) y-history
    rho: jax.Array  # (m,)
    sy: jax.Array  # () newest pair's s^T y (cached for gamma)
    yy: jax.Array  # () newest pair's y^T y
    idx: jax.Array  # next slot to write
    count: jax.Array  # valid pairs
    it: jax.Array
    evals: jax.Array  # line-search evaluations taken so far
    done: jax.Array
    converged: jax.Array
    failed: jax.Array
    hist: jax.Array
    ghist: jax.Array


@device_scope("lbfgs.two_loop")
def two_loop(g, S, Y, rho, idx, count, sy, yy):
    """H·g approximation via the two-loop recursion over a circular buffer.
    Invalid slots are masked, so shapes never change.

    ``sy``/``yy`` are the NEWEST accepted pair's sᵀy / yᵀy, cached by
    `_push` (bitwise what recomputing from the stored slots gives): at
    d = 10M the recompute was two extra (d,)-vector reads per iteration on
    top of the two full history passes the recursion itself needs."""
    m = S.shape[0]

    def bwd(i, carry):
        q, alphas = carry
        slot = jnp.mod(idx - 1 - i, m)
        valid = i < count
        alpha = jnp.where(valid, rho[slot] * jnp.dot(S[slot], q), 0.0)
        q = q - jnp.where(valid, alpha, 0.0) * Y[slot]
        return q, alphas.at[slot].set(alpha)

    q, alphas = lax.fori_loop(
        0, m, bwd, (g, vary_like(jnp.zeros((m,), g.dtype), g)))

    gamma = jnp.where(count > 0, sy / jnp.maximum(yy, 1e-20), 1.0)
    r = gamma * q

    def fwd(j, r):
        i = m - 1 - j  # oldest → newest
        slot = jnp.mod(idx - 1 - i, m)
        valid = i < count
        beta = jnp.where(valid, rho[slot] * jnp.dot(Y[slot], r), 0.0)
        return r + jnp.where(valid, alphas[slot] - beta, 0.0) * S[slot]

    return lax.fori_loop(0, m, fwd, r)


@device_scope("lbfgs.push")
def _push(S, Y, rho, idx, count, s, y, sy_c, yy_c):
    """Append an (s, y) pair; skip it if the curvature condition fails
    (sᵀy too small), as Breeze does. ``sy_c``/``yy_c`` carry the newest
    accepted pair's inner products (a skipped push keeps the previous
    pair's — the newest slot is unchanged)."""
    m = S.shape[0]
    sy = jnp.dot(s, y)
    yy = jnp.dot(y, y)
    ok = sy > 1e-10 * jnp.maximum(yy, 1e-20)
    S = jnp.where(ok, S.at[idx].set(s), S)
    Y = jnp.where(ok, Y.at[idx].set(y), Y)
    rho = jnp.where(ok, rho.at[idx].set(1.0 / jnp.maximum(sy, 1e-20)), rho)
    idx = jnp.where(ok, jnp.mod(idx + 1, m), idx)
    count = jnp.where(ok, jnp.minimum(count + 1, m), count)
    return S, Y, rho, idx, count, jnp.where(ok, sy, sy_c), \
        jnp.where(ok, yy, yy_c)


def _convergence(ok, f_old, f_new, gnorm, g0norm, dphi0, tolerance, dtype):
    """Shared stop criteria for both L-BFGS drivers (generic and margin-
    cached): gradient tolerance, relative-f progress on ACCEPTED steps, and
    the precision-limited case (line search failed with expected decrease
    below the f32 noise floor — machine convergence, not failure)."""
    grad_conv = gnorm <= tolerance * jnp.maximum(1.0, g0norm)
    f_conv = ok & (
        jnp.abs(f_old - f_new)
        <= tolerance * jnp.maximum(
            jnp.maximum(jnp.abs(f_old), jnp.abs(f_new)), 1e-12)
    )
    noise = 4.0 * jnp.finfo(dtype).eps * jnp.maximum(jnp.abs(f_old), 1.0)
    precision_limited = (~ok) & (jnp.abs(dphi0) <= noise)
    return grad_conv | f_conv | precision_limited


def minimize_lbfgs(
    value_and_grad: Callable,
    w0: jax.Array,
    max_iters: int = 100,
    tolerance: float = 1e-7,
    history: int = 10,
    max_ls_evals: int = 12,
) -> OptResult:
    w0 = jnp.asarray(w0)
    if not jnp.issubdtype(w0.dtype, jnp.floating):
        w0 = w0.astype(jnp.float32)
    dtype = w0.dtype
    d = w0.shape[0]
    m = history
    with device_scope("solve.prologue"):
        f0, g0 = value_and_grad(w0)
        g0norm = jnp.linalg.norm(g0)
        hist0 = jnp.full((max_iters + 1,), jnp.nan, dtype).at[0].set(f0)
        ghist0 = jnp.full((max_iters + 1,), jnp.nan,
                          dtype).at[0].set(g0norm)

    def cond(s: _State):
        return (~s.done) & (s.it < max_iters)

    def body(s: _State):
        hg = two_loop(s.g, s.S, s.Y, s.rho, s.idx, s.count, s.sy, s.yy)
        with device_scope("lbfgs.direction"):
            direction = -hg
            dphi0 = jnp.dot(direction, s.g)
            # Safeguard: fall back to steepest descent if not a descent
            # direction.
            bad_dir = dphi0 >= 0.0
            direction = jnp.where(bad_dir, -s.g, direction)
            dphi0 = jnp.where(bad_dir, -jnp.dot(s.g, s.g), dphi0)
            a_init = jnp.where(
                s.count > 0, 1.0,
                1.0 / jnp.maximum(jnp.linalg.norm(direction), 1.0))

        def phi(a):
            f, g = value_and_grad(s.w + a * direction)
            return f, jnp.dot(g, direction)

        with device_scope("lbfgs.linesearch"):
            alpha, _, ok, ls_evals = wolfe_line_search(
                phi, s.f, dphi0, a_init, max_ls_evals)

        with device_scope("lbfgs.update"):
            w_new = s.w + alpha * direction
            f_new, g_new = value_and_grad(w_new)
            # A failed line search keeps the iterate and terminates (the
            # reference surfaces Breeze's line-search failure the same way).
            w_new = jnp.where(ok, w_new, s.w)
            f_new = jnp.where(ok, f_new, s.f)
            g_new = jnp.where(ok, g_new, s.g)

        S, Y, rho, idx, count, sy, yy = _push(
            s.S, s.Y, s.rho, s.idx, s.count, w_new - s.w, g_new - s.g,
            s.sy, s.yy
        )

        with device_scope("lbfgs.update"):
            gnorm = jnp.linalg.norm(g_new)
            converged = _convergence(ok, s.f, f_new, gnorm, g0norm, dphi0,
                                     tolerance, dtype)
            done, converged, failed = stop_state(
                tolerance, (s.done, s.converged, s.failed), converged,
                converged | ~ok, ~ok & ~converged)
            it = s.it + 1
            solver_tap("lbfgs", it, f_new, gnorm, jnp.where(ok, alpha, 0.0))
            snapshot_tap("lbfgs", it, w_new, f_new, gnorm)
            return _State(
                w=w_new, f=f_new, g=g_new, S=S, Y=Y, rho=rho, sy=sy, yy=yy,
                idx=idx, count=count, it=it, evals=s.evals + ls_evals,
                done=done, converged=converged, failed=failed,
                hist=s.hist.at[it].set(f_new),
                ghist=s.ghist.at[it].set(gnorm),
            )

    solver_tap("lbfgs", 0, f0, g0norm)
    with device_scope("solve.prologue"):
        init = vary_like(_State(
            w=w0, f=f0, g=g0,
            S=jnp.zeros((m, d), dtype), Y=jnp.zeros((m, d), dtype),
            rho=jnp.zeros((m,), dtype),
            sy=jnp.zeros((), dtype), yy=jnp.zeros((), dtype),
            idx=jnp.zeros((), jnp.int32), count=jnp.zeros((), jnp.int32),
            it=jnp.zeros((), jnp.int32), evals=jnp.zeros((), jnp.int32),
            done=g0norm <= 1e-14,
            converged=g0norm <= 1e-14,
            failed=jnp.zeros((), bool),
            hist=hist0,
            ghist=ghist0,
        ), w0, g0)
    out = lax.while_loop(cond, body, init)
    with device_scope("solve.epilogue"):
        return OptResult(
            w=out.w, value=out.f, grad_norm=jnp.linalg.norm(out.g),
            iterations=out.it, converged=out.converged, failed=out.failed,
            loss_history=out.hist, grad_norm_history=out.ghist,
            evaluations=out.evals,
        )


# Refresh the chained margin from w every this many iterations (f32 drift
# bound); most solves finish sooner and never pay the extra pass.
_Z_REFRESH = 64


class _MarginState(NamedTuple):
    w: jax.Array
    z: jax.Array  # cached margin z = Xw (+norm/offset terms), shard-local
    f: jax.Array
    g: jax.Array
    S: jax.Array
    Y: jax.Array
    rho: jax.Array
    sy: jax.Array
    yy: jax.Array
    idx: jax.Array
    count: jax.Array
    it: jax.Array
    evals: jax.Array  # line-search evaluations taken so far
    done: jax.Array
    converged: jax.Array
    failed: jax.Array
    hist: jax.Array
    ghist: jax.Array


def minimize_lbfgs_margin(
    obj,  # ops.objective.Objective
    batch,
    w0: jax.Array,
    max_iters: int = 100,
    tolerance: float = 1e-7,
    history: int = 10,
    max_ls_evals: int = 12,
) -> OptResult:
    """L-BFGS over a GLM objective with a CACHED margin.

    The GLM margin is linear in w, so along a direction p the whole Wolfe
    line search runs on z + a·dz elementwise — every trial step costs an
    O(n) pointwise pass and two scalar psums instead of a pass over X. A
    full iteration is then exactly TWO X passes (dz = Xp, and Xᵀr at the
    accepted point), where the generic `minimize_lbfgs` pays two per line-
    search evaluation (the reference pays one Spark treeAggregate per
    Breeze evaluation). Same math, same convergence criteria, same
    tolerances as `minimize_lbfgs` — results agree to f32 reduction noise.

    jit/vmap-safe like the generic solver; used automatically for smooth
    solves by models.training.solve.
    """
    w0 = jnp.asarray(w0)
    if not jnp.issubdtype(w0.dtype, jnp.floating):
        w0 = w0.astype(jnp.float32)
    dtype = w0.dtype
    d = w0.shape[0]
    m = history
    with device_scope("solve.prologue"):
        z0 = obj.margin(w0, batch)
        f0, g0 = obj.value_and_grad_at_margin(w0, z0, batch)
        g0norm = jnp.linalg.norm(g0)
        hist0 = jnp.full((max_iters + 1,), jnp.nan, dtype).at[0].set(f0)
        ghist0 = jnp.full((max_iters + 1,), jnp.nan,
                          dtype).at[0].set(g0norm)

    def cond(s: _MarginState):
        return (~s.done) & (s.it < max_iters)

    def body(s: _MarginState):
        hg = two_loop(s.g, s.S, s.Y, s.rho, s.idx, s.count, s.sy, s.yy)
        with device_scope("lbfgs.direction"):
            direction = -hg
            dphi0 = jnp.dot(direction, s.g)
            bad_dir = dphi0 >= 0.0
            direction = jnp.where(bad_dir, -s.g, direction)
            dphi0 = jnp.where(bad_dir, -jnp.dot(s.g, s.g), dphi0)
            a_init = jnp.where(
                s.count > 0, 1.0,
                1.0 / jnp.maximum(jnp.linalg.norm(direction), 1.0))

            # One O(d) pass for the regularizer's ray coefficients; every
            # Wolfe trial below is then O(n) elementwise with zero (d,) work.
            ray = obj.ray_reg_coeffs(s.w, direction)

        dz = obj.direction_margin(direction, batch)  # X pass 1

        def phi(a):
            return obj.phi_at_ray(s.z, dz, a, ray, batch)

        with device_scope("lbfgs.linesearch"):
            alpha, f_star, ok, ls_evals = wolfe_line_search(
                phi, s.f, dphi0, a_init, max_ls_evals)

        with device_scope("lbfgs.update"):
            w_new = jnp.where(ok, s.w + alpha * direction, s.w)
            z_new = jnp.where(ok, s.z + alpha * dz, s.z)
        # The chained z accumulates f32 drift vs margin(w); refresh it from
        # w periodically (one extra X pass every _Z_REFRESH iters) so long
        # tight-tolerance solves converge on the true objective. lax.cond
        # keeps the pass free on non-refresh iterations (under vmap it
        # degrades to one always-on pass, but vmapped per-entity solves are
        # short and tiny, so the cost is noise there).
        if max_iters >= _Z_REFRESH:  # statically unreachable below that —
            # skipping the cond matters under vmap, where it degrades to an
            # always-on extra X pass per iteration for EVERY lane
            z_new = lax.cond(
                (s.it + 1) % _Z_REFRESH == 0,
                lambda: obj.margin(w_new, batch),
                lambda: z_new,
            )
        g_new = obj.grad_at_margin(w_new, z_new, batch)  # X pass 2
        with device_scope("lbfgs.update"):
            f_new = jnp.where(ok, f_star, s.f)
            g_new = jnp.where(ok, g_new, s.g)

        S, Y, rho, idx, count, sy, yy = _push(
            s.S, s.Y, s.rho, s.idx, s.count, w_new - s.w, g_new - s.g,
            s.sy, s.yy
        )

        with device_scope("lbfgs.update"):
            gnorm = jnp.linalg.norm(g_new)
            converged = _convergence(ok, s.f, f_new, gnorm, g0norm, dphi0,
                                     tolerance, dtype)
            done, converged, failed = stop_state(
                tolerance, (s.done, s.converged, s.failed), converged,
                converged | ~ok, ~ok & ~converged)
            it = s.it + 1
            solver_tap("lbfgs_margin", it, f_new, gnorm,
                       jnp.where(ok, alpha, 0.0))
            snapshot_tap("lbfgs_margin", it, w_new, f_new, gnorm)
            return _MarginState(
                w=w_new, z=z_new, f=f_new, g=g_new, S=S, Y=Y, rho=rho,
                sy=sy, yy=yy, idx=idx,
                count=count, it=it, evals=s.evals + ls_evals,
                done=done, converged=converged, failed=failed,
                hist=s.hist.at[it].set(f_new),
                ghist=s.ghist.at[it].set(gnorm),
            )

    solver_tap("lbfgs_margin", 0, f0, g0norm)
    with device_scope("solve.prologue"):
        init = vary_like(_MarginState(
            w=w0, z=z0, f=f0, g=g0,
            S=jnp.zeros((m, d), dtype), Y=jnp.zeros((m, d), dtype),
            rho=jnp.zeros((m,), dtype),
            sy=jnp.zeros((), dtype), yy=jnp.zeros((), dtype),
            idx=jnp.zeros((), jnp.int32), count=jnp.zeros((), jnp.int32),
            it=jnp.zeros((), jnp.int32), evals=jnp.zeros((), jnp.int32),
            done=g0norm <= 1e-14,
            converged=g0norm <= 1e-14,
            failed=jnp.zeros((), bool),
            hist=hist0,
            ghist=ghist0,
        ), w0, g0)
    out = lax.while_loop(cond, body, init)
    with device_scope("solve.epilogue"):
        return OptResult(
            w=out.w, value=out.f, grad_norm=jnp.linalg.norm(out.g),
            iterations=out.it, converged=out.converged, failed=out.failed,
            loss_history=out.hist, grad_norm_history=out.ghist,
            evaluations=out.evals,
        )
