"""L-BFGS in pure JAX: bounded `lax.while_loop`, circular (s, y) history,
strong-Wolfe line search.

Reference parity: com.linkedin.photon.ml.optimization.LBFGS (which wraps
breeze.optimize.LBFGS). Differences are deliberate TPU choices:
- the whole solve is one compiled XLA program — no host round-trips between
  iterations; on a mesh, gradient psums ride the ICI inside the same program.
- fixed-shape history + masked two-loop recursion instead of a deque, so the
  solver `vmap`s over thousands of per-entity problems (GAME random effects).
- the recursion runs on COEFFICIENTS over the history's carried inner
  products (`History`), so an iteration reads the (m, d) history twice —
  one fused reduction pass in `_push`, one combination pass in `two_loop` —
  and writes one slot of it, instead of fetching a slot and re-reading and
  re-writing the working vector in each of 2m dependent steps.
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


class History(NamedTuple):
    """Circular (s, y) history and the inner products the recursion needs.

    The two-loop recursion only ever multiplies stored vectors with each
    other and with the vector it is applied to, so the history carries
    those products (the Gram blocks SᵀY and YᵀY — SᵀS is never used) and
    the direction is computed on 2m + 1 COEFFICIENTS, not on d-vectors
    (vector-free L-BFGS, Chen et al., NIPS 2014).

    S and Y are a ring in SLOT order (``idx`` the next slot to write). The
    small blocks are in RECENCY order — index 0 the newest pair, i the
    pair i pushes back — and stored FLAT, entry [i, k] at i·m + k: the
    recursion then reads them by static index (under `vmap` every lane
    has its own ``idx``, and reading a slot by a computed index would be a
    gather, priced per element), and a (lanes, m·m) array is a few MB
    where the chip tiles a (lanes, m, m) one to 33 MB (PERF.md §6, PR 28).

    Row ``m`` of S and of Y is never read: a push that fails the curvature
    test writes there, so the slot write is one unconditional update (a
    conditional one reads the old slot back — a gather a lane under
    `vmap`). From ``_TILED_FROM`` features on, a stored vector is laid out
    (d / 128, 128): a (m, d) f32 array is tiled (8, 128) on the chip, which
    pads m = 5 slots to 8 and makes one slot a 512-byte stride through the
    whole buffer (PERF.md §6, PR 28: 5.7 ms an iteration against 1.3)."""
    S: jax.Array  # (m + 1, d) or (m + 1, d / 128, 128) s-history, slot order
    Y: jax.Array  # y-history, as S
    sy: jax.Array  # (m·m,) S[i]·Y[k] at i·m + k; its diagonal is 1/rho
    yy: jax.Array  # (m·m,) Y[i]·Y[k]
    sv: jax.Array  # (m,) S[i]·v for the vector the next direction is of
    yv: jax.Array  # (m,) Y[i]·v
    idx: jax.Array  # next slot to write
    count: jax.Array  # valid pairs


_LANE_WIDTH = 128
# where the two layouts cross on the chip (PERF.md §6, PR 28; direction +
# push, ms, flat / tiled): 0.054 / 0.053 at 32,768 features, 0.072 / 0.063
# at 65,536, 0.65 / 0.24 at 2^20; under `vmap` flat wins at every width a
# bucket has (2.8 / 6.1 at 1280 over 4096 lanes)
_TILED_FROM = 1 << 16


def empty_history(m: int, d: int, dtype) -> History:
    vector = ((d,) if d < _TILED_FROM
              else (-(-d // _LANE_WIDTH), _LANE_WIDTH))
    return History(
        S=jnp.zeros((m + 1,) + vector, dtype),
        Y=jnp.zeros((m + 1,) + vector, dtype),
        sy=jnp.zeros((m * m,), dtype), yy=jnp.zeros((m * m,), dtype),
        sv=jnp.zeros((m,), dtype), yv=jnp.zeros((m,), dtype),
        idx=jnp.zeros((), jnp.int32), count=jnp.zeros((), jnp.int32))


def history_from_slots(S, Y, idx, count, v) -> History:
    """A `History` over bare slot-ordered (m, d) rings — what a streamed
    snapshot held before the history carried its products: the spare row
    added, the vectors laid out as `empty_history` lays them out, and
    every carried product recomputed from the slots against ``v``, the
    vector the next direction is of."""
    m, d = S.shape
    h = empty_history(m, d, S.dtype)
    hp = lax.Precision.HIGHEST
    order = jnp.mod(idx - 1 - jnp.arange(m), m)  # recency -> slot
    Sr, Yr = S[order], Y[order]

    def stored(A, like):
        return like.at[:m].set(jax.vmap(lambda x: _as_stored(x, like))(A))

    return History(
        S=stored(S, h.S), Y=stored(Y, h.Y),
        sy=jnp.dot(Sr, Yr.T, precision=hp).reshape(-1),
        yy=jnp.dot(Yr, Yr.T, precision=hp).reshape(-1),
        sv=jnp.dot(Sr, v, precision=hp), yv=jnp.dot(Yr, v, precision=hp),
        idx=jnp.asarray(idx, jnp.int32), count=jnp.asarray(count, jnp.int32))


def _as_stored(x, S):
    """A (d,) vector in the shape the history stores its vectors in."""
    if S.ndim == 2:
        return x
    return jnp.pad(x, (0, S[0].size - x.size)).reshape(S.shape[1:])


class _State(NamedTuple):
    w: jax.Array
    f: jax.Array
    g: jax.Array
    h: History  # its sv / yv are against g
    it: jax.Array
    evals: jax.Array  # line-search evaluations taken so far
    done: jax.Array
    converged: jax.Array
    failed: jax.Array
    hist: jax.Array
    ghist: jax.Array


def _recency(idx, m: int):
    """(m, m) one-hot: [i, a] holds where slot ``a`` holds the pair ``i``
    pushes back, for a ring whose next write goes to ``idx``."""
    age = jnp.mod(idx - 1 - jnp.arange(m), m)
    return age[None, :] == jnp.arange(m)[:, None]


def _to_recency(onehot, x):
    """Slot-ordered (m, ...) → recency-ordered, by a masked sum: m² flops
    where a gather would be priced per element."""
    lanes = (..., *(None,) * (x.ndim - 1))
    return jnp.sum(jnp.where(onehot[lanes], x[None], 0.0), axis=1)


def _to_slots(onehot, x):
    """Recency-ordered (m, ...) → slot-ordered: `_to_recency`'s inverse."""
    lanes = (..., *(None,) * (x.ndim - 1))
    return jnp.sum(jnp.where(onehot[lanes], x[:, None], 0.0), axis=0)


def _pushed_block(M, m: int, row, col, corner):
    """A flat recency-ordered block after a push: every pair moves one
    step back (the oldest falls off) and the new pair's products with the
    stored ones come in as row and column 0."""
    kept = M.reshape((m, m) + M.shape[1:])[:m - 1, :m - 1]
    top = jnp.concatenate([corner[None], row[:m - 1]])[None]
    rest = jnp.concatenate([col[:m - 1, None], kept], axis=1)
    return jnp.concatenate([top, rest]).reshape(M.shape)


def _pushed(x, newest):
    """A recency-ordered (m, ...) array after a push."""
    return jnp.concatenate([newest[None], x[:-1]])


def _coefficients(sy, yy, sv, yv, rho, gamma, valid):
    """The two-loop recursion in coefficient space: cy, cs (m, ...) with
    H·v = gamma·v + cy·Y + cs·S, from the carried recency-ordered products
    alone (``rho`` is read only where a pair is ``valid``). Shared by the
    scalar and the lane history: every array may carry trailing lane axes.

    Backward, newest → oldest: alpha[i] = rho[i]·(S[i]·q) with
    q = v − Σ_{k<i} alpha[k]·Y[k]. Forward, oldest → newest:
    beta[i] = rho[i]·(Y[i]·r) with r = gamma·q + Σ_{j>i} c[j]·S[j], and
    c[i] = alpha[i] − beta[i]. Each of the 2m steps is one row (or
    column) of a block against the coefficients found so far — the
    others are still zero — on static indices: no step reads by age."""
    m = sv.shape[0]
    sy = sy.reshape((m, m) + sy.shape[1:])
    yy = yy.reshape((m, m) + yy.shape[1:])
    alpha = jnp.zeros_like(sv)
    for i in range(m):
        s_q = sv[i] - jnp.sum(sy[i] * alpha, axis=0)
        alpha = alpha.at[i].set(jnp.where(valid[i], rho[i] * s_q, 0.0))
    y_q = gamma * (yv - jnp.sum(yy * alpha[None], axis=1))
    c = jnp.zeros_like(sv)
    for i in reversed(range(m)):
        y_r = y_q[i] + jnp.sum(sy[:, i] * c, axis=0)
        c = c.at[i].set(jnp.where(valid[i], alpha[i] - rho[i] * y_r, 0.0))
    return -gamma * alpha, c


def _products(S, Y, s, y, v):
    """S·y, S·v, Y·s, Y·y, Y·v, each (m,) in slot order: every product of
    the stored vectors the recursion can ask for once (s, y) is stored and
    ``v`` is next. One multi-output reduction: S and Y are read once.
    Spelled per slot for tiled vectors and as one reduce for flat ones —
    which of the two the compiler fuses into a single pass differs by
    shape (PERF.md §6, PR 28)."""
    m = S.shape[0] - 1
    if S.ndim == 2:
        def dots(A, x):
            return jnp.sum(A[:m] * x, axis=1)
    else:
        def dots(A, x):
            return jnp.stack([jnp.sum(A[a] * x) for a in range(m)])
    return dots(S, y), dots(S, v), dots(Y, s), dots(Y, y), dots(Y, v)


def _combine(out, cy, Y, cs, S):
    """out + cy·Y + cs·S over the m read slots: one pass, every operand
    read once."""
    for a in range(cy.shape[0]):
        out = out + cy[a] * Y[a] + cs[a] * S[a]
    return out


@device_scope("lbfgs.two_loop")
def two_loop(h: History, v):
    """H·v approximation over the circular buffer, for the ``v`` the last
    `_push` was given (``h.sv`` / ``h.yv`` are its products with the
    history; an empty history returns ``v``) — for any other vector the
    result is silently wrong, and no signature can enforce it:
    tests/test_optim.py runs every solver eagerly and holds each direction
    call to the vector of the push that made its history. Invalid pairs
    are masked, so shapes never change.

    Reads: the 2m + 1 coefficients come from the carried m·m products
    (`_coefficients`, no d-sized operand), then ONE pass combines
    gamma·v + cy·Y + cs·S — S, Y and v read once, the result written
    once. The vector-space recursion this replaces made 2m dependent slot
    fetches and 6m passes over the working vector."""
    m = h.sv.shape[0]
    valid = jnp.arange(m) < h.count
    rho = 1.0 / jnp.maximum(h.sy[::m + 1], 1e-20)  # the diagonal
    gamma = jnp.where(h.count > 0,
                      h.sy[0] / jnp.maximum(h.yy[0], 1e-20), 1.0)
    cy, cs = _coefficients(h.sy, h.yy, h.sv, h.yv, rho, gamma, valid)
    slots = _recency(h.idx, m)
    return _combine(gamma * _as_stored(v, h.S), _to_slots(slots, cy), h.Y,
                    _to_slots(slots, cs), h.S).reshape(-1)[:v.size]


@device_scope("lbfgs.push")
def _push(h: History, s, y, v) -> History:
    """Append an (s, y) pair; skip it if the curvature condition fails
    (sᵀy too small), as Breeze does — a skipped push leaves the slots and
    their products as they were. ``v`` is the vector the NEXT `two_loop`
    will be applied to (the new gradient; OWL-QN's new pseudo-gradient).

    Reads: ONE fused pass over S and Y for their products with s, y and
    v (the new pair's row and column of the Gram blocks, and the next
    direction's sv / yv, which therefore needs no reduction pass of its
    own); writes one slot of each."""
    m = h.sv.shape[0]
    s, y, v = (_as_stored(x, h.S) for x in (s, y, v))
    slots = _recency(h.idx, m)
    S_y, S_v, Y_s, Y_y, Y_v = _to_recency(
        slots, jnp.stack(_products(h.S, h.Y, s, y, v), axis=1)).T
    sy = jnp.sum(s * y)
    yy = jnp.sum(y * y)
    ok = sy > 1e-10 * jnp.maximum(yy, 1e-20)
    slot = jnp.where(ok, h.idx, m)  # a skipped pair goes to the unread row
    return History(
        S=h.S.at[slot].set(s), Y=h.Y.at[slot].set(y),
        sy=jnp.where(ok, _pushed_block(h.sy, m, Y_s, S_y, sy), h.sy),
        yy=jnp.where(ok, _pushed_block(h.yy, m, Y_y, Y_y, yy), h.yy),
        sv=jnp.where(ok, _pushed(S_v, jnp.sum(s * v)), S_v),
        yv=jnp.where(ok, _pushed(Y_v, jnp.sum(y * v)), Y_v),
        idx=jnp.where(ok, jnp.mod(h.idx + 1, m), h.idx),
        count=jnp.where(ok, jnp.minimum(h.count + 1, m), h.count))


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
        hg = two_loop(s.h, s.g)
        with device_scope("lbfgs.direction"):
            direction = -hg
            dphi0 = jnp.dot(direction, s.g)
            # Safeguard: fall back to steepest descent if not a descent
            # direction.
            bad_dir = dphi0 >= 0.0
            direction = jnp.where(bad_dir, -s.g, direction)
            dphi0 = jnp.where(bad_dir, -jnp.dot(s.g, s.g), dphi0)
            a_init = jnp.where(
                s.h.count > 0, 1.0,
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

        h = _push(s.h, w_new - s.w, g_new - s.g, g_new)

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
                w=w_new, f=f_new, g=g_new, h=h, it=it,
                evals=s.evals + ls_evals,
                done=done, converged=converged, failed=failed,
                hist=s.hist.at[it].set(f_new),
                ghist=s.ghist.at[it].set(gnorm),
            )

    solver_tap("lbfgs", 0, f0, g0norm)
    with device_scope("solve.prologue"):
        init = vary_like(_State(
            w=w0, f=f0, g=g0,
            h=empty_history(m, d, dtype),
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
    h: History  # its sv / yv are against g
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
        hg = two_loop(s.h, s.g)
        with device_scope("lbfgs.direction"):
            direction = -hg
            dphi0 = jnp.dot(direction, s.g)
            bad_dir = dphi0 >= 0.0
            direction = jnp.where(bad_dir, -s.g, direction)
            dphi0 = jnp.where(bad_dir, -jnp.dot(s.g, s.g), dphi0)
            a_init = jnp.where(
                s.h.count > 0, 1.0,
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

        h = _push(s.h, w_new - s.w, g_new - s.g, g_new)

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
                w=w_new, z=z_new, f=f_new, g=g_new, h=h, it=it,
                evals=s.evals + ls_evals,
                done=done, converged=converged, failed=failed,
                hist=s.hist.at[it].set(f_new),
                ghist=s.ghist.at[it].set(gnorm),
            )

    solver_tap("lbfgs_margin", 0, f0, g0norm)
    with device_scope("solve.prologue"):
        init = vary_like(_MarginState(
            w=w0, z=z0, f=f0, g=g0,
            h=empty_history(m, d, dtype),
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
