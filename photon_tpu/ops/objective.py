"""GLM objective: value / gradient / Hessian products over a (possibly
device-sharded) batch.

Reference parity: com.linkedin.photon.ml.function.glm.{DistributedGLMLossFunction,
SingleNodeGLMLossFunction} and function.L2RegularizationTwiceDiffFunction.
Where the reference aggregates per-partition (value, gradient) pairs with
`RDD.treeAggregate(depth=2)`, here each device computes its local partial sum
and a single `lax.psum` over the mesh's data axis combines them across the
ICI — one fused all-reduce instead of a JVM aggregation tree.

All quantities use the reference's *sum* convention (weighted sum over
examples, not mean), so regularization weights mean the same thing.

Everything is shape-static and jit/vmap-safe: the same `Objective` drives the
distributed fixed-effect solve (under shard_map) and the vmapped per-entity
random-effect solves.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.data.dataset import GLMBatch
from photon_tpu.data.matrix import (layout_matvec, rmatvec, sq_rmatvec,
                                    weighted_gram)
from photon_tpu.ops.fused import can_fuse, fused_value_and_grad
from photon_tpu.ops.losses import TaskType, loss_fns
from photon_tpu.telemetry import device_scope


@dataclasses.dataclass(frozen=True)
class Objective:
    """Smooth part of the regularized negative log-likelihood.

    l2 is the smooth L2 weight; the non-smooth L1 term is owned by OWL-QN
    (as in the reference, where Breeze's OWLQN adds the L1 term itself).

    reg_mask: optional (d,) 0/1 per-coordinate regularization mask (used to
    exclude the intercept column when configured; reference regularizes the
    intercept, so the default is all-ones = None).

    prior_mean / prior_precision: informative-prior (incremental training)
    parameters; the L2 term becomes 0.5 Σ_j (l2 + τ_j)(w_j - μ_j)² with μ=0,
    τ=0 when absent. Reference: function.PriorDistribution.

    norm_factors / norm_shifts: feature normalization folded into the margin
    (reference: NormalizationContext factors/shiftsAndIntercept applied inside
    every loss evaluation so sparse X stays sparse). The margin becomes
    z = X(f∘w) − (s·(f∘w)) + offset, i.e. the solve runs in normalized
    coefficient space — which is also the space the L2 penalty sees, matching
    the reference's regularization-under-normalization semantics. Convert
    trained coefficients back with NormalizationContext.to_original_space.
    """

    task: TaskType
    l2: float = 0.0
    # Mesh axis (or tuple of axes — hybrid ICI×DCN meshes psum over both,
    # lowered hierarchically by XLA) for the gradient all-reduce.
    axis_name: Optional[str | tuple] = None
    # Use the pallas fused single-pass kernel (ops/fused.py) for
    # value_and_grad when the batch qualifies (dense X, no normalization).
    # Set by train_glm; leave False for vmapped per-entity solves.
    fused: bool = False
    reg_mask: Optional[jax.Array] = None
    prior_mean: Optional[jax.Array] = None
    prior_precision: Optional[jax.Array] = None
    # Dense (d, d) prior precision (reference: PriorDistribution with a full
    # covariance, from a previous solve's FULL Hessian). Adds
    # 0.5·dwᵀ P dw on top of the diagonal terms; small-d only.
    prior_full_precision: Optional[jax.Array] = None
    norm_factors: Optional[jax.Array] = None
    norm_shifts: Optional[jax.Array] = None

    # ---------------------------------------------------------------- helpers
    def _psum(self, x):
        if self.axis_name is None:
            return x
        with device_scope("mesh.psum"):
            return lax.psum(x, self.axis_name)

    def _psum_many(self, *xs):
        """One all-reduce for several partial sums (skipping Nones).

        The reference aggregates (value, gradient) in a single
        treeAggregate; a variadic psum keeps that one-collective-per-
        evaluation shape here too (tests/test_multihost.py pins the
        compiled all-reduce count)."""
        if self.axis_name is None:
            return xs
        with device_scope("mesh.psum"):
            present = lax.psum(tuple(x for x in xs if x is not None),
                               self.axis_name)
        it = iter(present)
        return tuple(None if x is None else next(it) for x in xs)

    def _eff_w(self, w):
        """Normalized-space coefficients as seen by the data: f∘w."""
        return w if self.norm_factors is None else w * self.norm_factors

    def _margin_of_eff(self, wt, batch: GLMBatch):
        z = layout_matvec(batch.X, wt) + batch.offsets
        if self.norm_shifts is not None:
            z = z - jnp.dot(self.norm_shifts, wt)
        return z

    def _margin(self, w, batch: GLMBatch):
        return self._margin_of_eff(self._eff_w(w), batch)

    def _backprop(self, batch: GLMBatch, g):
        """∂z/∂w pulled back over a per-row cotangent g: f∘(Xᵀg − s·Σg).
        Returns the LOCAL (pre-psum) pieces (Xᵀg, Σg); Σg is only computed
        (and later psum'd) when a shift term exists."""
        gX = rmatvec(batch.X, g)
        gsum = jnp.sum(g) if self.norm_shifts is not None else None
        return gX, gsum

    def _finish_backprop(self, gX, gsum=None):
        out = gX
        if self.norm_shifts is not None:
            out = out - self.norm_shifts * gsum
        if self.norm_factors is not None:
            out = out * self.norm_factors
        return out

    def _reg_terms(self, w):
        """(value, grad) of the smooth regularizer at w."""
        mask = self.reg_mask if self.reg_mask is not None else 1.0
        mu = self.prior_mean if self.prior_mean is not None else 0.0
        tau = self.prior_precision if self.prior_precision is not None else 0.0
        dw = w - mu
        coeff = (self.l2 + tau) * mask
        value = 0.5 * jnp.sum(coeff * dw * dw)
        grad = coeff * dw
        if self.prior_full_precision is not None:
            Pdw = self.prior_full_precision @ dw
            value = value + 0.5 * jnp.dot(dw, Pdw)
            grad = grad + Pdw
        return value, grad

    def _reg_hess_diag(self, w):
        mask = self.reg_mask if self.reg_mask is not None else 1.0
        tau = self.prior_precision if self.prior_precision is not None else 0.0
        diag = (self.l2 + tau) * mask * jnp.ones_like(w)
        if self.prior_full_precision is not None:
            diag = diag + jnp.diagonal(self.prior_full_precision)
        return diag

    def _reg_hvp(self, w, v):
        """Regularizer Hessian-vector product (full prior needs P@v, not
        diag(P)∘v)."""
        mask = self.reg_mask if self.reg_mask is not None else 1.0
        tau = self.prior_precision if self.prior_precision is not None else 0.0
        out = (self.l2 + tau) * mask * v
        if self.prior_full_precision is not None:
            out = out + self.prior_full_precision @ v
        return out

    # ------------------------------------------------------------------- API
    def value(self, w, batch: GLMBatch):
        return self.value_and_grad(w, batch)[0]

    def grad(self, w, batch: GLMBatch):
        return self.value_and_grad(w, batch)[1]

    def value_and_grad(self, w, batch: GLMBatch):
        if (self.fused and self.norm_factors is None
                and self.norm_shifts is None and can_fuse(batch.X)):
            local_value, gX = fused_value_and_grad(
                self.task, batch.X, w, batch.y, batch.weights, batch.offsets)
            value, grad = self._psum_many(local_value, gX)
            rv, rg = self._reg_terms(w)
            return value + rv, grad + rg
        return self.value_and_grad_at_margin(w, self._margin(w, batch), batch)

    # ------------------------------------------------ margin-space API
    # The margin is LINEAR in w: z(w + a·p) = z(w) + a·dz with dz the
    # direction's margin. The margin-cached L-BFGS (optim/lbfgs.py,
    # minimize_lbfgs_margin) exploits this: line-search evaluations become
    # elementwise work on cached (z, dz) — no pass over X — so a full
    # iteration costs exactly two X passes (dz and the accepted gradient)
    # regardless of how many step lengths the Wolfe search tries. The
    # reference pays a full treeAggregate per Breeze line-search evaluation.

    def margin(self, w, batch: GLMBatch):
        """z(w): the per-row margin, LOCAL to this shard."""
        return self._margin(w, batch)

    def direction_margin(self, p, batch: GLMBatch):
        """dz = ∂z/∂w · p (offset-free margin of the direction), LOCAL."""
        return self._margin_of_eff(
            self._eff_w(p),
            batch._replace(offsets=jnp.zeros_like(batch.offsets)))

    def phi_at(self, z, dz, a, w, p, batch: GLMBatch):
        """(φ(a), φ'(a)) along w + a·p from cached margins — one elementwise
        pass plus two scalar psums; zero passes over X."""
        return self.phi_at_ray(z, dz, a, self.ray_reg_coeffs(w, p), batch)

    def ray_reg_coeffs(self, w, p):
        """Scalars (c0, c1, c2) of the regularizer along the ray w + a·p:
        every smooth reg term (L2, diagonal prior, full prior) is QUADRATIC
        in w, so reg value(a) = c0 + a·c1 + a²/2·c2 exactly, and its
        directional derivative is c1 + a·c2. One O(d) pass per line search
        instead of several (d,)-vector passes per TRIAL — at the 10M-feature
        regime those trial passes dominated the whole solve."""
        mask = self.reg_mask if self.reg_mask is not None else 1.0
        mu = self.prior_mean if self.prior_mean is not None else 0.0
        tau = self.prior_precision if self.prior_precision is not None else 0.0
        dw = w - mu
        coeff = (self.l2 + tau) * mask
        c0 = 0.5 * jnp.sum(coeff * dw * dw)
        c1 = jnp.sum(coeff * dw * p)
        c2 = jnp.sum(coeff * p * p)
        if self.prior_full_precision is not None:
            Pdw = self.prior_full_precision @ dw
            Pp = self.prior_full_precision @ p
            c0 = c0 + 0.5 * jnp.dot(dw, Pdw)
            c1 = c1 + jnp.dot(dw, Pp)
            c2 = c2 + jnp.dot(p, Pp)
        return c0, c1, c2

    def phi_at_ray(self, z, dz, a, coeffs, batch: GLMBatch):
        """phi_at with the regularizer's ray coefficients precomputed —
        a line-search trial is O(n) elementwise + scalars, with NO (d,)
        work at all."""
        loss, d1, _ = loss_fns(self.task)
        with device_scope("objective.loss"):
            za = z + a * dz
            wl = batch.weights * loss(za, batch.y)
            wd = batch.weights * d1(za, batch.y) * dz
            f, dphi = self._psum_many(jnp.sum(wl), jnp.sum(wd))
        c0, c1, c2 = coeffs
        return f + c0 + a * (c1 + 0.5 * a * c2), dphi + c1 + a * c2

    def value_at_margin(self, w, z, batch: GLMBatch):
        """f(w) from a cached margin — elementwise only, no pass over X."""
        loss, _, _ = loss_fns(self.task)
        with device_scope("objective.loss"):
            value = self._psum(jnp.sum(batch.weights * loss(z, batch.y)))
        rv, _ = self._reg_terms(w)
        return value + rv

    def hvp_at_margin(self, w, z, batch: GLMBatch, v, dz_v=None):
        """H(w)·v with the margin z cached (Gauss-Newton form): the d2 curve
        is evaluated on z instead of recomputing X·w, so an HVP costs two X
        passes (dz_v and the backprop) instead of three. Pass dz_v when the
        caller already has the direction's margin (TRON's CG does)."""
        _, _, d2 = loss_fns(self.task)
        if dz_v is None:
            dz_v = self.direction_margin(v, batch)
        g = batch.weights * d2(z, batch.y) * dz_v
        gX, gsum = self._backprop(batch, g)
        hv = self._finish_backprop(*self._psum_many(gX, gsum))
        return hv + self._reg_hvp(w, v)

    def grad_at_margin(self, w, z, batch: GLMBatch):
        """Full gradient from a cached margin — ONE pass over X (Xᵀr)."""
        _, d1, _ = loss_fns(self.task)
        with device_scope("objective.loss"):
            r = batch.weights * d1(z, batch.y)
        gX, gsum = self._backprop(batch, r)
        grad = self._finish_backprop(*self._psum_many(gX, gsum))
        _, rg = self._reg_terms(w)
        return grad + rg

    def value_and_grad_at_margin(self, w, z, batch: GLMBatch):
        """(f, g) from a cached margin — one elementwise pass + one Xᵀr."""
        loss, d1, _ = loss_fns(self.task)
        with device_scope("objective.loss"):
            r = batch.weights * d1(z, batch.y)
            local_value = jnp.sum(batch.weights * loss(z, batch.y))
        gX, gsum = self._backprop(batch, r)
        value, gX, gsum = self._psum_many(local_value, gX, gsum)
        grad = self._finish_backprop(gX, gsum)
        rv, rg = self._reg_terms(w)
        return value + rv, grad + rg

    # ------------------------------------------------ chunk-partial API
    # The literal treeAggregate contract (optim/streamed.py): a dataset too
    # big for HBM streams through the solve as device-resident CHUNKS, and
    # each evaluation accumulates per-chunk partial sums on device — the
    # per-chunk leaf of the reference's RDD.treeAggregate, with the Python
    # chunk loop standing in for Spark's aggregation tree. Partials carry
    # NO regularization terms (reg is a function of w alone and must be
    # added exactly once, by `finish_value_grad`); they are LOCAL sums and
    # NEVER psum here — under a mesh the streamed machinery runs these
    # methods inside shard_map, keeps each device's running partial local
    # across chunks, and issues exactly ONE hierarchical psum per
    # evaluation when it closes with finish_value_grad
    # (optim.streamed._MeshChunkOps). An axis_name psum inside a chunk
    # partial would multiply that single collective by n_chunks.

    def chunk_value_grad_partials(self, w, batch: GLMBatch):
        """(margin, partials) of ONE chunk: the streamed analog of
        value_and_grad. The margin is returned for the caller's per-chunk
        cache (the streamed L-BFGS line search rides it); `partials` sum
        across chunks with `add_partials` and close with
        `finish_value_grad`."""
        z = self._margin(w, batch)
        return z, self.chunk_partials_at_margin(z, batch)

    def chunk_partials_at_margin(self, z, batch: GLMBatch):
        """(loss_sum, Xᵀr, Σr-or-None) partials from a cached chunk margin
        — one elementwise pass + one Xᵀr pass, no margin recompute."""
        loss, d1, _ = loss_fns(self.task)
        with device_scope("objective.loss"):
            r = batch.weights * d1(z, batch.y)
            local_value = jnp.sum(batch.weights * loss(z, batch.y))
        gX, gsum = self._backprop(batch, r)
        return local_value, gX, gsum

    @staticmethod
    def add_partials(a, b):
        """Accumulate two chunk-partial pytrees (the treeAggregate `seqOp`/
        `combOp` — addition either way)."""
        return jax.tree_util.tree_map(jnp.add, a, b)

    def finish_value_grad(self, w, partials):
        """(f, g) from summed chunk partials + the regularizer at w."""
        val, gX, gsum = partials
        grad = self._finish_backprop(gX, gsum)
        rv, rg = self._reg_terms(w)
        return val + rv, grad + rg

    def chunk_phi_partials(self, z, dz, a, y, weights):
        """(φ_loss, φ'_loss) partials of one chunk at step `a` along its
        cached (z, dz) margins — elementwise only, no X, no (d,) work. The
        regularizer's exact quadratic ray (ray_reg_coeffs) is added once
        by the caller, so a streamed line-search trial uploads 16 bytes/row
        instead of re-streaming the chunk's features."""
        loss, d1, _ = loss_fns(self.task)
        with device_scope("objective.loss"):
            za = z + a * dz
            return (jnp.sum(weights * loss(za, y)),
                    jnp.sum(weights * d1(za, y) * dz))

    def chunk_value_partials_many(self, W, batch: GLMBatch):
        """(K,) smooth-objective value partials of K candidate coefficient
        vectors (rows of W) over ONE chunk — the streamed OWL-QN ladder
        leaf: the orthant projection breaks margin linearity, so trial
        points need real margins, and evaluating the whole backtracking
        ladder per chunk visit shares the chunk upload across all K trials
        (the reference pays one full treeAggregate per Breeze trial).
        Loss partials only — the caller adds the per-candidate smooth reg
        value once, not per chunk."""
        loss, _, _ = loss_fns(self.task)

        def one(wk):
            z = self._margin(wk, batch)
            with device_scope("objective.loss"):
                return jnp.sum(batch.weights * loss(z, batch.y))

        return jax.vmap(one)(W)

    def hvp(self, w, batch: GLMBatch, v):
        """Hessian-vector product: Jᵀ diag(weight · d2) J v + reg·v, where
        J = ∂z/∂w (= X when unnormalized).

        Reference: TwiceDiffFunction.hessianVector — computed the same way
        (Gauss-Newton form is exact for GLMs) per partition + treeAggregate.
        """
        _, _, d2 = loss_fns(self.task)
        z = self._margin(w, batch)
        dz = self.direction_margin(v, batch)
        g = batch.weights * d2(z, batch.y) * dz
        gX, gsum = self._backprop(batch, g)
        hv = self._finish_backprop(*self._psum_many(gX, gsum))
        return hv + self._reg_hvp(w, v)

    def hess_diag(self, w, batch: GLMBatch):
        """diag(H). Reference: TwiceDiffFunction.hessianDiagonal (used for
        VarianceComputationType.SIMPLE coefficient variances).

        With normalization, H_jj = f_j² Σ_i w2_i (x_ij − s_j)², expanded into
        segment-sum pieces so sparse X never densifies.
        """
        _, _, d2 = loss_fns(self.task)
        z = self._margin(w, batch)
        w2 = batch.weights * d2(z, batch.y)
        if self.norm_shifts is not None:
            diag, xw2, w2sum = self._psum_many(
                sq_rmatvec(batch.X, w2), rmatvec(batch.X, w2), jnp.sum(w2))
            s = self.norm_shifts
            diag = diag - 2.0 * s * xw2 + s * s * w2sum
        else:
            diag = self._psum(sq_rmatvec(batch.X, w2))
        if self.norm_factors is not None:
            diag = diag * self.norm_factors * self.norm_factors
        return diag + self._reg_hess_diag(w)

    def full_hessian(self, w, batch: GLMBatch):
        """Dense (d, d) Hessian. Reference: TwiceDiffFunction.hessianMatrix
        (VarianceComputationType.FULL); only for small feature spaces.

        With normalization: F(G − s qᵀ − q sᵀ + (Σw2) s sᵀ)F with
        G = Xᵀdiag(w2)X, q = Xᵀw2, F = diag(factors).
        """
        _, _, d2 = loss_fns(self.task)
        z = self._margin(w, batch)
        w2 = batch.weights * d2(z, batch.y)
        if self.norm_shifts is not None:
            H, q, w2sum = self._psum_many(
                weighted_gram(batch.X, w2), rmatvec(batch.X, w2), jnp.sum(w2))
            s = self.norm_shifts
            H = H - jnp.outer(s, q) - jnp.outer(q, s) + w2sum * jnp.outer(s, s)
        else:
            H = self._psum(weighted_gram(batch.X, w2))
        if self.norm_factors is not None:
            H = H * jnp.outer(self.norm_factors, self.norm_factors)
        mask = self.reg_mask if self.reg_mask is not None else 1.0
        tau = self.prior_precision if self.prior_precision is not None else 0.0
        H = H + jnp.diag((self.l2 + tau) * mask * jnp.ones_like(w))
        if self.prior_full_precision is not None:
            H = H + self.prior_full_precision
        return H


# Pytree registration: array-valued fields are leaves; task/l2/axis_name/
# fused are static metadata. This lets an Objective cross jit boundaries as
# an ARGUMENT, so module-level jitted runners (models/training._train_run)
# cache by treedef+shape instead of retracing per closure — the difference
# between one trace per program shape and one trace per train_glm() call.
# l2 is a DATA field (traced leaf): a regularization-weight grid or the GP
# tuner then reuses one compiled solver across every weight instead of
# recompiling per grid point.
jax.tree_util.register_dataclass(
    Objective,
    data_fields=["l2", "reg_mask", "prior_mean", "prior_precision",
                 "prior_full_precision", "norm_factors", "norm_shifts"],
    meta_fields=["task", "axis_name", "fused"],
)


# ----------------------------------------------------------------- contracts
# Static-analysis contracts for this module's hot programs (registered next
# to the code they pin; traced and enforced by `python -m
# photon_tpu.analysis` and tests/test_analysis_contracts.py). Builders run
# only when the checker traces them — module import just records the spec.
from photon_tpu.analysis.contracts import register_contract  # noqa: E402
from photon_tpu.analysis.walker import SCATTER_PRIMITIVES  # noqa: E402


def _contract_batch(n=64, d=8, feature_dtype=None):
    import numpy as np

    from photon_tpu.data.dataset import cast_features, make_batch

    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    batch = make_batch(X, y)
    if feature_dtype is not None:
        batch = cast_features(batch, feature_dtype)
    return batch


def _contract_objective():
    import numpy as np

    # l2 as np.float32, matching models.training.make_objective's canon:
    # a Python-float leaf is weak-typed and the retrace-hazard rule
    # (rightly) rejects it.
    return Objective(task=TaskType.LOGISTIC_REGRESSION, l2=np.float32(0.4))


@register_contract(
    name="resident_value_and_grad",
    description="single-device Objective.value_and_grad: communication-"
                "free, transfer-free, f32 throughout",
    collectives={}, tags=("resident",))
def _contract_resident_value_and_grad():
    batch = _contract_batch()
    obj = _contract_objective()
    w = jnp.zeros((8,), jnp.float32)
    return (lambda o, wv, b: o.value_and_grad(wv, b)), (obj, w, batch)


@register_contract(
    name="resident_value_and_grad_bf16",
    description="value_and_grad on bf16 features: every contraction "
                "accumulates f32 (the MXU policy the dtype rule enforces)",
    collectives={}, tags=("resident",))
def _contract_resident_value_and_grad_bf16():
    batch = _contract_batch(feature_dtype=jnp.bfloat16)
    obj = _contract_objective()
    w = jnp.zeros((8,), jnp.float32)
    return (lambda o, wv, b: o.value_and_grad(wv, b)), (obj, w, batch)


@register_contract(
    name="streamed_blocked_ell_chunk_partials",
    description="Objective.chunk_value_grad_partials on a blocked-ELL "
                "chunk (the streamed-chunk leaf): communication-free, "
                "zero scatters of any kind, every sparse dot/einsum "
                "accumulating f32 — the out-of-HBM face of the "
                "blocked-ELL law",
    collectives={}, forbid=SCATTER_PRIMITIVES, require_f32_accum=True,
    tags=("streamed", "sparse"))
def _contract_streamed_blocked_ell_chunk_partials():
    from photon_tpu.data.dataset import make_batch
    from photon_tpu.data.matrix import _contract_blocked_ell

    X = _contract_blocked_ell(bf16=True)
    n = X.shape[0]
    batch = make_batch(X, jnp.zeros((n,), jnp.float32))
    obj = _contract_objective()
    w = jnp.zeros((X.n_features,), jnp.float32)
    return (lambda o, wv, b: o.chunk_value_grad_partials(wv, b)), \
        (obj, w, batch)


@register_contract(
    name="lane_blocked_ell_value_and_grad",
    description="lane-minor margin + value_and_grad_at_margin over a "
                "BlockedEllRows batch (G=3): the reg-sweep evaluation is "
                "scatter-free with f32 accumulation",
    collectives={}, forbid=SCATTER_PRIMITIVES, require_f32_accum=True,
    tags=("lane", "sparse"))
def _contract_lane_blocked_ell_value_and_grad():
    from photon_tpu.data.dataset import make_batch
    from photon_tpu.data.matrix import _contract_blocked_ell

    X = _contract_blocked_ell(bf16=True)
    n, d = X.shape
    G = 3
    batch = make_batch(X, jnp.zeros((n,), jnp.float32))
    obj = _contract_objective()
    l2s = jnp.asarray([0.1, 0.5, 1.0], jnp.float32)

    def fn(o, l2v, W, b):
        from photon_tpu.ops import lane_objective as lo

        z = lo.margin_lanes(o, W, b)
        return lo.value_and_grad_at_margin_lanes(o, l2v, W, z, b)

    return fn, (obj, l2s, jnp.zeros((d, G), jnp.float32), batch)


@register_contract(
    name="resident_linesearch_trial",
    description="margin-cached Wolfe trial (phi_at_ray): elementwise on "
                "cached (z, dz) — ZERO passes over X, pinned by forbidding "
                "dot_general outright",
    collectives={}, forbid=("dot_general",), tags=("resident",))
def _contract_linesearch_trial():
    import numpy as np

    batch = _contract_batch()
    obj = _contract_objective()
    z = jnp.zeros((64,), jnp.float32)
    dz = jnp.zeros((64,), jnp.float32)
    coeffs = tuple(jnp.asarray(v, jnp.float32) for v in (0.1, 0.2, 0.3))
    a = np.float32(0.5)
    return (lambda o, zz, dd, aa, cc, b: o.phi_at_ray(zz, dd, aa, cc, b)), \
        (obj, z, dz, a, coeffs, batch)
