"""Random-effect coordinate: vmapped per-entity solves over bucketed blocks.

Reference parity: com.linkedin.photon.ml.algorithm.RandomEffectCoordinate —
the reference trains one Breeze solver per entity inside each Spark
partition. Here each bucket's entities are stacked (E, m, …) and the whole
solver (L-BFGS/OWL-QN/TRON `lax.while_loop` included) is `vmap`'d over the
entity axis, then jit-compiled once per bucket shape; the entity axis is
sharded across the mesh's ``data`` axis so per-entity training scales over
chips. vmap of `lax.while_loop` runs all lanes until every entity converges,
freezing finished lanes — the per-entity convergence mask the reference
tracks via per-model OptimizationTrackers comes back in the vmapped
OptResult for free.

The block loop in :meth:`RandomEffectCoordinate.train` is a SOFTWARE
PIPELINE (docs/PERF.md "GAME random-effect cost model"): bucket *k+1*'s
upload and solve are dispatched BEFORE bucket *k*'s results are forced to
host, so device compute overlaps the host-side scatter/projection — JAX's
async dispatch makes this a reordering of the loop plus a small in-flight
ledger (``pipeline_depth``, default a depth-1 double-buffer mirroring
``ChunkedBatch.iter_device``'s prefetch). Buckets partition the entity set,
so every interleaving is bit-identical to the sequential loop
(``pipeline_depth=0``). Orthogonally, ``straggler_budget`` caps the first
vmapped pass at a budgeted iteration count and re-solves ONLY the
unconverged lanes — compacted into one small dense block
(`parallel.mesh.compact_rows`) — to full depth, so one ill-conditioned
entity no longer burns ``max_iters`` worth of MXU time for its whole
chunk: total device lane-iterations drop from ``chunks × max(lane iters)``
toward ``Σ per-entity iters``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from photon_tpu import checkpoint as _ckpt
from photon_tpu import profiling
from photon_tpu import telemetry
from photon_tpu.data.matrix import next_pow2
from photon_tpu.game.dataset import RandomEffectDataset, REBlock
from photon_tpu.game.model import RandomEffectModel
from photon_tpu.models.training import (
    _l1_lam,
    _static_config,
    l2_positive,
    make_objective,
    solve,
)
from photon_tpu.models.variance import (VarianceComputationType,
                                        compute_variances, takes_rows_form)
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.parallel.mesh import compact_rows, data_sharding, pad_to_multiple


def _pad_axis0(tree, target: int):
    """Pad every leaf's leading (entity) axis to `target` with zeros."""

    def pad(x):
        e = x.shape[0]
        if e == target:
            return x
        widths = [(0, target - e)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, widths)

    return jax.tree_util.tree_map(pad, tree)


# XLA-TPU compile time grows superlinearly in the vmapped lane count (~3s at
# 512 lanes, ~100s at 39k), so big entity blocks are solved in fixed-size
# lane chunks: one compile per block SHAPE, many cheap dispatches. The
# lanes of a chunk also step together until its slowest stops: on GLMix's
# widths 4096 beat both 512 (every op of a step nearer its launch cost:
# +13 % a fit) and 65,536 (every lane waits for the slowest of a whole
# bucket: +30 %) — my chip runs, PR 27.
_MAX_SOLVE_LANES = 4096
# Module-level solver cache keyed on (with_prior, weight-normalized config,
# variance type); the Objective and the L1 weight are runtime ARGUMENTS, so
# reg-weight grids and repeated estimator fits all share compilations.
# Entries are (jitted_fn, raw_vmapped_fn): the raw form feeds the
# scan-over-chunks dispatcher below.
_RE_SOLVERS: dict = {}


def _re_solver(with_prior: bool, cfg, variance):
    import dataclasses as _dc

    key = (with_prior, cfg, variance)
    fns = _RE_SOLVERS.get(key)
    if fns is not None:
        return fns

    def one(obj, lam, batch, w0):
        res = solve(obj, batch, w0, cfg, l1_weight=lam)
        var = compute_variances(obj, res.w, batch, variance)
        return res, var

    def one_with_prior(obj, lam, batch, w0, pm, pp):
        # Per-entity informative prior: the vmapped lanes each carry their
        # own (mean, precision) — incremental training's per-entity
        # PriorDistribution (pp == 0 ⇒ no prior for that lane, e.g. an
        # entity unseen in the previous run).
        obj_p = _dc.replace(obj, prior_mean=pm, prior_precision=pp)
        res = solve(obj_p, batch, w0, cfg, l1_weight=lam)
        var = compute_variances(obj_p, res.w, batch, variance)
        return res, var

    # One compile per bucket shape (jax.jit caches on shapes); the vmap
    # batches the entire while_loop solver across entities. obj/lam are
    # broadcast (in_axes None): shared by every lane.
    if with_prior:
        raw = jax.vmap(one_with_prior, in_axes=(None, None, 0, 0, 0, 0))
    else:
        raw = jax.vmap(one, in_axes=(None, None, 0, 0))
    fns = (jax.jit(raw), raw)
    _RE_SOLVERS[key] = fns
    return fns


def _re_lane_solve(cfg, obj, l2s, l1s, batch, w0):
    """The (entity × grid lane) form of `_re_solver`'s raw solves: ONE
    lane-minor solve (`models.training._lane_solve`) whose lanes are the
    batch's E entities × the grid's G lanes, each entity's block, labels
    and weights shared by its G lanes (`data.matrix.EntityBlocks`: a block
    read once a pass for all of them) and each lane with its own offsets,
    warm start, L2 weight and (OWL-QN) L1 weight. Entity-leading in and
    out, the lane axis last: offsets (E, m, G), warm starts (E, width, G),
    every result (E, ..., G) — the layout in which one gathered index reads
    or writes all G lanes of an entity's coefficient. Nothing is vmapped:
    a vmap over the lanes inside one over the entities batched each of the
    solver's while loops at both levels, and tracing that took most of a
    grid's set-up (PERF.md §6)."""
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.data.matrix import EntityBlocks
    from photon_tpu.models.training import _lane_solve

    E, m = batch.y.shape
    G = l2s.shape[0]

    def lanes(x):  # (E, r, G) -> (r, G·E), entity e's lane g at g·E + e
        return jnp.moveaxis(x, 0, -1).reshape(x.shape[1], -1)

    def rows(x):  # (E, m) -> (m, G·E): a row's one value for its G lanes
        return jnp.broadcast_to(x.T[:, None], (m, G, E)).reshape(m, -1)

    def back(x):  # (..., G·E) -> (E, ..., G)
        return jnp.moveaxis(x.reshape(x.shape[:-1] + (G, E)), -1, 0)

    res = _lane_solve(
        obj, GLMBatch(EntityBlocks(batch.X), rows(batch.y),
                      rows(batch.weights), lanes(batch.offsets)),
        lanes(w0).T, jnp.repeat(l2s, E),
        None if l1s is None else jnp.repeat(l1s, E), cfg,
        lane_evaluations=True)
    if res.evaluations is not None:
        # the OWL-QN lanes count their searches in lock step: every lane of
        # the solve took each of them (TRON has none)
        res = res._replace(evaluations=jnp.broadcast_to(res.evaluations,
                                                        res.value.shape))
    return jax.tree_util.tree_map(back, res), None


# vmapped per-lane variances, keyed on (with_prior, variance type, whether
# the objective's l2 is positive): the one-dispatch update computes a
# bucket's variances apart from its solves, in chunks of the bucket plan's
# `variance_lanes` (width² a lane; `variance_lanes_rows` in the rows form),
# where a solve's chunk holds up to _MAX_SOLVE_LANES lanes.
_RE_VARIANCES: dict = {}


def _re_variances(with_prior: bool, variance, l2_positive: bool = False):
    import dataclasses as _dc

    key = (with_prior, variance, l2_positive)
    raw = _RE_VARIANCES.get(key)
    if raw is not None:
        return raw

    def one(obj, batch, w, pm=None, pp=None):
        if with_prior:
            obj = _dc.replace(obj, prior_mean=pm, prior_precision=pp)
        return compute_variances(obj, w, batch, variance, l2_positive)

    raw = jax.vmap(one, in_axes=(None, 0, 0) + ((0, 0) if with_prior
                                                 else ()))
    _RE_VARIANCES[key] = raw
    return raw


# jitted scan-over-chunks wrappers, keyed on the raw vmapped solver: a block
# bigger than one lane chunk runs as lax.scan over its equal-shape chunks —
# ONE device dispatch per block (launch latency paid once, not once per
# chunk) while compile cost stays that of a single chunk.
_SCAN_DISPATCH: dict = {}


def _scan_dispatch(raw_fn):
    fn = _SCAN_DISPATCH.get(raw_fn)
    if fn is None:
        def run(head, stacked):
            def body(_, part):
                return None, raw_fn(*head, *part)

            _, outs = jax.lax.scan(body, None, stacked)
            return outs

        fn = jax.jit(run)
        _SCAN_DISPATCH[raw_fn] = fn
    return fn


def dispatch_chunked(solver_fns, head: tuple, args: tuple, chunk: int,
                     e_pad: int, mesh):
    """Run a bucket's vmapped solves in `chunk`-entity pieces.

    One chunk → the plain jitted solver. Multiple chunks → leaves reshaped
    to (k, chunk, ...) and lax.scan'd: one dispatch, single-chunk compile
    cost, finished chunks retired as the scan advances. ``head`` holds the
    broadcast arguments (objective, reg weights), ``args`` the
    entity-leading ones (batch, w0, priors), already padded to e_pad.
    """
    jit_fn, raw_fn = solver_fns
    if e_pad == chunk:
        if mesh is not None:
            args = jax.device_put(args, data_sharding(mesh))
        return jit_fn(*head, *args)
    k = e_pad // chunk
    stacked = jax.tree_util.tree_map(
        lambda x: x.reshape((k, chunk) + x.shape[1:]), args)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        stacked = jax.device_put(
            stacked, NamedSharding(mesh, P(None, tuple(mesh.axis_names))))
    outs = _scan_dispatch(raw_fn)(head, stacked)
    return jax.tree_util.tree_map(
        lambda x: x.reshape((e_pad,) + x.shape[2:]), outs)


def _lane_chunk(e_real: int, n_dev: int = 1) -> int:
    """Lane-chunk size for a bucket: next power of two of the entity count
    (floor 1 — `data.matrix.next_pow2` is the single pow2 implementation),
    capped at _MAX_SOLVE_LANES and rounded to a mesh multiple — so every
    block compiles at a small fixed lane count and larger blocks lax.scan
    over their chunks in ONE dispatch (dispatch_chunked)."""
    return pad_to_multiple(min(_MAX_SOLVE_LANES, next_pow2(max(e_real, 1), 1)),
                           n_dev)


def align_entity_priors(prior: RandomEffectModel, entity_keys, d: int):
    """A previous run's RandomEffectModel → per-entity Gaussian-prior
    blocks ``(means (E, d), precisions (E, d))`` aligned by entity KEY to
    ``entity_keys`` — the reference's per-entity incremental-training
    semantics, shared by `RandomEffectCoordinate.train` and the continual
    refresh (`photon_tpu/continual/refresh.py`).

    Entities unseen in the prior get precision 0 everywhere (no prior);
    with variances present the precision is the Laplace-posterior
    `optim.prior.PriorDistribution.from_variances` diagonal (variance ≤ 0
    ⇒ the dim was never estimated ⇒ no prior THERE, not an infinite one);
    without variances every seen entity gets unit precision (the
    flat-default incremental weight)."""
    from photon_tpu.optim.prior import PriorDistribution

    entity_keys = np.asarray(entity_keys)
    E = int(entity_keys.shape[0])
    pid = prior.dense_ids(entity_keys)  # (E,) rows in the prior
    seen = (pid < prior.n_entities).astype(np.float32)[:, None]
    prior_means = np.asarray(prior.coeffs_for(pid), np.float32)
    if prior.variances is not None:
        pvar = np.concatenate(
            [np.asarray(prior.variances, np.float32),
             np.ones((1, d), np.float32)])[pid]
        dist = PriorDistribution.from_variances(prior_means, pvar)
        prior_precs = (seen * dist.precision_diag).astype(np.float32)
    else:
        prior_precs = seen * np.ones((E, d), np.float32)
    return prior_means, prior_precs


@dataclasses.dataclass
class RETrainStats:
    """Per-train diagnostics (reference: per-entity OptimizationTracker)."""

    n_entities: int
    n_converged: int
    n_failed: int
    total_iterations: int
    # (E,) int64 solver iterations per dense entity id (first pass + any
    # compacted straggler re-solve), the per-entity tracker detail behind
    # the totals. None on the fused one-dispatch path, which keeps only
    # device-scalar totals.
    iterations_per_entity: Optional[np.ndarray] = dataclasses.field(
        default=None, compare=False, repr=False)
    # Σ over entities of weight-carrying active rows × solver iterations
    # taken: the update's work in rows·iterations (the fixed effect's is
    # n × iterations). None where a resumed run restored only the totals.
    row_iterations: Optional[float] = dataclasses.field(
        default=None, compare=False)
    # (E,) each entity's final objective (its loss over its active rows at
    # the update's offsets + its penalty) as its own solve computed it, on
    # the device; the one-dispatch update only.
    entity_values: Optional[jax.Array] = dataclasses.field(
        default=None, compare=False, repr=False)


@dataclasses.dataclass
class _InFlight:
    """One dispatched bucket in train()'s pipeline ledger: the block, its
    PADDED device args (kept alive so the straggler repack can gather the
    unconverged tail without re-uploading anything), and the solver outputs
    that have not yet been forced to host."""

    block: REBlock
    e_real: int
    chunk: int
    with_prior: bool
    obj: object
    args: tuple
    res: object
    var: object


@dataclasses.dataclass(eq=False)
class RandomEffectCoordinate:
    """Reference: algorithm.RandomEffectCoordinate."""

    dataset: RandomEffectDataset
    task: TaskType
    config: OptimizerConfig
    mesh: Optional[Mesh] = None
    variance: VarianceComputationType = VarianceComputationType.NONE
    # Shard-level NormalizationContext shared by every entity's solve; the
    # vmapped objective runs in normalized space and coefficients convert
    # back per entity row below.
    normalization: Optional[object] = None
    # Software-pipeline depth of train()'s block loop: how many bucket
    # solves may be in flight before the oldest is forced to host, so
    # device compute overlaps host scatter/projection. 1 = double-buffer
    # (default; mirrors ChunkedBatch.iter_device's prefetch), 0 = the
    # strictly sequential dispatch→readback→scatter loop. Buckets
    # partition the entity set, so every depth is bit-identical.
    pipeline_depth: int = 1
    # Straggler mitigation: cap the first vmapped pass at this many
    # iterations, then compact ONLY the unconverged lanes into one dense
    # second pass run to config.max_iters (warm-started from the capped
    # pass). None/0/≥max_iters = off. Changes iteration history (the
    # second pass restarts L-BFGS curvature state) but not the optimum —
    # per-entity problems are solved to the same tolerance.
    straggler_budget: Optional[int] = None

    def __post_init__(self):
        ds = self.dataset
        if ds.projection is not None:
            # Projection composes with neither normalization (the per-entity
            # factor gather has no shared-vector representation) nor, for
            # RANDOM, variances/priors (no diagonal transform exists through
            # a dense Gaussian matrix).
            if self.normalization is not None and not self.normalization.is_identity:
                raise ValueError(
                    "feature-space projection and normalization cannot be "
                    "combined on a random-effect coordinate; normalize the "
                    "shard before building the dataset instead"
                )
            if ds.projector is not None and self.variance is not VarianceComputationType.NONE:
                raise ValueError(
                    "coefficient variances are not defined through a RANDOM "
                    "projection; use INDEX_MAP projection or no projection"
                )

    def _solver_for(self, with_prior: bool):
        """jit(vmap(solve)) taking the Objective (and the dynamic L1 weight)
        as ARGUMENTS — cached at module level on the weight-normalized
        config, so different reg weights in a grid/tuner sweep, and even
        different RandomEffectCoordinate instances, share one compiled
        program per bucket shape. Per-dim specialization falls out of jit's
        shape-keyed cache (the Objective's leaves carry the dim)."""
        return _re_solver(with_prior, _static_config(self.config),
                          self.variance)

    def _block_objective(self, dim: int):
        norm = (self.normalization
                if self.dataset.projection is None else None)
        return make_objective(self.task, self.config, dim,
                              normalization=norm)

    def _variance_rows(self, block: REBlock) -> bool:
        """Whether the block's FULL variances take the rows form
        (`models.variance.takes_rows_form`, on one lane's shapes)."""
        if self.variance is not VarianceComputationType.FULL:
            return False
        ds = self.dataset
        X = ds.block_batch(block).X
        if isinstance(X, (jax.Array, np.ndarray)):
            X = jax.ShapeDtypeStruct(X.shape[1:], X.dtype)
        obj = self._block_objective(
            block.dim if block.dim is not None else ds.dim)
        return takes_rows_form(obj, X, l2_positive(self.config))

    def variance_rows_lanes(self) -> int:
        """Entities of one one-dispatch update whose FULL variances take
        the rows form; 0 where the variance type is not FULL."""
        return sum(block.n_entities for block in self.dataset.blocks
                   if self._variance_rows(block))

    def _effective_budget(self) -> Optional[int]:
        """The straggler first-pass iteration cap, or None when compaction
        is off (unset, non-positive, or no smaller than max_iters)."""
        b = self.straggler_budget
        if b is None or b <= 0 or b >= self.config.max_iters:
            return None
        return int(b)

    def _resolve_stragglers(self, fl, idx, w_out, conv, fail, iters, var_h,
                            lam):
        """Compacted second pass: gather ONLY the unconverged lanes of a
        capped first pass (typically a small tail) into one dense block and
        run it to full max_iters, warm-started from the capped solution.
        Mutates the host result arrays in place; returns nothing."""
        n2 = int(idx.size)
        n_dev = self.mesh.devices.size if self.mesh is not None else 1
        chunk2 = _lane_chunk(n2, n_dev)
        e_pad2 = pad_to_multiple(n2, chunk2)
        # Device-side repack from the still-alive padded first-pass args:
        # batch rows + priors gathered as-is, w0 replaced by the capped
        # pass's coefficients (the warm start). No feature block crosses
        # the host; dispatch_chunked re-shards onto the mesh as usual.
        tail_args = compact_rows((fl.args[0], fl.res.w) + tuple(fl.args[2:]),
                                 idx, pad_rows=e_pad2)
        solver = self._solver_for(fl.with_prior)  # full-depth program
        with telemetry.span("game_re.tail_solve", entities=n2), \
                profiling.measure("game_re.block", "tail_solve"):
            res2, var2 = dispatch_chunked(solver, (fl.obj, lam), tail_args,
                                          chunk2, e_pad2, self.mesh)
            w2, conv2, fail2, it2, var2h = jax.device_get(
                (res2.w, res2.converged, res2.failed, res2.iterations,
                 var2 if var_h is not None else None))
        it2 = np.asarray(it2, np.int64)[:n2]
        first = iters.copy()
        w_out[idx] = np.asarray(w2)[:n2]
        conv[idx] = np.asarray(conv2, bool)[:n2]
        fail[idx] = np.asarray(fail2, bool)[:n2]
        iters[idx] += it2
        if var_h is not None:
            var_h[idx] = np.asarray(var2h)[:n2]
        telemetry.count("game_re.straggler_entities", n2)
        telemetry.count("game_re.tail_resolves")
        # Iterations-saved estimate: uncapped, every first-pass chunk runs
        # ALL its lanes to the chunk's slowest total (vmapped while_loop);
        # compacted, chunks stop at the cap and the tail pays its own
        # (dense) cost once. Device lane-iterations, clipped at 0.
        chunk, e_pad = fl.chunk, first.shape[0]
        k = e_pad // chunk
        baseline = int(chunk * iters.reshape(k, chunk).max(axis=1).sum())
        actual = (int(chunk * first.reshape(k, chunk).max(axis=1).sum())
                  + e_pad2 * int(it2.max(initial=0)))
        telemetry.count("game_re.iters_saved", max(baseline - actual, 0))

    def train(
        self,
        offsets_full,
        warm_start: Optional[RandomEffectModel] = None,
        prior: Optional[RandomEffectModel] = None,
    ) -> tuple[RandomEffectModel, RETrainStats]:
        """``prior``: a previous run's RandomEffectModel — each entity seen in
        it gets a Gaussian prior from its old coefficients/variances, aligned
        by entity KEY (entities new to this dataset get no prior), the
        reference's per-entity incremental-training semantics."""
        ds = self.dataset
        E, d = ds.n_entities, ds.dim
        norm = (self.normalization
                if self.normalization is not None
                and not self.normalization.is_identity else None)
        coeffs = (
            np.array(warm_start.coefficients, np.float32)
            if warm_start is not None and warm_start.coefficients.shape == (E, d)
            else np.zeros((E, d), np.float32)
        )
        if norm is not None:
            # warm-start coefficients live in original space; the solve
            # runs in normalized space
            coeffs = norm.rows_to_normalized_space(coeffs)

        if prior is not None and ds.projector is not None:
            raise ValueError(
                "per-entity priors cannot be projected through a RANDOM "
                "projection; use INDEX_MAP projection or no projection"
            )
        prior_means = prior_precs = None
        if prior is not None and prior.dim == d:
            prior_means, prior_precs = align_entity_priors(
                prior, ds.entity_keys, d)
            if norm is not None:
                prior_means = norm.rows_to_normalized_space(prior_means)
                if norm.factors is not None:
                    f = np.asarray(norm.factors)
                    prior_precs = prior_precs * (f * f)[None, :]
        variances = (
            np.zeros((E, d), np.float32)
            if self.variance is not VarianceComputationType.NONE
            else None
        )
        n_conv = n_fail = 0
        iters_per_entity = np.zeros((E,), np.int64)
        lam = _l1_lam(self.config)
        n_dev = self.mesh.devices.size if self.mesh is not None else 1
        # One upload of the shared offsets; block_batch gathers per bucket.
        offsets_dev = jnp.asarray(offsets_full, jnp.float32)
        budget = self._effective_budget()
        capped = (None if budget is None else
                  dataclasses.replace(_static_config(self.config),
                                      max_iters=budget))

        # ---- checkpoint/restore: buckets partition the entity set and
        # retire in dispatch order, so "buckets 0..k retired" is a
        # consistent cut — the snapshot is the live coefficient array (in
        # SOLVE space) + the per-entity trackers + the retire cursor. The
        # in-flight ledger is NOT snapshotted: un-retired buckets simply
        # re-dispatch on resume, bit-identically (their warm-start rows
        # are untouched by other buckets).
        ck = _ckpt.current()
        st = ck.restore("re") if ck is not None else None
        n_blocks = len(ds.blocks)
        start_block = 0
        if st is not None:
            from photon_tpu.checkpoint import SnapshotStateError

            got = (st.get("kind"), int(st.get("E", -1)),
                   int(st.get("d", -1)), int(st.get("n_blocks", -1)),
                   bool(st.get("has_var", False)))
            want = ("re_train", E, d, n_blocks, variances is not None)
            if got != want:
                raise SnapshotStateError(
                    f"random-effect snapshot does not fit this coordinate:"
                    f" snapshot (kind, E, d, n_blocks, has_var)={got} vs "
                    f"resuming train() {want}")
            coeffs = np.array(st["coeffs"], np.float32)
            if variances is not None:
                variances = np.array(st["variances"], np.float32)
            iters_per_entity = np.array(st["iters"], np.int64)
            n_conv, n_fail = int(st["n_conv"]), int(st["n_fail"])
            start_block = int(st["blocks_done"])
            telemetry.count("checkpoint.re_restores")
        retired = start_block

        def dispatch(block: REBlock) -> _InFlight:
            """Pipeline stage 1: host prep + non-blocking upload + solve
            dispatch for one bucket. Nothing here waits on the device."""
            with telemetry.span("game_re.upload", m=block.m,
                                entities=block.n_entities), \
                    profiling.measure("game_re.block", "upload"):
                batch = ds.block_batch(block, offsets_dev)
                w0_full = coeffs[block.entity_index]
                # Project warm starts / priors into this bucket's solve
                # space (reference: ProjectionMatrix.projectCoefficients).
                if block.proj is not None:  # INDEX_MAP
                    from photon_tpu.game.projector import gather_rows

                    w0 = jnp.asarray(gather_rows(w0_full, block.proj))
                    pm = pp = None
                    if prior_means is not None:
                        pm = jnp.asarray(gather_rows(
                            prior_means[block.entity_index], block.proj))
                        pp = jnp.asarray(gather_rows(
                            prior_precs[block.entity_index], block.proj))
                elif ds.projector is not None:  # RANDOM
                    w0 = jnp.asarray(ds.projector.project_coeffs(w0_full))
                    pm = pp = None
                else:
                    w0 = jnp.asarray(w0_full)
                    pm = pp = None
                    if prior_means is not None:
                        pm = jnp.asarray(prior_means[block.entity_index])
                        pp = jnp.asarray(prior_precs[block.entity_index])
            e_real = block.n_entities
            with_prior = pm is not None
            obj = self._block_objective(
                block.dim if block.dim is not None else d)
            # Straggler mode runs the budget-capped variant of the SAME
            # cached solver family; the full-depth program only ever sees
            # the compacted tail.
            solver = (_re_solver(with_prior, capped, self.variance)
                      if capped is not None else self._solver_for(with_prior))
            chunk = _lane_chunk(e_real, n_dev)
            e_pad = pad_to_multiple(e_real, chunk)
            args = _pad_axis0((batch, w0) + ((pm, pp) if with_prior else ()),
                              e_pad)
            with telemetry.span("game_re.solve", m=block.m,
                                entities=e_real), \
                    profiling.measure("game_re.block", "solve_dispatch"):
                res, var = dispatch_chunked(solver, (obj, lam), args, chunk,
                                            e_pad, self.mesh)
            telemetry.count("game_re.blocks")
            return _InFlight(block, e_real, chunk, with_prior, obj, args,
                             res, var)

        def retire(fl: _InFlight) -> None:
            """Pipeline stage 2: force the OLDEST in-flight bucket's outputs
            to host and scatter/project them back — while any younger
            bucket's solve still runs on device."""
            nonlocal n_conv, n_fail, retired
            # fault-injection site: a preemption at bucket retirement
            # loses this bucket's (unscattered) results; resume
            # re-dispatches from the last retired cursor.
            _ckpt.kill_point("bucket_retire")
            block, e_real = fl.block, fl.e_real
            t0 = time.perf_counter_ns()
            with telemetry.span("game_re.readback", m=block.m), \
                    profiling.measure("game_re.block", "readback"):
                w_out, conv, fail, iters, var_h = jax.device_get(
                    (fl.res.w, fl.res.converged, fl.res.failed,
                     fl.res.iterations,
                     fl.var if variances is not None else None))
            telemetry.count("game_re.readback_wait_ns",
                            time.perf_counter_ns() - t0)
            # device_get buffers may be read-only; the straggler pass (and
            # nothing else) writes into them.
            w_out = np.asarray(w_out)
            conv = np.array(conv, bool)
            fail = np.array(fail, bool)
            iters = np.asarray(iters).astype(np.int64)
            if var_h is not None:
                var_h = np.array(var_h)
            if capped is not None:
                strag = np.nonzero(~conv[:e_real] & ~fail[:e_real])[0]
                if strag.size:
                    w_out = np.array(w_out)
                    self._resolve_stragglers(fl, strag, w_out, conv, fail,
                                             iters, var_h, lam)
            w_out = w_out[:e_real]
            if block.proj is not None:
                from photon_tpu.game.projector import scatter_rows_into

                scatter_rows_into(coeffs, w_out, block.entity_index,
                                  block.proj)
                if variances is not None:
                    scatter_rows_into(variances, var_h[:e_real],
                                      block.entity_index, block.proj)
            elif ds.projector is not None:
                coeffs[block.entity_index] = ds.projector.back_project(w_out)
            else:
                coeffs[block.entity_index] = w_out
                if variances is not None:
                    variances[block.entity_index] = var_h[:e_real]
            n_conv += int(conv[:e_real].sum())
            n_fail += int(fail[:e_real].sum())
            iters_per_entity[block.entity_index] = iters[:e_real]
            retired += 1
            if ck is not None:
                payload = {
                    "kind": "re_train", "E": E, "d": d,
                    "n_blocks": n_blocks,
                    "has_var": variances is not None,
                    "coeffs": coeffs, "iters": iters_per_entity,
                    "n_conv": n_conv, "n_fail": n_fail,
                    "blocks_done": retired}
                if variances is not None:
                    payload["variances"] = variances
                ck.update("re", payload)
                ck.note_evaluations()
                ck.maybe_snapshot()

        # The pipeline: dispatch runs ahead of retire by up to
        # `pipeline_depth` buckets. Buckets partition the entity set, so
        # dispatch(k+1)'s warm-start gather never reads rows retire(k)
        # writes — any depth is bit-identical to depth 0. A resumed run
        # skips the already-retired prefix of the bucket sequence.
        pending: deque = deque()
        depth = max(int(self.pipeline_depth), 0)
        for bi, block in enumerate(ds.blocks):
            if bi < start_block:
                continue
            pending.append(dispatch(block))
            telemetry.gauge("game_re.blocks_in_flight", len(pending))
            while len(pending) > depth:
                retire(pending.popleft())
        while pending:
            retire(pending.popleft())
        if ck is not None:
            ck.clear("re")
        total_iters = int(iters_per_entity.sum())
        if norm is not None:
            coeffs = norm.rows_to_original_space(coeffs)
            if variances is not None:
                variances = norm.variances_to_original_space(variances)
        model = RandomEffectModel(
            entity_name=ds.entity_name,
            feature_shard=ds.shard_name,
            task=self.task,
            coefficients=jnp.asarray(coeffs),
            entity_keys=ds.entity_keys,
            key_to_index=ds.key_to_index,
            variances=None if variances is None else jnp.asarray(variances),
        )
        return model, RETrainStats(
            E, n_conv, n_fail, total_iters, iters_per_entity,
            row_iterations=float(self._rows_per_entity() @ iters_per_entity))

    def _rows_per_entity(self) -> np.ndarray:
        """(E,) weight-carrying active rows of each entity, read from the
        blocks once a coordinate."""
        rows = getattr(self, "_rows_cache", None)
        if rows is None:
            rows = np.zeros((self.dataset.n_entities,), np.int64)
            for block in self.dataset.blocks:
                rows[block.entity_index] = np.count_nonzero(
                    np.asarray(block.weights), axis=1)
            self._rows_cache = rows
        return rows

    def score(self, model: RandomEffectModel) -> jax.Array:
        """Per-row margin for ALL rows — active and passive — via one gather
        + rowwise dot (reference: RandomEffectCoordinate.score joins the
        per-entity models back onto the data)."""
        return model.score(self.dataset.X, self.dataset.entity_dense)

    def fused_update_program(self, lanes: int = 1,
                             config: Optional[OptimizerConfig] = None):
        """ONE-dispatch whole-coordinate update for the no-normalization /
        single-device case, unprojected or INDEX_MAP, with or without a
        prior: offsets sum, every bucket's (chunk-scanned) solves from the
        warm starts it is HANDED in the bucket's own space — each lane
        regularized toward the prior it is handed, where one is
        (`bucket_priors`) —, the variances of the solution, a chunk of the
        bucket plan's `variance_lanes` at a time, the coefficient/variance
        write into the (E, d) table (through the bucket's index map where
        it has one), the full-row margins, and the objective — one jitted
        program, where the unfused
        train()+score()+objective route pays ~4+ device dispatches and,
        projected, carries the (E, d) table through the host. The path is
        chosen by what the dataset carries: a RANDOM projection (its dense
        matrix lives on the host) keeps the block loop.

        The table is WRITTEN in place and never read by the solves: a
        bucket's solution, in the bucket's projected space, is that
        bucket's warm start at its next update (Photon-ML's
        RandomEffectModelInProjectedSpace), so the program takes the
        buckets' warm starts as arguments and returns their solutions
        beside the table. A table enters that regime once, by
        `cold_warm_starts` (zeros, with a table of zeros) or by
        `adopt_table` (a caller's model): from then on a column outside an
        entity's index map is 0 and stays 0, because nothing writes it.

        The margins come from where a row's features already lie: a row a
        bucket holds is one element of `X_b · w_b`, the bucket's block
        times the solution its solve has just returned (the same forward
        pass, the same space: what this method's gates guarantee); only the
        rows no block holds read the updated table, and one (n,) gather
        lays both over the rows (`dataset.ScoringPlan`).

        Returns (fn, blocks_args, plan, objs, lam) — call
        ``fn(coeffs, warm, base, scores_tuple, objs, lam, blocks_args,
        plan, y, weights[, priors])``, which DONATES ``coeffs`` and
        ``warm`` (the table is updated in place: pass buffers nothing else
        reads); ``warm`` is one (E_b, width_b) f32 array a bucket, 0 on a
        bucket's padding columns; ``priors``, where given, one (mean,
        precision) pair of such arrays a bucket (`bucket_priors`, fixed
        across a descent, not donated) →
        (coeffs', variances', margins, objective, (n_conv, n_fail,
        n_iters, row_iters, block_steps, moved_row_iters, ls_trials),
        values, warm') — `row_iters` / `block_steps` the update's work: Σ
        weight-carrying rows × iterations over the entities, and Σ
        lock-step iterations over the blocks; `moved_row_iters` the part of
        `row_iters` whose iteration lowered its lane's loss; `ls_trials` Σ
        line-search evaluations over the entities; `values` (E,) each
        entity's own final objective, as its solve computed it; `warm'` the
        buckets' solutions, the next update's ``warm`` — or None
        when this coordinate needs the general train() path.

        ``lanes`` G > 1: the same update for G regularization-grid lanes
        (`game.grid.fit_game_grid`) under ``config``, the lanes'
        weight-normalized configuration (`models.training.
        lane_weight_arrays`). Every bucket's block is shared by its G lanes
        and everything a lane owns carries a trailing lane axis: the warm
        starts and solutions (E_b, width_b, G), the other coordinates'
        scores and the margins (n, G), the objective and the stats' sums
        (G,), ``values`` (E, G); ``lam`` is the lanes' (l2s, l1s) weights.
        There is no (E, d) table (pass None; None comes back): the passive
        rows read the buckets' solutions (`dataset.LaneScoringPlan`), and
        the lanes' tables are made from the last solutions, once
        (`game.grid`). No variances and no priors: None for a coordinate
        with either.
        """
        if lanes > 1:
            cache = self.__dict__.setdefault("_fused_lanes_cache", {})
            if (lanes, config) not in cache:
                cache[lanes, config] = (
                    None if self.variance is not VarianceComputationType.NONE
                    else self._fused_program(config, lanes))
            return cache[lanes, config]
        cached = getattr(self, "_fused_cache", None)
        if cached is not None:
            return cached
        out = self._fused_program(_static_config(self.config), 1)
        self._fused_cache = out
        return out

    def _fused_program(self, cfg, lanes: int):
        ds = self.dataset
        if self._effective_budget() is not None:
            # the compacted straggler re-solve needs the host repack
            # between passes — it cannot live inside one jit program, so a
            # budgeted coordinate takes the pipelined train() path. Said
            # out loud (once) rather than silently: a user who set BOTH
            # knobs should know which one won.
            telemetry.count("game_re.fused_gate_offs")
            if not getattr(self, "_fused_gate_logged", False):
                object.__setattr__(self, "_fused_gate_logged", True)
                from photon_tpu.utils.logging import photon_logger

                photon_logger("photon_tpu.game", propagate=True).info(
                    "random-effect coordinate %r: straggler_budget=%s "
                    "disables the fused one-dispatch update (the "
                    "compacted tail re-solve needs a host repack between "
                    "passes); training on the pipelined block loop",
                    ds.entity_name, self.straggler_budget)
            return None
        if (ds.projector is not None or self.mesh is not None
                or (self.normalization is not None
                    and not self.normalization.is_identity)):
            return None
        meta = []  # (lane chunk, entities, variance chunk) per block — static
        blocks_args = []  # (row_index, ents, cols, batch_base) — arrays
        objs = []
        d = ds.dim
        for block, (ents, cols) in zip(ds.blocks, ds.table_maps):
            e_real = block.n_entities
            base_batch = ds.block_batch(block)
            var_lanes = (block.variance_lanes_rows
                         if self._variance_rows(block)
                         else block.variance_lanes)
            # a chunk holds up to _MAX_SOLVE_LANES (entity, lane) solves
            meta.append((min(e_real, max(_MAX_SOLVE_LANES // lanes, 1)),
                         e_real, min(var_lanes, e_real)))
            blocks_args.append((block.row_index, ents, cols, base_batch))
            objs.append(self._block_objective(
                block.dim if block.dim is not None else d))
        return (_fused_re_fn(cfg, tuple(meta), self.task, self.variance,
                             l2_positive(self.config), lanes,
                             ds.n_entities if lanes > 1 else 0),
                tuple(blocks_args),
                ds.scoring_plan if lanes == 1 else ds.lane_scoring_plan,
                tuple(objs), _l1_lam(self.config))


# Module-level cache for the fused RE update (cf. _RE_SOLVERS): keyed on the
# solver fns + static block metadata + task/variance, so sequential
# reg-weight grids — which build one RandomEffectCoordinate per weight over
# the SAME dataset — share one compiled program (obj/lam are runtime args).
_FUSED_RE: dict = {}


def _solve_lanes(raw_fn, head: tuple, args: tuple, chunk: int, e_real: int):
    """A bucket's vmapped solves over its RESIDENT arrays, `chunk` lanes at
    a time: one call where the bucket is one chunk; else `lax.scan` over
    slices of the arrays as they lie (no padded copy of the block). The
    last slice is laid back over the end of the bucket, so its first lanes
    solve entities the slice before it has solved (the same problem, the
    same answer) and are cut from the result."""
    if e_real <= chunk:
        return raw_fn(*head, *args)
    k = -(-e_real // chunk)
    starts = np.minimum(np.arange(k) * chunk, e_real - chunk)

    def body(_, start):
        part = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, start, chunk), args)
        return None, raw_fn(*head, *part)

    _, outs = jax.lax.scan(body, None, jnp.asarray(starts, jnp.int32))
    overlap = k * chunk - e_real
    return jax.tree_util.tree_map(
        lambda x: jnp.concatenate(
            [x[:-1].reshape((-1,) + x.shape[2:]), x[-1, overlap:]]), outs)


def _table_index(ents, cols):
    """Where a bucket's (E_b, width_b) coefficients lie in the (E, d) table:
    whole rows, or through the bucket's index map (a padding column points
    past the table: a read there fills 0, a write drops)."""
    return (ents,) if cols is None else (ents[:, None], cols)


def cold_warm_starts(blocks_args: tuple, d: int, lanes: int = 1) -> tuple:
    """The buckets' warm starts of a cold start: zeros in each bucket's own
    space, to go with an (E, d) table of zeros — with ``lanes`` > 1, one
    (E_b, width_b, G) array a bucket."""
    return tuple(
        jnp.zeros((ents.shape[0], d if cols is None else cols.shape[1])
                  + ((lanes,) if lanes > 1 else ()), jnp.float32)
        for _, ents, cols, _ in blocks_args)


@partial(jax.jit, donate_argnums=(0,))
def adopt_table(coeffs, blocks_args: tuple):
    """A caller's (E, d) table enters a descent: → (coeffs', warm), the
    buckets' warm starts read through their index maps and the columns
    outside an entity's map cleared — what every fused update did before
    the buckets' solutions were carried from one update to the next. It
    DONATES ``coeffs``: pass a copy of a model somebody else holds."""
    warm = []
    with telemetry.device_scope("game_re.adopt"):
        for _, ents, cols, _ in blocks_args:
            at = _table_index(ents, cols)
            w0 = coeffs.at[at].get(mode="fill", fill_value=0)
            if cols is not None:
                coeffs = coeffs.at[ents].set(0.0).at[at].set(w0, mode="drop")
            warm.append(w0)
    return coeffs, tuple(warm)


@jax.jit
def _rows_by_key(coeffs, pid):
    with telemetry.device_scope("game_re.adopt"):
        return coeffs.at[pid].get(mode="fill", fill_value=0)


def initial_table(model: RandomEffectModel, entity_keys) -> jax.Array:
    """A caller's model as a FRESH (E, d) f32 table over ``entity_keys``
    (for `adopt_table`, which donates it): the model's own rows where it
    was fit over these entities, else each entity's row found by key — an
    entity the model never saw (new since it was fit) gets zeros."""
    keys = np.asarray(entity_keys)
    if np.array_equal(np.asarray(model.entity_keys), keys):
        return jnp.array(model.coefficients, jnp.float32)
    return _rows_by_key(jnp.asarray(model.coefficients, jnp.float32),
                        jnp.asarray(model.dense_ids(keys), jnp.int32))


# A non-positive variance is a dimension the prior model never estimated:
# no prior there (optim.prior.PriorDistribution.from_variances, whose
# floor this is).
_PRIOR_MIN_VARIANCE = 1e-12


@jax.jit
def _bucket_priors(means, variances, pid, maps):
    out = []
    with telemetry.device_scope("game_re.prior"):
        for ents, cols in maps:
            rows = pid[ents]
            at = _table_index(rows, cols)
            mu = means.at[at].get(mode="fill", fill_value=0)
            if variances is None:  # the flat-default incremental weight
                known = (rows < means.shape[0])[:, None]
                if cols is not None:
                    known = known & (cols < means.shape[1])
                tau = jnp.broadcast_to(known, mu.shape).astype(jnp.float32)
            else:
                var = variances.at[at].get(mode="fill", fill_value=0)
                tau = jnp.where(
                    var > 0.0,
                    1.0 / jnp.maximum(var, _PRIOR_MIN_VARIANCE), 0.0)
            out.append((mu, tau))
    return tuple(out)


def bucket_priors(prior: RandomEffectModel, entity_keys,
                  blocks_args: tuple) -> tuple:
    """A previous run's model as each bucket's Gaussian prior, in the
    bucket's own projected space: ((mean (E_b, width_b), precision
    (E_b, width_b)) a bucket), gathered ON THE DEVICE through the buckets'
    index maps from the model's (E', d) coefficient and variance tables,
    each entity's row found by key. The semantics of `align_entity_priors`
    without its two host (E, d) arrays: an entity the prior never saw, a
    column it never estimated (variance ≤ 0) and a bucket's padding column
    get precision 0 — no prior there; without variances every column of a
    seen entity gets unit precision."""
    pid = prior.dense_ids(entity_keys)
    seen = int(np.count_nonzero(pid < prior.n_entities))
    telemetry.count("game_re.prior_seen", seen)
    telemetry.count("game_re.prior_unseen", int(pid.shape[0]) - seen)
    variances = (None if prior.variances is None
                 else jnp.asarray(prior.variances, jnp.float32))
    return _bucket_priors(jnp.asarray(prior.coefficients, jnp.float32),
                          variances, jnp.asarray(pid, jnp.int32),
                          tuple((ents, cols)
                                for _, ents, cols, _ in blocks_args))


def _fused_re_fn(cfg, meta: tuple, task, variance, l2_positive=False,
                 lanes: int = 1, n_entities: int = 0):
    """The jitted one-dispatch update of `fused_update_program` (which says
    what it takes and returns), one compiled program a (solver config,
    block shapes, task, variance, whether l2 > 0, grid lanes) and a prior
    or none. It writes the (E, d) table and never reads a warm start out of
    it, nor clears a row: the table it is given is 0 outside the buckets'
    index maps (`cold_warm_starts` / `adopt_table`). With ``lanes`` > 1
    every solve runs over (entity × lane) on the entity's one block, no
    table is written, and the passive rows' gathers read all G lanes of a
    coefficient through one index of the buckets' solutions (``values``:
    (``n_entities``, G))."""
    key = (cfg, meta, task, variance, l2_positive, lanes, n_entities)
    fn = _FUSED_RE.get(key)
    if fn is not None:
        return fn

    def run(coeffs, warm, base, scores, objs, lam, blocks_args, plan, y,
            weights, priors=None):
        from photon_tpu.data.matrix import layout_matvec
        from photon_tpu.game.model import score_entities, score_lanes
        from photon_tpu.game.scoring import _sum_scores
        from photon_tpu.ops.losses import loss_fns

        loss, _, _ = loss_fns(task)
        with_prior = priors is not None
        if lanes == 1:
            raw_fn = _re_solver(with_prior, cfg,
                                VarianceComputationType.NONE)[1]
            head = (lam,)
        else:  # lam: the lanes' (l2s, l1s)
            raw_fn = partial(_re_lane_solve, cfg)
            head = tuple(lam)
        var_fn = (None if variance is VarianceComputationType.NONE
                  else _re_variances(with_prior, variance, l2_positive))
        with telemetry.device_scope("game.objective"):
            if lanes == 1:
                offs = _sum_scores(base, scores)
            else:  # the other coordinates' (n, G) lane scores
                offs = jnp.broadcast_to(_sum_scores(base[:, None], scores),
                                        base.shape + (lanes,))
        # The table is written IN PLACE (its buffer is donated): the
        # buckets partition the entities, so each writes rows no other
        # touches, over the index map its last solution was written through.
        variances = jnp.zeros_like(coeffs) if var_fn is not None else None
        conv = fail = iters = row_iters = steps = moved = trials = 0
        if lanes == 1:
            values = jnp.zeros((coeffs.shape[0],), jnp.float32)
            total, matvec = jnp.sum, jax.vmap(layout_matvec)
        else:  # per-lane sums; a block pass for every lane of its entity
            values = jnp.zeros((n_entities, lanes), jnp.float32)
            total = partial(jnp.sum, axis=0)
            matvec = jax.vmap(jax.vmap(layout_matvec), in_axes=(None, -1),
                              out_axes=-1)
        scored = []  # the buckets' (E_b · m,) block margins, then the table's
        carried = []  # the buckets' solutions: the next update's `warm`
        for (row_index, ents, cols, batch_base), w0, (chunk, e_real,
                                                     var_chunk), obj, prior \
                in zip(blocks_args, warm, meta, objs,
                       priors or ((),) * len(meta)):
            with telemetry.device_scope("game_re.gather"):
                batch = batch_base._replace(offsets=offs[row_index])
            with telemetry.device_scope("game_re.solve"):
                res, _ = _solve_lanes(raw_fn, (obj,) + head,
                                      (batch, w0) + tuple(prior), chunk,
                                      e_real)
            var = None
            if var_fn is not None:
                # the variances of the solution this update returns, a
                # chunk of the plan's workspaces at a time (width² a lane,
                # or m² + 2·m·width in the rows form)
                with telemetry.device_scope("game_re.variance"):
                    var = _solve_lanes(var_fn, (obj,),
                                       (batch, res.w) + tuple(prior),
                                       var_chunk, e_real)
            carried.append(res.w)
            with telemetry.device_scope("game_re.score"):
                # the rows this bucket holds, by the forward pass its
                # objective runs, at the solution in the block's own space
                scored.append(matvec(batch_base.X, res.w).reshape(
                    (-1,) + offs.shape[1:]))
            if lanes == 1:
                with telemetry.device_scope("game_re.scatter"):
                    at = _table_index(ents, cols)
                    coeffs = coeffs.at[at].set(res.w, mode="drop")
                    if var is not None:
                        variances = variances.at[at].set(var, mode="drop")
            conv += total(res.converged)
            fail += total(res.failed)
            iters += total(res.iterations)
            rows = jnp.sum(batch_base.weights != 0.0, axis=1)
            if lanes > 1:
                rows = rows[:, None]
            row_iters += total(rows.astype(jnp.float32)
                               * res.iterations.astype(jnp.float32))
            steps += jnp.max(res.iterations)
            # iterations that lowered the lane's loss: the rest repeated
            # a point (a fixed-depth solve past its stall)
            lower = res.loss_history[:, 1:] < res.loss_history[:, :-1]
            moved += total(rows.astype(jnp.float32)
                           * jnp.sum(lower, axis=1).astype(jnp.float32))
            if res.evaluations is not None:
                trials += total(res.evaluations)
            values = values.at[ents].set(res.value)
        slot_of_row = plan.slot_of_row
        if plan.n_table_rows:  # rows no block holds: the updated table
            scored.append(
                score_entities(plan.X_passive, coeffs, plan.ids_passive,
                               exact=True) if lanes == 1 else
                score_lanes(tuple(carried), plan.values, plan.positions))
        with telemetry.device_scope("game_re.score"):
            margins = jnp.concatenate(scored)[slot_of_row]
        with telemetry.device_scope("game.objective"):
            if lanes == 1:
                objective = jnp.sum(weights * loss(offs + margins, y))
            else:
                objective = jnp.sum(
                    weights[:, None] * loss(offs + margins, y[:, None]),
                    axis=0)
        return coeffs, variances, margins, objective, (
            conv, fail, iters, row_iters, steps, moved, trials), values, \
            tuple(carried)

    fn = jax.jit(run, donate_argnums=(0, 1))
    _FUSED_RE[key] = fn
    return fn


# ----------------------------------------------------------------- contracts
# The vmapped per-entity solve block — the "lane" workload (one whole
# L-BFGS while_loop per entity lane, batched): every lane is device-local,
# so the block is communication-free, f32, and host-exit-free end to end
# (photon_tpu/analysis traces and enforces this on every PR).
from photon_tpu.analysis.contracts import register_contract  # noqa: E402


def _re_contract_fixture(max_iters: int = 5):
    """Shared (raw solver, obj, batch, w0) fixture for the game_re specs."""
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.optim.regularization import l2

    E, m, d = 4, 16, 5
    cfg = OptimizerConfig(max_iters=max_iters, tolerance=1e-7, reg=l2(),
                          reg_weight=0.3, history=3)
    raw = _re_solver(False, _static_config(cfg),
                     VarianceComputationType.NONE)[1]
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d)
    batch = GLMBatch(X=jnp.zeros((E, m, d), jnp.float32),
                     y=jnp.zeros((E, m), jnp.float32),
                     weights=jnp.ones((E, m), jnp.float32),
                     offsets=jnp.zeros((E, m), jnp.float32))
    w0 = jnp.zeros((E, d), jnp.float32)
    return raw, obj, batch, w0


@register_contract(
    name="game_re_vmapped_solve",
    description="one random-effect bucket's vmapped per-entity L-BFGS "
                "solves: E lanes, zero communication, no transfers inside "
                "the vmapped while_loop",
    collectives={}, tags=("game", "lane"))
def _contract_re_vmapped_solve():
    raw, obj, batch, w0 = _re_contract_fixture()
    return (lambda o, b, w: raw(o, None, b, w)), (obj, batch, w0)


@register_contract(
    name="game_re_budgeted_first_pass",
    description="the straggler-capped first pass: the SAME vmapped lane "
                "program at a budgeted max_iters — capping iterations must "
                "not change the zero-collective / no-transfer story the "
                "pipelined block loop rests on",
    collectives={}, tags=("game", "lane"))
def _contract_re_budgeted_first_pass():
    # max_iters=2 stands in for dataclasses.replace(cfg, max_iters=budget):
    # the capped solver is the same cached family at a smaller static bound.
    raw, obj, batch, w0 = _re_contract_fixture(max_iters=2)
    return (lambda o, b, w: raw(o, None, b, w)), (obj, batch, w0)


@register_contract(
    name="game_re_mesh_bucket_solve",
    description="a random-effect bucket's vmapped per-entity solves "
                "SHARDED over the mesh's entity axis (shard_map over all "
                "axes): B buckets solve on B x lanes chips with ZERO "
                "collectives — per-entity training is embarrassingly "
                "parallel and the pod-scale GAME sweep's RE half "
                "contributes nothing to the collective budget",
    collectives={}, tags=("game", "lane", "mesh"))
def _contract_re_mesh_bucket_solve():
    from jax.sharding import PartitionSpec as P

    from photon_tpu.parallel.mesh import make_mesh, shard_map

    mesh = make_mesh()
    n_dev = int(mesh.devices.size)
    E = 2 * n_dev  # entity lanes divide the mesh
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.optim.regularization import l2

    m, d = 8, 5
    cfg = OptimizerConfig(max_iters=4, tolerance=1e-7, reg=l2(),
                          reg_weight=0.3, history=3)
    raw = _re_solver(False, _static_config(cfg),
                     VarianceComputationType.NONE)[1]
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d)
    batch = GLMBatch(X=jnp.zeros((E, m, d), jnp.float32),
                     y=jnp.zeros((E, m), jnp.float32),
                     weights=jnp.ones((E, m), jnp.float32),
                     offsets=jnp.zeros((E, m), jnp.float32))
    w0 = jnp.zeros((E, d), jnp.float32)
    ent = P(tuple(mesh.axis_names))

    def fn(o, b, w):
        ospec = jax.tree_util.tree_map(lambda _: P(), o)
        bspec = jax.tree_util.tree_map(lambda _: ent, b)
        return shard_map(lambda ov, bv, wv: raw(ov, None, bv, wv),
                         mesh=mesh, in_specs=(ospec, bspec, ent),
                         out_specs=ent)(o, b, w)

    return fn, (obj, batch, w0)


@register_contract(
    name="game_re_straggler_resolve",
    description="the compacted straggler re-solve: device-side gather of "
                "the unconverged tail (parallel.mesh.compact_rows) + the "
                "dense full-depth second pass — zero collectives off-mesh, "
                "no transfer/callback primitives inside the vmapped "
                "while_loop",
    collectives={}, tags=("game", "lane"))
def _contract_re_straggler_resolve():
    raw, obj, batch, w0 = _re_contract_fixture()

    def fn(o, b, w, idx):
        tail_b, tail_w = compact_rows((b, w), idx, pad_rows=4)
        return raw(o, None, tail_b, tail_w)

    idx = jnp.asarray(np.asarray([1, 3]), jnp.int32)
    return fn, (obj, batch, w0, idx)
