"""Streamed (out-of-HBM) solvers: L-BFGS and OWL-QN whose every objective
evaluation accumulates over host-resident device chunks — on one chip, or
row-sharded across a whole mesh.

Reference parity: com.linkedin.photon.ml.function.glm.DistributedGLMLossFunction
drives Breeze L-BFGS/OWL-QN with ONE `RDD.treeAggregate` per evaluation — the
dataset never lives in one executor's memory. This module is the literal
analog: the dataset lives on host as a `data.dataset.ChunkedBatch`, each
evaluation streams the chunks through the device (prefetched `device_put`,
so chunk i+1 transfers while chunk i computes) and sums the
`Objective.chunk_*_partials` leaves on device, so HBM holds O(chunk + solver
state) instead of O(dataset). That is the one capability the resident solvers
cannot offer: BASELINE config 4's 100M-row regime past the HBM budget.

MESH MODE (``mesh=``): every streamed chunk is row-sharded over ALL mesh
axes — each device slot is fed its own host slice (`ChunkedBatch.
mesh_chunk`; on multi-host each process device_puts only its own slots'
rows, so features never cross DCN) and the chunk-partial programs run under
`shard_map` with NO internal collective: per-chunk partial sums stay
device-local, accumulate device-local across chunks, and each evaluation
closes with ONE hierarchical `psum` of the (value, (d,)-gradient) partials
(`_MeshChunkOps.finish`) — reduce over the ICI inside the slice, one (d,)
vector across DCN per evaluation, the exact treeAggregate shape of
`parallel/mesh.py`'s docstring, driven chunk by chunk. An out-of-HBM
dataset therefore trains on every chip of a pod at once, each device
streaming 1/D of every feature chunk.

Where the execution regime differs from the resident solvers, the MATH does
not:

- The outer loop runs on HOST (it must re-stream chunks per evaluation, so a
  `lax.while_loop` cannot express it), but every numeric step — two-loop
  direction, history push, chunk partials, margin updates — is the SAME
  device code the resident solvers run (`two_loop` and `_push` are
  imported, not reimplemented), and convergence criteria mirror
  `optim.lbfgs._convergence` / `optim.owlqn` term for term. The parity
  tests pin streamed == resident to f32 accumulation noise
  (tests/test_streamed.py).
- L-BFGS line search rides CACHED PER-CHUNK MARGINS: z chains on host as
  z += α·dz (refreshed from w every `_Z_REFRESH` iterations, like the
  resident margin solver), so a Wolfe trial uploads 16 bytes/row of (z, dz)
  instead of re-streaming the chunk's features, and the first trial
  piggybacks on the direction pass — the common accept-at-α=1 iteration
  costs exactly TWO feature-chunk streams (dz pass + gradient pass), the
  same two X passes per iteration the resident margin-cached solver pays.
  The reference pays a full treeAggregate per Breeze trial.
- OWL-QN's orthant projection breaks margin linearity, so its backtracking
  ladder is evaluated in candidate LANES instead: one chunk stream prices
  up to `ladder_lanes` trial steps at once (`chunk_value_partials_many`
  shares the chunk upload across candidates), and selecting the FIRST
  passing rung is exactly equivalent to the resident solver's sequential
  halving (each rung's Armijo test is memoryless).

TRON is deliberately absent: its CG inner loop needs one HVP — a full
dataset stream — per CG step, so a streamed TRON pays cg_max_iters streams
per outer iteration where L-BFGS pays two. `models.training.train_glm`
rejects the combination with a pointer here instead of silently shipping a
solver whose cost model is wrong for the regime.
"""
from __future__ import annotations

import itertools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from photon_tpu import checkpoint as _ckpt
from photon_tpu import profiling
from photon_tpu import telemetry
from photon_tpu.data.dataset import GLMBatch
from photon_tpu.data.matrix import ShardedBlockedEllRows, SparseRows
from photon_tpu.optim.config import stop_state
from photon_tpu.optim.lbfgs import (_Z_REFRESH, History, _push,
                                     empty_history, history_from_slots,
                                     two_loop)
from photon_tpu.optim.linesearch import C1, C2
from photon_tpu.optim.owlqn import pseudo_gradient
from photon_tpu.optim.tracker import OptResult

__all__ = ["minimize_lbfgs_streamed", "minimize_owlqn_streamed"]


# ---------------------------------------------------------------- device ops
# Every numeric step is a module-level jitted program (cached by shape), so
# the host loop costs dispatches, not retraces. Objective/GLMBatch are
# registered pytrees; host numpy chunk leaves device-put on call.
#
# DONATION (the upload/compute-overlap round): each chunk-consuming
# program has a `_don`-suffixed twin that DONATES its feature-chunk
# argument. What that buys is less than it says: jax donates an input only
# where an output of its aval or size can take the buffer
# (`jax/_src/interpreters/mlir.py::_set_up_aliases`), so a chunk's scalar
# leaves (y/weights/offsets ↔ margins) alias outputs and its feature
# blocks — the hot block, every tail bucket — are NOT donated at all: they
# stay on the device until the host loop's last name for them goes (on
# the chip a third 4.45 GB chunk was live through every upload, PERF.md
# §6, PR 34). So the one-device backend also hands each program's outputs
# to `DeviceChunkRing.consumed`, straight after the dispatch and before it
# reads anything back: there the ring frees the chunk handed out BEFORE
# this one (its program has finished) and issues the next upload BEHIND
# the program just dispatched, which so runs beside that upload's
# transfers and not after them (PERF.md §6, PR 35); the margin readback
# that follows finds its program done. The backends pick the donated twin
# whenever the chunk has no cross-chunk shared leaves (`_donatable` — the
# mesh blocked-ELL ladder shares ONE replicated column permutation across
# chunks, so it keeps the plain programs). Donation never changes the
# traced program or its signature — the `mesh_stream_donated_no_retrace`
# contract pins that the ring's rotating dispatches stay ONE signature.


# jax warns "Some donated buffers were not usable" once per compiled chunk
# shape for exactly those feature blocks; the ring frees them.
import warnings as _warnings  # noqa: E402

_warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def _chunk_init_fn(obj, w, batch):
    return obj.chunk_value_grad_partials(w, batch)


def _chunk_grad_fn(obj, z, batch):
    return obj.chunk_partials_at_margin(z, batch)


def _chunk_dz_phi_fn(obj, p, z, a, batch):
    dz = obj.direction_margin(p, batch)
    return dz, obj.chunk_phi_partials(z, dz, a, batch.y, batch.weights)


def _chunk_value_many_fn(obj, W, batch):
    return obj.chunk_value_partials_many(W, batch)


_chunk_init = jax.jit(_chunk_init_fn)
_chunk_init_don = jax.jit(_chunk_init_fn, donate_argnums=(2,))
_chunk_grad_at_margin = jax.jit(_chunk_grad_fn)
_chunk_grad_at_margin_don = jax.jit(_chunk_grad_fn, donate_argnums=(2,))
_chunk_dz_phi = jax.jit(_chunk_dz_phi_fn)
_chunk_dz_phi_don = jax.jit(_chunk_dz_phi_fn, donate_argnums=(4,))
_chunk_value_many = jax.jit(_chunk_value_many_fn)
_chunk_value_many_don = jax.jit(_chunk_value_many_fn, donate_argnums=(2,))


@jax.jit
@telemetry.device_scope("lbfgs.linesearch")
def _chunk_phi(obj, z, dz, a, y, weights):
    return obj.chunk_phi_partials(z, dz, a, y, weights)


@jax.jit
def _finish(obj, w, partials):
    return obj.finish_value_grad(w, partials)


# The cross-chunk partial accumulator donates its running total: the
# (value, (d,)-gradient[, gsum]) tree updates IN PLACE instead of
# allocating a fresh tree per chunk — on a mesh that is the stacked
# (n_slots, d) gradient block every chunk of every evaluation.
_acc = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
               donate_argnums=(0,))


def _donatable(c0) -> bool:
    """Whether a chunk ladder's device chunks may be donated to their
    compute program: True unless chunks share device buffers (the mesh
    blocked-ELL ladder replicates ONE column permutation across all
    chunks of a solve — donating chunk 0 would invalidate chunk 1)."""
    return not isinstance(c0, ShardedBlockedEllRows)


@jax.jit
def _ray_coeffs(obj, w, p):
    return obj.ray_reg_coeffs(w, p)


@jax.jit
@telemetry.device_scope("lbfgs.update")
def _axpy(w, a, p):
    return w + a * p


@jax.jit
def _lbfgs_direction(g, h):
    p = -two_loop(h, g)
    with telemetry.device_scope("lbfgs.direction"):
        dphi0 = jnp.dot(p, g)
        bad = dphi0 >= 0.0
        p = jnp.where(bad, -g, p)
        dphi0 = jnp.where(bad, -jnp.dot(g, g), dphi0)
        return p, dphi0, jnp.linalg.norm(p)


@jax.jit
def _owlqn_direction(w, g, l1, mask, h):
    pg = pseudo_gradient(w, g, l1, mask)
    p = -two_loop(h, pg)
    p = jnp.where(p * pg < 0.0, p, 0.0)
    dphi0 = jnp.dot(p, pg)
    bad = dphi0 >= 0.0
    p = jnp.where(bad, -pg, p)
    dphi0 = jnp.where(bad, -jnp.dot(pg, pg), dphi0)
    xi = jnp.where(w != 0.0, jnp.sign(w), jnp.sign(-pg))
    return p, dphi0, xi, pg, jnp.linalg.norm(p)


@jax.jit
def _owlqn_candidates(obj, w, p, xi, alphas, pg, l1, mask):
    """Projected ladder candidates W (K, d) + their Armijo decrements,
    L1 terms and smooth-reg values — the per-iteration (d,)-sized work,
    done ONCE on device, not per chunk."""
    W = w[None, :] + alphas[:, None] * p[None, :]
    W = jnp.where(W * xi[None, :] > 0.0, W, 0.0)
    dec = (W - w[None, :]) @ pg
    l1t = l1 * jnp.sum(mask[None, :] * jnp.abs(W), axis=1)
    rv = jax.vmap(lambda wk: obj._reg_terms(wk)[0])(W)
    return W, dec, l1t, rv


@jax.jit
def _pg_norm(w, g, l1, mask):
    return jnp.linalg.norm(pseudo_gradient(w, g, l1, mask))


@jax.jit
def _l1_term(w, l1, mask):
    return l1 * jnp.sum(mask * jnp.abs(w))


_pseudo_gradient = jax.jit(pseudo_gradient)
# NOT donated: a checkpoint session may still hold the previous history's
# buffers for a snapshot it has not written yet
_push_history = jax.jit(_push)


# ------------------------------------------------------------- mesh backend
# Mesh-sharded streamed execution. Chunk programs run under shard_map with
# NO collective inside: partials come back STACKED (one block per device
# slot, leading axis sharded over the whole mesh), accumulate elementwise
# across chunks (still no communication), and the evaluation closes with
# ONE psum in `finish` / `psum_tree` — hierarchical on a hybrid
# replica×data mesh (ICI inside the slice, the (d,) vector across DCN once
# per evaluation).


def _squeeze0(tree):
    return jax.tree_util.tree_map(lambda x: jnp.squeeze(x, 0), tree)


class _MeshChunkOps:
    """Per-mesh jitted shard_map programs for the streamed chunk-partial
    evaluation (cached per mesh by `_mesh_ops`)."""

    def __init__(self, mesh):
        from photon_tpu.parallel.mesh import shard_map

        self.mesh = mesh
        axes = tuple(mesh.axis_names)
        self.axes = axes
        row, rep = P(axes), P()

        def ospec(obj):
            return jax.tree_util.tree_map(lambda _: rep, obj)

        def bspec(b):
            X = b.X
            if isinstance(X, ShardedBlockedEllRows):
                # the mesh blocked-ELL chunk: dense block row-sharded,
                # per-shard ELL/occurrence buckets one leading index per
                # device, permutation replicated — the same spec tree the
                # resident sharded solve uses (models.training).
                from photon_tpu.models.training import _hybrid_specs

                return _hybrid_specs(X, axes)
            xs = (SparseRows(row, row, X.n_features)
                  if isinstance(X, SparseRows) else row)
            return GLMBatch(xs, row, row, row)

        def lview(b):
            """The device-local view inside shard_map: a sharded
            blocked-ELL chunk squeezes its shard axis to a plain
            BlockedEllRows; everything else already IS local."""
            if isinstance(b.X, ShardedBlockedEllRows):
                return b._replace(X=b.X.local())
            return b

        def pspec(obj):
            # (loss_sum, gX, gsum-or-None) stacked one block per device
            return (row, row, row if obj.norm_shifts is not None else None)

        def stack(parts):
            return jax.tree_util.tree_map(lambda x: x[None], parts)

        def chunk_init(obj, w, b):
            def body(obj, w, b):
                z, parts = obj.chunk_value_grad_partials(w, lview(b))
                return z, stack(parts)

            return shard_map(body, mesh=mesh,
                             in_specs=(ospec(obj), rep, bspec(b)),
                             out_specs=(row, pspec(obj)))(obj, w, b)

        def chunk_grad(obj, z, b):
            def body(obj, z, b):
                return stack(obj.chunk_partials_at_margin(z, lview(b)))

            return shard_map(body, mesh=mesh,
                             in_specs=(ospec(obj), row, bspec(b)),
                             out_specs=pspec(obj))(obj, z, b)

        def chunk_dz_phi(obj, p, z, a, b):
            def body(obj, p, z, a, b):
                bl = lview(b)
                dz = obj.direction_margin(p, bl)
                wl, wd = obj.chunk_phi_partials(z, dz, a, bl.y, bl.weights)
                return dz, (wl[None], wd[None])

            return shard_map(body, mesh=mesh,
                             in_specs=(ospec(obj), rep, row, rep, bspec(b)),
                             out_specs=(row, (row, row)))(obj, p, z, a, b)

        @jax.jit
        def chunk_phi(obj, z, dz, a, y, wt):
            def body(obj, z, dz, a, y, wt):
                wl, wd = obj.chunk_phi_partials(z, dz, a, y, wt)
                return wl[None], wd[None]

            return shard_map(body, mesh=mesh,
                             in_specs=(ospec(obj), row, row, rep, row, row),
                             out_specs=(row, row))(obj, z, dz, a, y, wt)

        def chunk_value_many(obj, W, b):
            def body(obj, W, b):
                return obj.chunk_value_partials_many(W, lview(b))[None]

            return shard_map(body, mesh=mesh,
                             in_specs=(ospec(obj), rep, bspec(b)),
                             out_specs=row)(obj, W, b)

        # donated twins consume their feature-chunk argument (see the
        # module-level donation note) — picked by _MeshStream when the
        # ladder's chunks share no device buffers
        self.chunk_init_don = jax.jit(chunk_init, donate_argnums=(2,))
        self.chunk_grad_don = jax.jit(chunk_grad, donate_argnums=(2,))
        self.chunk_dz_phi_don = jax.jit(chunk_dz_phi, donate_argnums=(4,))
        self.chunk_value_many_don = jax.jit(chunk_value_many,
                                            donate_argnums=(2,))
        chunk_init = jax.jit(chunk_init)
        chunk_grad = jax.jit(chunk_grad)
        chunk_dz_phi = jax.jit(chunk_dz_phi)
        chunk_value_many = jax.jit(chunk_value_many)

        @jax.jit
        def finish(obj, w, parts):
            def body(obj, w, parts):
                # THE one collective of a streamed-mesh evaluation: value
                # and gradient partials ride a single (hierarchical) psum.
                total = lax.psum(_squeeze0(parts), axes)
                return obj.finish_value_grad(w, total)

            return shard_map(body, mesh=mesh,
                             in_specs=(ospec(obj), rep, pspec(obj)),
                             out_specs=(rep, rep))(obj, w, parts)

        @jax.jit
        def psum_tree(parts):
            def body(parts):
                return lax.psum(_squeeze0(parts), axes)

            specs = jax.tree_util.tree_map(lambda _: row, parts)
            outs = jax.tree_util.tree_map(lambda _: rep, parts)
            return shard_map(body, mesh=mesh,
                             in_specs=(specs,), out_specs=outs)(parts)

        self.chunk_init = chunk_init
        self.chunk_grad = chunk_grad
        self.chunk_dz_phi = chunk_dz_phi
        self.chunk_phi = chunk_phi
        self.chunk_value_many = chunk_value_many
        self.finish = finish
        self.psum_tree = psum_tree


_MESH_OPS_CACHE: dict = {}


def _mesh_ops(mesh) -> _MeshChunkOps:
    ops = _MESH_OPS_CACHE.get(mesh)
    if ops is None:
        ops = _MESH_OPS_CACHE[mesh] = _MeshChunkOps(mesh)
    return ops


class _SingleDeviceStream:
    """The single-chip execution regime: chunks upload whole, margin caches
    are (chunk_rows,) host numpy, partial totals are plain device scalars."""

    # attribution-ledger program-name prefix + the traceable chunk
    # programs behind each backend method (profiling.note_program
    # estimates their static FLOP/byte cost once per attached ledger)
    prog = "streamed."

    def __init__(self, data, prefetch: int = 2):
        self.data, self.prefetch = data, prefetch
        self.cost_fns = {"chunk_init": _chunk_init,
                         "chunk_grad": _chunk_grad_at_margin,
                         "chunk_dz_phi": _chunk_dz_phi,
                         "chunk_value_many": _chunk_value_many}
        # the persistent two-deep upload ring + donated chunk programs
        # (the upload/compute-overlap round — see DeviceChunkRing and the
        # module-level donation note)
        self.ring = data.device_ring(prefetch=prefetch)
        self.donate = _donatable(data.X.chunks[0])
        self._init = _chunk_init_don if self.donate else _chunk_init
        self._grad = (_chunk_grad_at_margin_don if self.donate
                      else _chunk_grad_at_margin)
        self._dz_phi = _chunk_dz_phi_don if self.donate else _chunk_dz_phi
        self._value_many = (_chunk_value_many_don if self.donate
                            else _chunk_value_many)

    def note(self, name, *args):
        """Static-cost registration (trace-only, once per attached
        ledger) for one chunk program, with the hot loop's own args."""
        if profiling.needs_note(self.prog + name):
            profiling.note_program(self.prog + name, self.cost_fns[name],
                                   args)

    def note_phi(self, obj, i, z, dz, a):
        """The margin-trial program's note (needs a live chunk's scalars;
        only prepared while a ledger wants it)."""
        if not profiling.needs_note(self.prog + "chunk_phi"):
            return
        b = self.data.chunk(i)
        profiling.note_program(self.prog + "chunk_phi", _chunk_phi,
                               (obj, z, dz, np.float32(a), b.y, b.weights))

    def iter_chunks(self):
        return self.ring.stream_pass()

    def close(self):
        self.ring.close()

    # Every chunk program's outputs go through `ring.consumed` BEFORE any
    # readback: the ring issues its next upload there, behind the program
    # just dispatched, and frees the chunk of the one before (a donated
    # hot block has no output to alias, so donation does not free it)

    def _consume(self, name, program, *args):
        """One chunk program: span ``stream.dispatch`` around its call
        ONLY, then the ring is told — its `stream.release` and
        `stream.upload` are this span's siblings, not its children."""
        with telemetry.span("stream.dispatch", program=name):
            out = program(*args)
        return self.ring.consumed(out)

    @staticmethod
    def _margins(z):
        with telemetry.span("stream.readback", what="margins"):
            return np.asarray(z)

    def chunk_init(self, obj, w, b):
        z, parts = self._consume("init", self._init, obj, w, b)
        return self._margins(z), parts

    def chunk_grad(self, obj, z, b):
        return self._consume("grad", self._grad, obj, z, b)

    def chunk_dz_phi(self, obj, p, z, a, b):
        dz, wlwd = self._consume("dz_phi", self._dz_phi, obj, p, z,
                                 np.float32(a), b)
        return self._margins(dz), wlwd

    def chunk_phi(self, obj, i, z, dz, a):
        b = self.data.chunk(i)
        return _chunk_phi(obj, z, dz, np.float32(a), b.y, b.weights)

    def chunk_value_many(self, obj, W, b):
        return self._consume("value_many", self._value_many, obj, W, b)

    def finish(self, obj, w, acc):
        return _finish(obj, w, acc)

    def totals(self, tree) -> tuple:
        return tuple(float(x) for x in tree)

    def values_total(self, acc) -> np.ndarray:
        return np.asarray(acc, np.float64)

    def result_w(self, w):
        return w


class _MeshStream:
    """Mesh-sharded streamed execution: every chunk row-shards over the
    whole mesh, chunk partials stay device-local (stacked one block per
    device slot), margin caches live on HOST in local-slot layout
    ((n_local_slots, s) numpy — `parallel.mesh.fetch_local_rows`), and each
    evaluation closes with the backend's single psum."""

    prog = "streamed_mesh."

    def __init__(self, data, mesh, prefetch: int = 2):
        self.data, self.mesh, self.prefetch = data, mesh, prefetch
        self.ops = _mesh_ops(mesh)
        self.cost_fns = {"chunk_init": self.ops.chunk_init,
                         "chunk_grad": self.ops.chunk_grad,
                         "chunk_dz_phi": self.ops.chunk_dz_phi,
                         "chunk_value_many": self.ops.chunk_value_many}
        # persistent ring (next-pass uploads overlap this pass's finish
        # psum + readback; the replicated ladder permutation uploads once
        # per solve) + donated chunk programs where chunks share nothing
        self.ring = data.device_ring(mesh=mesh, prefetch=prefetch)
        self.donate = _donatable(data.X.chunks[0])
        ops = self.ops
        self._init = ops.chunk_init_don if self.donate else ops.chunk_init
        self._grad = ops.chunk_grad_don if self.donate else ops.chunk_grad
        self._dz_phi = (ops.chunk_dz_phi_don if self.donate
                        else ops.chunk_dz_phi)
        self._value_many = (ops.chunk_value_many_don if self.donate
                            else ops.chunk_value_many)

    def note(self, name, *args):
        """Mesh face of `_SingleDeviceStream.note`: margin caches live
        host-side in LOCAL-SLOT layout, so the z-carrying programs trace
        with the re-sharded device array the real call would see."""
        if not profiling.needs_note(self.prog + name):
            return
        if name == "chunk_dz_phi":
            obj, p, z, a, b = args
            args = (obj, p, self._put(z), np.float32(a), b)
        elif name == "chunk_grad":
            obj, z, b = args
            args = (obj, self._put(z), b)
        profiling.note_program(self.prog + name, self.cost_fns[name], args)

    def note_phi(self, obj, i, z, dz, a):
        if not profiling.needs_note(self.prog + "chunk_phi"):
            return
        y, wt = self.data.chunk_scalars_sharded(i, self.mesh)
        profiling.note_program(
            self.prog + "chunk_phi", self.ops.chunk_phi,
            (obj, self._put(z), self._put(dz), np.float32(a), y, wt))

    def iter_chunks(self):
        return self.ring.stream_pass()

    def close(self):
        self.ring.close()

    def _fetch(self, arr):
        from photon_tpu.parallel.mesh import fetch_local_rows

        return fetch_local_rows(arr, self.mesh)

    def _put(self, local):
        from photon_tpu.parallel.mesh import shard_local_rows

        return shard_local_rows(local, self.mesh)

    def chunk_init(self, obj, w, b):
        z, parts = self._init(obj, w, b)
        return self._fetch(z), parts

    def chunk_grad(self, obj, z, b):
        return self._grad(obj, self._put(z), b)

    def chunk_dz_phi(self, obj, p, z, a, b):
        dz, wlwd = self._dz_phi(obj, p, self._put(z), np.float32(a), b)
        return self._fetch(dz), wlwd

    def chunk_phi(self, obj, i, z, dz, a):
        y, wt = self.data.chunk_scalars_sharded(i, self.mesh)
        return self.ops.chunk_phi(obj, self._put(z), self._put(dz),
                                  np.float32(a), y, wt)

    def chunk_value_many(self, obj, W, b):
        return self._value_many(obj, W, b)

    def finish(self, obj, w, acc):
        return self.ops.finish(obj, w, acc)

    def totals(self, tree) -> tuple:
        return tuple(float(x) for x in self.ops.psum_tree(tree))

    def values_total(self, acc) -> np.ndarray:
        return np.asarray(self.ops.psum_tree(acc), np.float64)

    def result_w(self, w):
        # hand back a host-backed (uncommitted) array: downstream scoring
        # and model assembly run on the default device, and a mesh-committed
        # w would poison every eager op it meets with a device mismatch
        return jnp.asarray(np.asarray(w))


def _backend(data, mesh, prefetch: int):
    c0 = data.X.chunks[0]
    if mesh is not None:
        if isinstance(c0, ShardedBlockedEllRows):
            n_dev = len(mesh.devices.reshape(-1))
            if c0.n_shards != n_dev:
                raise ValueError(
                    f"blocked-ELL chunk ladder was laid for "
                    f"{c0.n_shards} device shard(s) but the mesh has "
                    f"{n_dev}; rebuild with data.dataset."
                    f"chunk_blocked_ell(batch, chunk_rows, "
                    f"n_shards={n_dev})")
        elif getattr(data.X, "permuted", False):
            # single-device blocked-ELL chunks (n_shards=1) have no
            # row-sharded form — the MESH ladder is a different layout.
            raise ValueError(
                "this blocked-ELL chunk ladder was laid for ONE device "
                "per chunk and cannot row-shard over a mesh; rebuild it "
                "for the mesh with data.dataset.chunk_blocked_ell(batch, "
                f"chunk_rows, n_shards={len(mesh.devices.reshape(-1))}) "
                "— the pod-scale GAME fixed-effect regime — or stream "
                "SparseRows chunks, or drop mesh=")
        return _MeshStream(data, mesh, prefetch)
    if isinstance(c0, ShardedBlockedEllRows):
        raise ValueError(
            f"this blocked-ELL chunk ladder was laid for a "
            f"{c0.n_shards}-device mesh (chunk_blocked_ell(n_shards=...)); "
            "pass the mesh to the solve, or rebuild with n_shards=1 for "
            "the single-chip stream")
    return _SingleDeviceStream(data, prefetch)


def _check_streamable(obj, mesh) -> None:
    if obj.axis_name is not None:
        raise ValueError(
            "streamed solves own their collective: Objective.axis_name must "
            "be None (chunk partials are LOCAL sums; under a mesh the "
            "streamed machinery issues exactly one psum per evaluation)")
    if mesh is not None:
        import jax as _jax

        if not any(d.process_index == _jax.process_index()
                   for d in mesh.devices.reshape(-1)):
            raise ValueError(
                "streamed mesh solve: no device in the mesh is addressable "
                "from this process")


class _History:
    """Host-orchestrated circular (s, y) history: the resident solvers'
    `History` on the device, pushed by their `_push` (curvature gate, slot
    write and inner products in one program). The host mirrors ``count``
    only, for the first step's length."""

    def __init__(self, m: int, d: int, dtype=jnp.float32):
        self.h = empty_history(m, d, dtype)
        self.count = 0

    def push(self, s, y, v) -> None:
        """``v``: the vector the next direction is of (optim.lbfgs._push)."""
        self.h = _push_history(self.h, s, y, v)
        self.count = int(self.h.count)


# ---------------------------------------------------------- host line search
def _sign(x: float) -> float:
    return 0.0 if x == 0.0 else math.copysign(1.0, x)


def _cubic_min_host(a_lo, f_lo, d_lo, a_hi, f_hi, d_hi) -> float:
    """Scalar port of optim.linesearch._cubic_min (same safeguards)."""
    span = a_hi - a_lo
    d1 = d_lo + d_hi - 3.0 * (f_lo - f_hi) / (1.0 if span == 0.0 else -span)
    disc = d1 * d1 - d_lo * d_hi
    d2 = _sign(span) * math.sqrt(max(disc, 0.0))
    denom = d_hi - d_lo + 2.0 * d2
    a_c = a_hi - span * (d_hi + d2 - d1) / (1.0 if denom == 0.0 else denom)
    lo_m = a_lo + 0.1 * span
    hi_m = a_hi - 0.1 * span
    inside = ((lo_m <= a_c <= hi_m) if span > 0.0
              else (hi_m <= a_c <= lo_m))
    ok = disc >= 0.0 and denom != 0.0 and math.isfinite(a_c) and inside
    return a_c if ok else 0.5 * (a_lo + a_hi)


def _host_wolfe(phi, f0: float, dphi0: float, a_init: float,
                max_evals: int, first=None):
    """Host port of optim.linesearch.wolfe_line_search — the same
    bracket+zoom state machine, one streamed `phi` evaluation per step.
    `first` short-circuits the first evaluation with (f, dphi) already
    accumulated during the direction pass (the common accept-at-first-trial
    iteration then costs ZERO extra margin streams). Returns
    (alpha, f_alpha, ok, n_evals) with the resident solver's exact
    accept/fail semantics; ``n_evals`` is the trial count (the iteration
    stream's `trials` field)."""
    phase, i = 0, 0
    a, a_prev, f_prev, d_prev = a_init, 0.0, f0, dphi0
    a_lo, f_lo, d_lo = 0.0, f0, dphi0
    a_hi = f_hi = d_hi = math.inf
    a_star, f_star = 0.0, f0
    done = False

    def armijo(a_, f_):
        return f_ <= f0 + C1 * a_ * dphi0

    while not done and i < max_evals:
        f, d = first if (first is not None and i == 0) else phi(a)
        f, d = float(f), float(d)
        bad = math.isnan(f) or math.isinf(f)

        if phase == 0:  # bracketing (N&W Alg 3.5)
            to_zoom_hi = bad or not armijo(a, f) or (i > 0 and f >= f_prev)
            wolfe_ok = not to_zoom_hi and abs(d) <= -C2 * dphi0
            to_zoom_rev = (not to_zoom_hi and not wolfe_ok and d >= 0.0)
            expand = not (to_zoom_hi or wolfe_ok or to_zoom_rev)
            n_phase = 1 if (to_zoom_hi or to_zoom_rev) else 0
            n_lo = ((a_prev, f_prev, d_prev) if to_zoom_hi else (a, f, d))
            n_hi = ((a, f, d) if to_zoom_hi else (a_prev, f_prev, d_prev))
        else:  # zoom (Alg 3.6); `a` is the trial point inside [lo, hi]
            shrink_hi = bad or not armijo(a, f) or f >= f_lo
            wolfe_ok = not shrink_hi and abs(d) <= -C2 * dphi0
            flip = not shrink_hi and d * (a_hi - a_lo) >= 0.0
            expand, n_phase = False, 1
            n_lo = (a_lo, f_lo, d_lo) if shrink_hi else (a, f, d)
            n_hi = ((a, f, d) if shrink_hi
                    else ((a_lo, f_lo, d_lo) if flip else (a_hi, f_hi, d_hi)))

        done = wolfe_ok
        a_lo, f_lo, d_lo = n_lo
        a_hi, f_hi, d_hi = n_hi
        interp_a = _cubic_min_host(a_lo, f_lo, d_lo, a_hi, f_hi, d_hi)
        if not (math.isfinite(f_hi) and math.isfinite(d_hi)):
            interp_a = 0.5 * (a_lo + a_hi)
        next_a = 2.0 * a if (phase == 0 and expand) else interp_a

        if done or (armijo(a, f) and f < f_star and not bad):
            a_star, f_star = a, f
        i += 1
        a_prev, f_prev, d_prev = a, f, d
        a, phase = next_a, n_phase

    return a_star, f_star, done or a_star > 0.0, i


def _stop_host(tolerance, before, converged: bool, ok: bool) -> tuple:
    """`optim.config.stop_state` over the host loops' Python booleans."""
    c, ok = np.bool_(converged), np.bool_(ok)
    return tuple(bool(v) for v in stop_state(
        tolerance, tuple(np.bool_(v) for v in before), c, c | ~ok, ~ok & ~c))


def _convergence_host(ok, f_old, f_new, gnorm, g0norm, dphi0,
                      tolerance) -> bool:
    """Host mirror of optim.lbfgs._convergence (f32 noise floor)."""
    grad_conv = gnorm <= tolerance * max(1.0, g0norm)
    f_conv = ok and abs(f_old - f_new) <= tolerance * max(
        max(abs(f_old), abs(f_new)), 1e-12)
    noise = 4.0 * float(np.finfo(np.float32).eps) * max(abs(f_old), 1.0)
    precision_limited = (not ok) and abs(dphi0) <= noise
    return grad_conv or f_conv or precision_limited


def _pass_span(kind: str, n: int):
    """Host span ``stream.pass`` around ONE pass over the chunks, from the
    first chunk asked of the ring to the readback that closes the pass;
    ``kind`` says which: "init" (margins, value and gradient at the
    start), "dz" (the direction's margins, with the first trial),
    "gradient" (at cached margins), "refresh" (a gradient pass that
    re-anchors the margins on w), OWL-QN's "value_grad" and "ladder";
    ``n`` is the pass's number in its solve, so a chunk's spans under it
    (the ring's and the backend's, each with its ``chunk``) are one
    timeline (n, chunk)."""
    return telemetry.span("stream.pass", kind=kind, n=n)


def _totals_span():
    """Host span ``stream.readback`` around what closes a pass: the
    program that sums it up and the scalars read back from it."""
    return telemetry.span("stream.readback", what="totals")


def _host_step(part: str):
    """Host span ``solve.host_step`` around a stretch of the streamed
    L-BFGS loop BETWEEN two passes, link and device both waiting on the
    host: "direction" (two-loop and ray coefficients, before the dz
    pass), "linesearch" (the Wolfe search over cached margins, the step
    and the host margin chain, before the gradient pass), "update"
    (history push, convergence, bookkeeping, after it and after the
    first pass)."""
    return telemetry.span("solve.host_step", part=part)


def _eval_tick(ck, n: int = 1) -> None:
    """One objective evaluation closed: a fault-injection site (the
    streamed regime's 'evaluation' kill point) + checkpoint cadence
    accounting. Session-less cost: one global load and one branch."""
    _ckpt.kill_point("evaluation")
    if ck is not None:
        ck.note_evaluations(n)


# ------------------------------------------------- checkpoint (de)hydration
# The streamed solvers are HOST loops, so their full state is host-visible
# at every iteration boundary — the crash-consistency cut. Snapshots are
# exact: every f32 array round-trips bit-identically through the .npy
# store, so a restored run replays the remaining iterations bit-identically
# on the same topology (tests/test_checkpoint.py pins this per fault site).


def _pack_stream_state(kind, d, n_chunks, chunk_rows, max_iters, it, f,
                       g0norm, hist, ghist, converged, failed, done, w, g,
                       hist_st, trials, extra=None) -> dict:
    st = {
        "kind": kind, "d": int(d), "n_chunks": int(n_chunks),
        "chunk_rows": int(chunk_rows), "max_iters": int(max_iters),
        "it": int(it), "trials": int(trials), "f": float(f),
        "g0norm": float(g0norm),
        "hist": np.asarray(hist), "ghist": np.asarray(ghist),
        "converged": bool(converged), "failed": bool(failed),
        "done": bool(done), "w": w, "g": g,
        "S": hist_st.h.S, "Y": hist_st.h.Y,
        "h_sy": hist_st.h.sy, "h_yy": hist_st.h.yy,
        "h_sv": hist_st.h.sv, "h_yv": hist_st.h.yv,
        "h_idx": int(hist_st.h.idx), "h_count": int(hist_st.h.count),
    }
    if extra:
        st.update(extra)
    return st


def _validate_stream_state(st: dict, kind: str, d: int, n_chunks: int,
                           chunk_rows: int, max_iters: int) -> None:
    from photon_tpu.checkpoint import SnapshotStateError

    got = (st.get("kind"), int(st.get("d", -1)), int(st.get("n_chunks", -1)),
           int(st.get("chunk_rows", -1)), int(st.get("max_iters", -1)))
    want = (kind, d, n_chunks, chunk_rows, max_iters)
    if got != want:
        raise SnapshotStateError(
            f"streamed-solver snapshot does not fit this solve: snapshot "
            f"(kind, d, n_chunks, chunk_rows, max_iters)={got} vs resuming "
            f"program {want}. Resume must re-run the same problem with the "
            "same chunking and iteration budget (the mesh shape MAY "
            "differ; margin caches re-shard).")


def _restore_history(st: dict, history: int, d: int, v) -> _History:
    """``v``: the vector the resumed solve's next direction is of (the
    snapshot's gradient; OWL-QN's pseudo-gradient at its iterate)."""
    from photon_tpu.checkpoint import SnapshotStateError

    hs = _History(history, d)
    S, Y = np.asarray(st["S"]), np.asarray(st["Y"])
    idx, count = int(st["h_idx"]), int(st["h_count"])
    if "h_sv" not in st and S.shape == (history, d):
        # written before the history carried its products: bare (m, d)
        # rings, from which every product is recomputed — resumes the
        # same solve, to f32 reduction noise rather than to the bit
        hs.h = history_from_slots(jnp.asarray(S), jnp.asarray(Y), idx,
                                  count, v)
    elif S.shape != hs.h.S.shape:
        raise SnapshotStateError(
            f"curvature history shape {S.shape} in snapshot vs "
            f"{hs.h.S.shape} in the resuming solve (history {history}, "
            f"{d} features)")
    else:
        hs.h = History(
            S=jnp.asarray(S), Y=jnp.asarray(Y),
            **{k: jnp.asarray(np.asarray(st["h_" + k]))
               for k in ("sy", "yy", "sv", "yv")},
            idx=jnp.asarray(idx, jnp.int32),
            count=jnp.asarray(count, jnp.int32))
    hs.count = count
    return hs


def _restore_z_cache(st: dict, data, mesh) -> list:
    """Per-chunk cached margins out of a snapshot, re-laid for the
    CURRENT backend: slot-keyed entries (schema v2 — written per process,
    merged across every `p<k>_` prefix by the store) or the v1 packed
    global vector, re-sliced to single-device flat chunks or the mesh's
    local-slot stacks (a mesh-8 snapshot restores onto mesh-4 or one
    chip, a 2-process snapshot onto 1 or 4 processes; pad rows carry
    weight 0, so re-padding is exact)."""
    pad = (data.mesh_chunk_rows(mesh) if mesh is not None
           else data.chunk_rows)
    return [_ckpt.unpack_row_slots(st, f"z{i}", mesh, pad,
                                   data.chunk_rows)
            for i in range(data.n_chunks)]


def _result(w, value, gnorm, it, converged, failed, hist, ghist,
            trials) -> OptResult:
    return OptResult(
        w=w, value=jnp.asarray(np.float32(value)),
        grad_norm=jnp.asarray(np.float32(gnorm)),
        iterations=jnp.asarray(np.int32(it)),
        converged=jnp.asarray(bool(converged)),
        failed=jnp.asarray(bool(failed)),
        loss_history=jnp.asarray(hist),
        grad_norm_history=jnp.asarray(ghist),
        evaluations=jnp.asarray(np.int32(trials)),
    )


# --------------------------------------------------------- streamed L-BFGS
def minimize_lbfgs_streamed(
    obj,  # ops.objective.Objective (axis_name must be None)
    data,  # data.dataset.ChunkedBatch
    w0,
    max_iters: int = 100,
    tolerance: float = 1e-7,
    history: int = 10,
    max_ls_evals: int = 12,
    mesh=None,
    prefetch=2,
) -> OptResult:
    """L-BFGS whose value+gradient accumulate over streamed device chunks —
    the treeAggregate-per-iteration execution regime, same math and same
    convergence criteria as `optim.lbfgs.minimize_lbfgs_margin`. With
    ``mesh=``, chunks row-shard over every mesh device and each evaluation
    closes with one hierarchical psum (see the module docstring).

    ``prefetch`` is an int window or a stall-driven controller
    (`data.ingest_plane.AdaptivePrefetch`) — the window then widens
    across passes while chunk uploads measurably stall, up to the
    controller's byte budget; depth never changes results.

    The host driver loop emits telemetry for free: one `iteration` event
    per solver iteration (loss/grad_norm/step/trials — the live face of
    `OptResult.loss_history`), plus feature-stream / evaluation /
    line-search / margin-cache counters (photon_tpu.telemetry; no-ops
    without an attached Run)."""
    _check_streamable(obj, mesh)
    be = _backend(data, mesh, prefetch)
    with telemetry.span("solve.lbfgs_streamed", mesh=mesh is not None,
                        n_chunks=data.n_chunks):
        try:
            return _lbfgs_streamed(obj, data, w0, max_iters, tolerance,
                                   history, max_ls_evals, mesh, be)
        finally:
            # the last pass primed chunks for one that never comes; the
            # wait for them is the solve's (`stream.release` under it)
            be.close()


def _pack_lbfgs_state(d, n_chunks, data, mesh, max_iters, it, f, g0norm,
                      hist, ghist, converged, failed, done, w, g, hist_st,
                      trials, z_cache, z_gen) -> dict:
    extra: dict = {}
    for i in range(n_chunks):
        extra.update(_ckpt.pack_row_slots(z_cache[i], mesh,
                                          data.chunk_rows, prefix=f"z{i}"))
    extra["z_gen"] = int(z_gen)
    return _pack_stream_state("lbfgs_streamed", d, n_chunks,
                              data.chunk_rows, max_iters, it, f, g0norm,
                              hist, ghist, converged, failed, done, w, g,
                              hist_st, trials, extra)


def _lbfgs_streamed(obj, data, w0, max_iters, tolerance, history,
                    max_ls_evals, mesh, be) -> OptResult:
    n_chunks = data.n_chunks
    d = int(jnp.asarray(w0).shape[0])
    ck = _ckpt.current()
    st = ck.restore("lbfgs_streamed") if ck is not None else None
    z_gen = 0
    passes = itertools.count()  # `stream.pass`'s ordinal in this solve
    if st is not None:
        # ---- resume: the full iteration-boundary state rehydrates and
        # the initial pass is skipped (margins come from the snapshot).
        _validate_stream_state(st, "lbfgs_streamed", d, n_chunks,
                               data.chunk_rows, max_iters)
        w = jnp.asarray(np.asarray(st["w"]), jnp.float32)
        g = jnp.asarray(np.asarray(st["g"]), jnp.float32)
        if mesh is not None:
            from photon_tpu.parallel.mesh import replicated

            w = jax.device_put(w, replicated(mesh))
            g = jax.device_put(g, replicated(mesh))
        hist_st = _restore_history(st, history, d, g)
        z_cache = _restore_z_cache(st, data, mesh)
        f, g0norm = float(st["f"]), float(st["g0norm"])
        hist = np.array(st["hist"], np.float32)
        ghist = np.array(st["ghist"], np.float32)
        it, trials = int(st["it"]), int(st.get("trials", 0))
        converged, failed = bool(st["converged"]), bool(st["failed"])
        done = bool(st["done"])
        z_gen = int(st.get("z_gen", 0))
        telemetry.count("checkpoint.solver_restores")
    else:
        w = jnp.asarray(w0, jnp.float32)
        if mesh is not None:
            from photon_tpu.parallel.mesh import replicated

            # solver state lives mesh-replicated so every derived array
            # shares one device assignment (mixing mesh- and single-
            # device-committed operands is an error in eager ops)
            w = jax.device_put(w, replicated(mesh))

        hist_st = _History(history, d)

        # ---- initial pass: margins cached per chunk, (f, g) accumulated
        z_cache = [None] * n_chunks
        acc = None
        with _pass_span("init", next(passes)), profiling.measure(
                be.prog + "chunk_init", "lbfgs/init", calls=n_chunks):
            for i, b in be.iter_chunks():
                be.note("chunk_init", obj, w, b)
                z_cache[i], parts = be.chunk_init(obj, w, b)
                acc = parts if acc is None else _acc(acc, parts)
            with _totals_span():
                f_dev, g = be.finish(obj, w, acc)
                f = float(f_dev)  # the host readback closes the measured
                # pass
        with _host_step("update"):
            g0norm = float(jnp.linalg.norm(g))
            telemetry.count("solver.feature_streams")
            telemetry.count("solver.evaluations")
            _eval_tick(ck)
            telemetry.iteration("lbfgs_streamed", 0, f, grad_norm=g0norm)

            hist = np.full(max_iters + 1, np.nan, np.float32)
            ghist = np.full(max_iters + 1, np.nan, np.float32)
            hist[0], ghist[0] = f, g0norm

            it, trials, converged, failed = 0, 0, g0norm <= 1e-14, False
            done = converged
            if ck is not None:
                # the it=0 cut: resuming from here is provably == cold
                # start
                ck.update("lbfgs_streamed", _pack_lbfgs_state(
                    d, n_chunks, data, mesh, max_iters, it, f, g0norm,
                    hist, ghist, converged, failed, done, w, g, hist_st,
                    trials, z_cache, z_gen))
                ck.maybe_snapshot()
    dz_cache: list = [None] * n_chunks
    # Every stretch of the loop between two passes lies under a
    # `solve.host_step` span: with the passes' own spans the solve's wall
    # is accounted for, host turn by host turn
    while not done and it < max_iters:
        with _host_step("direction"):
            p, dphi0_dev, pnorm = _lbfgs_direction(g, hist_st.h)
            dphi0 = float(dphi0_dev)
            a_init = (1.0 if hist_st.count > 0
                      else 1.0 / max(float(pnorm), 1.0))
            c0, c1r, c2r = (float(v) for v in _ray_coeffs(obj, w, p))

        def reg_ray(a):  # exact quadratic reg along the ray (phi_at_ray)
            return c0 + a * (c1r + 0.5 * a * c2r), c1r + a * c2r

        # ---- direction pass (feature stream 1 of 2): dz per chunk, with
        # the FIRST Wolfe trial's φ(a_init) partials riding along.
        phis = None
        with _pass_span("dz", next(passes)), profiling.measure(
                be.prog + "chunk_dz_phi", "lbfgs/direction",
                calls=n_chunks):
            for i, b in be.iter_chunks():
                be.note("chunk_dz_phi", obj, p, z_cache[i],
                        np.float32(a_init), b)
                dz_cache[i], wlwd = be.chunk_dz_phi(obj, p, z_cache[i],
                                                    a_init, b)
                phis = wlwd if phis is None else _acc(phis, wlwd)
            with _totals_span():
                wl0, wd0 = be.totals(phis)

        def phi(a):
            """Streamed trial: 16 bytes/row of cached margins, no X."""
            telemetry.count("solver.evaluations")
            telemetry.count("solver.margin_cache.hits")
            phis = None
            with profiling.measure(be.prog + "chunk_phi",
                                   "lbfgs/linesearch", calls=n_chunks):
                be.note_phi(obj, 0, z_cache[0], dz_cache[0], a)
                for i in range(n_chunks):
                    wlwd = be.chunk_phi(obj, i, z_cache[i], dz_cache[i], a)
                    phis = wlwd if phis is None else _acc(phis, wlwd)
                wl, wd = be.totals(phis)
            _eval_tick(ck)
            rv, rd = reg_ray(a)
            return wl + rv, wd + rd

        with _host_step("linesearch"):
            rv, rd = reg_ray(a_init)
            first_eval = (wl0 + rv, wd0 + rd)
            # feature stream 1 of 2; its piggybacked φ(a_init) is both an
            # evaluation and the line search's first trial
            telemetry.count("solver.feature_streams")
            telemetry.count("solver.evaluations")
            _eval_tick(ck)
            alpha, f_star, ok, n_trials = _host_wolfe(
                phi, f, dphi0, a_init, max_ls_evals, first=first_eval)
            telemetry.count("solver.linesearch_trials", n_trials)
            trials += n_trials
            if ok:
                w_new = _axpy(w, np.float32(alpha), p)
                a32 = np.float32(alpha)
                for i in range(n_chunks):  # host margin chain: z += α·dz
                    z_cache[i] = z_cache[i] + a32 * dz_cache[i]
                refresh = (max_iters >= _Z_REFRESH
                           and (it + 1) % _Z_REFRESH == 0)
                telemetry.count("solver.feature_streams")
                telemetry.count("solver.evaluations")
                if refresh:
                    telemetry.count("solver.margin_cache.refreshes")
                    z_gen += 1

        if ok:
            # ---- gradient pass (feature stream 2 of 2)
            acc = None
            grad_prog = be.prog + ("chunk_init" if refresh else "chunk_grad")
            with _pass_span("refresh" if refresh else "gradient",
                            next(passes)), \
                    profiling.measure(grad_prog, "lbfgs/gradient",
                                      calls=n_chunks):
                for i, b in be.iter_chunks():
                    if refresh:  # re-anchor chained margin on w (f32 drift)
                        z_cache[i], parts = be.chunk_init(obj, w_new, b)
                    else:
                        be.note("chunk_grad", obj, z_cache[i], b)
                        parts = be.chunk_grad(obj, z_cache[i], b)
                    acc = parts if acc is None else _acc(acc, parts)
                _, g_new = be.finish(obj, w_new, acc)

        with _host_step("update"):
            if ok:
                _eval_tick(ck)
                f_new = f_star  # the accepted trial's value, as the
                # resident margin solver uses it
                hist_st.push(w_new - w, g_new - g, g_new)
            else:
                w_new, g_new, f_new = w, g, f

            gnorm = float(jnp.linalg.norm(g_new))
            now = _convergence_host(ok, f, f_new, gnorm, g0norm, dphi0,
                                    tolerance)
            done, converged, failed = _stop_host(
                tolerance, (done, converged, failed), now, ok)
            it += 1
            hist[it], ghist[it] = f_new, gnorm
            telemetry.count("solver.iterations")
            telemetry.iteration("lbfgs_streamed", it, f_new,
                                grad_norm=gnorm,
                                step=(alpha if ok else 0.0),
                                trials=n_trials)
            w, g, f = w_new, g_new, f_new
            if ck is not None:
                # iteration boundary = the crash-consistency cut
                ck.update("lbfgs_streamed", _pack_lbfgs_state(
                    d, n_chunks, data, mesh, max_iters, it, f, g0norm,
                    hist, ghist, converged, failed, done, w, g, hist_st,
                    trials, z_cache, z_gen))
                ck.maybe_snapshot()

    return _result(be.result_w(w), f, float(jnp.linalg.norm(g)), it,
                   converged, failed, hist, ghist, trials)


# --------------------------------------------------------- streamed OWL-QN
def minimize_owlqn_streamed(
    obj,
    data,
    w0,
    l1_weight: float,
    max_iters: int = 100,
    tolerance: float = 1e-7,
    history: int = 10,
    max_ls_evals: int = 20,
    reg_mask=None,
    ladder_lanes: int = 8,
    mesh=None,
    prefetch=2,
) -> OptResult:
    """OWL-QN over streamed chunks (``prefetch``: int window or an
    `data.ingest_plane.AdaptivePrefetch` controller, as in the streamed
    L-BFGS). The projected backtracking ladder is
    evaluated `ladder_lanes` candidates per chunk stream (selecting the
    first passing rung == the resident solver's sequential halving, rung by
    rung), so the common iteration costs two feature streams: the ladder
    pass and the accepted point's gradient pass. With ``mesh=``, chunks
    row-shard over every mesh device; each ladder block and each gradient
    pass still closes with one psum (see the module docstring).

    Telemetry mirrors the streamed L-BFGS: live `iteration` events plus
    feature-stream / evaluation / ladder-trial counters from the host
    driver loop (no-ops without an attached Run)."""
    _check_streamable(obj, mesh)
    be = _backend(data, mesh, prefetch)
    with telemetry.span("solve.owlqn_streamed", mesh=mesh is not None,
                        n_chunks=data.n_chunks):
        try:
            return _owlqn_streamed(obj, data, w0, l1_weight, max_iters,
                                   tolerance, history, max_ls_evals,
                                   reg_mask, ladder_lanes, mesh, be)
        finally:
            be.close()


def _pack_owlqn_state(d, n_chunks, data, max_iters, it, f, F, pg0norm,
                      hist, ghist, converged, failed, done, w, g,
                      hist_st, trials) -> dict:
    return _pack_stream_state("owlqn_streamed", d, n_chunks,
                              data.chunk_rows, max_iters, it, f, pg0norm,
                              hist, ghist, converged, failed, done, w, g,
                              hist_st, trials, {"F": float(F)})


def _owlqn_streamed(obj, data, w0, l1_weight, max_iters, tolerance,
                    history, max_ls_evals, reg_mask, ladder_lanes, mesh,
                    be) -> OptResult:
    n_chunks = data.n_chunks
    d = int(jnp.asarray(w0).shape[0])
    l1 = np.float32(l1_weight)
    mask = (jnp.ones((d,), jnp.float32) if reg_mask is None
            else jnp.asarray(reg_mask, jnp.float32))
    c1 = 1e-4  # optim.owlqn's Armijo constant
    ck = _ckpt.current()
    st = ck.restore("owlqn_streamed") if ck is not None else None
    passes = itertools.count()  # `stream.pass`'s ordinal in this solve

    def value_grad_pass(w_at):
        telemetry.count("solver.feature_streams")
        telemetry.count("solver.evaluations")
        acc = None
        with _pass_span("value_grad", next(passes)), profiling.measure(
                be.prog + "chunk_init", "owlqn/value_grad",
                calls=n_chunks):
            for i, b in be.iter_chunks():
                be.note("chunk_init", obj, w_at, b)
                _, parts = be.chunk_init(obj, w_at, b)
                acc = parts if acc is None else _acc(acc, parts)
            with _totals_span():
                f_dev, g_at = be.finish(obj, w_at, acc)
                f_host = float(f_dev)  # readback closes the measured pass
        _eval_tick(ck)
        return f_host, g_at

    if st is not None:
        # ---- resume: OWL-QN keeps no margin cache across iterations, so
        # the full iteration-boundary state is iterate+history+scalars.
        _validate_stream_state(st, "owlqn_streamed", d, n_chunks,
                               data.chunk_rows, max_iters)
        w = jnp.asarray(np.asarray(st["w"]), jnp.float32)
        g = jnp.asarray(np.asarray(st["g"]), jnp.float32)
        if mesh is not None:
            from photon_tpu.parallel.mesh import replicated

            w = jax.device_put(w, replicated(mesh))
            g = jax.device_put(g, replicated(mesh))
        hist_st = _restore_history(st, history, d,
                                   _pseudo_gradient(w, g, l1, mask))
        f, F = float(st["f"]), float(st["F"])
        pg0norm = float(st["g0norm"])
        hist = np.array(st["hist"], np.float32)
        ghist = np.array(st["ghist"], np.float32)
        it, trials = int(st["it"]), int(st.get("trials", 0))
        converged, failed = bool(st["converged"]), bool(st["failed"])
        done = bool(st["done"])
        telemetry.count("checkpoint.solver_restores")
    else:
        w = jnp.asarray(w0, jnp.float32)
        if mesh is not None:
            from photon_tpu.parallel.mesh import replicated

            w = jax.device_put(w, replicated(mesh))
        hist_st = _History(history, d)

        f, g = value_grad_pass(w)
        F = f + float(_l1_term(w, l1, mask))
        pg0norm = float(_pg_norm(w, g, l1, mask))
        telemetry.iteration("owlqn_streamed", 0, F, grad_norm=pg0norm)

        hist = np.full(max_iters + 1, np.nan, np.float32)
        ghist = np.full(max_iters + 1, np.nan, np.float32)
        hist[0], ghist[0] = F, pg0norm

        it, trials, converged, failed = 0, 0, pg0norm <= 1e-14, False
        done = converged
        if ck is not None:
            ck.update("owlqn_streamed", _pack_owlqn_state(
                d, n_chunks, data, max_iters, it, f, F, pg0norm, hist,
                ghist, converged, failed, done, w, g, hist_st, trials))
            ck.maybe_snapshot()
    while not done and it < max_iters:
        p, dphi0_dev, xi, pg, pnorm = _owlqn_direction(
            w, g, l1, mask, hist_st.h)
        dphi0 = float(dphi0_dev)
        a0 = 1.0 if hist_st.count > 0 else 1.0 / max(float(pnorm), 1.0)

        # ---- ladder line search: blocks of `ladder_lanes` rungs, each
        # block priced by ONE chunk stream (vmapped candidate margins).
        ok, w_new = False, None
        evals = 0
        while evals < max_ls_evals and not ok:
            K = min(ladder_lanes, max_ls_evals - evals)
            alphas = (a0 * 0.5 ** np.arange(evals, evals + K)).astype(
                np.float32)
            W, dec, l1t, rv = _owlqn_candidates(obj, w, p, xi,
                                                alphas, pg, l1, mask)
            # one feature stream prices K ladder rungs at once
            telemetry.count("solver.feature_streams")
            telemetry.count("solver.evaluations", K)
            telemetry.count("solver.linesearch_trials", K)
            acc = None
            with _pass_span("ladder", next(passes)), profiling.measure(
                    be.prog + "chunk_value_many", "owlqn/ladder",
                    calls=n_chunks):
                for _, b in be.iter_chunks():
                    be.note("chunk_value_many", obj, W, b)
                    part = be.chunk_value_many(obj, W, b)
                    acc = part if acc is None else _acc(acc, part)
                with _totals_span():  # sync: closes the pass
                    vals_total = be.values_total(acc)
            _eval_tick(ck, K)
            F_cand = (vals_total + np.asarray(rv, np.float64)
                      + np.asarray(l1t, np.float64))
            dec_np = np.asarray(dec, np.float64)
            for k in range(K):  # first passing rung == sequential halving
                if (np.isfinite(F_cand[k]) and dec_np[k] < 0.0
                        and F_cand[k] <= F + c1 * dec_np[k]):
                    ok, w_new = True, W[k]
                    break
            evals += K
        trials += evals

        if ok:
            f_new, g_new = value_grad_pass(w_new)  # gradient stream
            F_new = f_new + float(_l1_term(w_new, l1, mask))
            # smooth-gradient history; its products are with the next
            # direction's vector, the pseudo-gradient at w_new
            hist_st.push(w_new - w, g_new - g,
                         _pseudo_gradient(w_new, g_new, l1, mask))
        else:
            w_new, g_new, f_new, F_new = w, g, f, F

        pgnorm = float(_pg_norm(w_new, g_new, l1, mask))
        grad_conv = pgnorm <= tolerance * max(1.0, pg0norm)
        f_conv = ok and abs(F - F_new) <= tolerance * max(
            max(abs(F), abs(F_new)), 1e-12)
        noise = 4.0 * float(np.finfo(np.float32).eps) * max(abs(F), 1.0)
        precision_limited = (not ok) and abs(dphi0) <= noise
        done, converged, failed = _stop_host(
            tolerance, (done, converged, failed),
            grad_conv or f_conv or precision_limited, ok)
        it += 1
        hist[it], ghist[it] = F_new, pgnorm
        telemetry.count("solver.iterations")
        telemetry.iteration("owlqn_streamed", it, F_new, grad_norm=pgnorm,
                            trials=evals)
        w, g, f, F = w_new, g_new, f_new, F_new
        if ck is not None:
            ck.update("owlqn_streamed", _pack_owlqn_state(
                d, n_chunks, data, max_iters, it, f, F, pg0norm, hist,
                ghist, converged, failed, done, w, g, hist_st, trials))
            ck.maybe_snapshot()

    return _result(be.result_w(w), F, float(_pg_norm(w, g, l1, mask)), it,
                   converged, failed, hist, ghist, trials)


# ----------------------------------------------------------------- contracts
# The module docstring's communication law, as enforced static analysis
# (photon_tpu/analysis; tests/test_streamed_mesh.py pins the same facts
# dynamically): chunk-partial programs are communication-FREE — a psum
# inside one would multiply the per-evaluation collective by n_chunks —
# and an evaluation (or a line-search trial's totals) closes with exactly
# ONE hierarchical psum.
from photon_tpu.analysis.contracts import register_contract  # noqa: E402
from photon_tpu.analysis.walker import SCATTER_PRIMITIVES  # noqa: E402


def _contract_problem(mesh=None, d=6):
    """(obj, w, batch) with rows divisible by the mesh (trace-only; zeros
    are fine — contracts are shape/structure facts, not value facts)."""
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.ops.objective import Objective

    n = 16 * (int(mesh.devices.size) if mesh is not None else 1)
    batch = GLMBatch(X=jnp.zeros((n, d), jnp.float32),
                     y=jnp.zeros((n,), jnp.float32),
                     weights=jnp.ones((n,), jnp.float32),
                     offsets=jnp.zeros((n,), jnp.float32))
    # l2 as np.float32 (make_objective's canon): a Python-float leaf is
    # weak-typed and the retrace-hazard rule rejects it.
    obj = Objective(task=TaskType.LOGISTIC_REGRESSION, l2=np.float32(0.4))
    return obj, jnp.zeros((d,), jnp.float32), batch


@register_contract(
    name="streamed_chunk_init",
    description="single-chip streamed chunk-partial program (_chunk_init): "
                "margins + (loss, grad) partials, LOCAL sums only",
    collectives={}, tags=("streamed",))
def _contract_streamed_chunk_init():
    obj, w, batch = _contract_problem()
    return (lambda o, wv, b: _chunk_init(o, wv, b)), (obj, w, batch)


@register_contract(
    name="streamed_mesh_chunk_init",
    description="mesh-streamed chunk-partial program under shard_map: "
                "partials stay device-local, ZERO collectives per chunk",
    collectives={}, tags=("mesh-streamed",))
def _contract_streamed_mesh_chunk_init():
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    ops = _mesh_ops(mesh)
    obj, w, batch = _contract_problem(mesh)
    return (lambda o, wv, b: ops.chunk_init(o, wv, b)), (obj, w, batch)


@register_contract(
    name="streamed_mesh_finish",
    description="the evaluation close (_MeshChunkOps.finish): value and "
                "gradient partials ride ONE hierarchical psum — the whole "
                "evaluation's only collective",
    collectives={"psum": 1}, tags=("mesh-streamed",))
def _contract_streamed_mesh_finish():
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    ops = _mesh_ops(mesh)
    obj, w, _ = _contract_problem(mesh, d=6)
    n_slots = int(mesh.devices.size)
    parts = (jnp.zeros((n_slots,), jnp.float32),
             jnp.zeros((n_slots, 6), jnp.float32), None)
    return (lambda o, wv, p: ops.finish(o, wv, p)), (obj, w, parts)


@register_contract(
    name="streamed_mesh_blocked_ell_chunk_partials",
    description="a mesh blocked-ELL streamed chunk's partial program "
                "(chunk_blocked_ell(n_shards=D) under _MeshChunkOps): "
                "each device's ELL/occurrence buckets stay local — ZERO "
                "collectives per chunk, no scatters of any kind, every "
                "sparse dot/einsum accumulating f32 from bf16 storage",
    collectives={}, forbid=SCATTER_PRIMITIVES, require_f32_accum=True,
    tags=("mesh-streamed", "sparse", "game"))
def _contract_streamed_mesh_blocked_ell_chunk_partials():
    from photon_tpu.data.dataset import (cast_features, make_batch,
                                         shard_blocked_ell_batch)
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    ops = _mesh_ops(mesh)
    n_sh = int(mesh.devices.size)
    d, k = 96, 4
    rng = np.random.default_rng(0)
    n = 16 * n_sh
    sp = SparseRows(rng.integers(0, d, size=(n, k)).astype(np.int32),
                    rng.normal(size=(n, k)).astype(np.float32), d)
    batch = cast_features(shard_blocked_ell_batch(
        make_batch(sp, (rng.uniform(size=n) < 0.5).astype(np.float32)),
        n_sh, d_dense=16))
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.ops.objective import Objective

    obj = Objective(task=TaskType.LOGISTIC_REGRESSION, l2=np.float32(0.4))
    return (lambda o, wv, b: ops.chunk_init(o, wv, b)), \
        (obj, jnp.zeros((d,), jnp.float32), batch)


@register_contract(
    name="mesh_stream_donated_no_retrace",
    description="the donated double-buffer upload ring is signature-"
                "stable: rotating the DeviceChunkRing across passes "
                "(wraparound included) dispatches the chunk-partial "
                "program with ONE argument signature — the builder "
                "drains two full passes through TraceSignatureLog and "
                "raises on divergence or weak-type drift, so donation + "
                "ring rotation never retrace — and the program itself "
                "stays communication-free",
    collectives={}, tags=("streamed",))
def _contract_donated_ring_no_retrace():
    from photon_tpu.analysis.rules import TraceSignatureLog
    from photon_tpu.data.dataset import chunk_batch

    obj, w, batch = _contract_problem(d=6)
    cb = chunk_batch(batch, chunk_rows=8)  # 16 rows -> 2 chunks
    ring = cb.device_ring(prefetch=2)
    log = TraceSignatureLog()
    first = None
    for _ in range(2):  # two passes: the ring wraps across the boundary
        for i, b in ring.stream_pass():
            log.record("streamed.chunk_init", (obj, w, b))
            if first is None:
                first = b
    sigs = log.signatures("streamed.chunk_init")
    if len(sigs) != 1:
        raise AssertionError(
            f"donated ring dispatch drifted: {len(sigs)} distinct "
            "chunk-program signatures across ring rotations (expected 1)")
    if log.hazards():
        raise AssertionError(
            f"donated ring weak-type drift: {log.hazards()}")
    return _chunk_init_fn, (obj, w, first)


@register_contract(
    name="streamed_mesh_trial_totals",
    description="a line-search trial's (phi, phi') totals (psum_tree): "
                "trials never multiply the collective count — ONE psum",
    collectives={"psum": 1}, tags=("mesh-streamed",))
def _contract_streamed_mesh_trial_totals():
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    ops = _mesh_ops(mesh)
    n_slots = int(mesh.devices.size)
    parts = (jnp.zeros((n_slots,), jnp.float32),
             jnp.zeros((n_slots,), jnp.float32))
    return (lambda p: ops.psum_tree(p)), (parts,)
