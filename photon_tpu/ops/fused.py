"""Pallas TPU kernel: fused GLM objective value + gradient in ONE pass over X.

The jnp objective (ops/objective.py) computes z = Xw then g = Xᵀr as two
separate contractions, so X (the only large operand) is read from HBM twice
per solver evaluation. This kernel streams X through VMEM once per
evaluation: for each row chunk it computes the margin on the MXU, applies the
per-example loss/derivative on the VPU while the chunk is still resident, and
accumulates both the weighted loss and the gradient contribution Xᵀr into
VMEM accumulators — halving HBM traffic on the path that dominates GLM
training (reference hot loop: DistributedGLMLossFunction.calculate +
Breeze LBFGS iterations; here it is one `pallas_call` per evaluation inside
the jitted solver `while_loop`).

With bf16 feature storage (data.dataset.cast_features) both contractions run
with bf16 operands and f32 accumulation (`preferred_element_type`), halving
HBM traffic again.

Layout: per-example vectors (y, weight, offset) ride as one (8, n) f32 array
(sublane-padded to the f32 tile height so chunk DMAs slice only the lane
dim); margins/cotangents are (1, rows) row vectors and the gradient a
(1, d) row vector, so no in-kernel transposes are needed.

Two lowerings of the same math:
- compiled TPU path (compiles for the v5e at (524288, 256) f32 and
  (2^21, 1024) bf16 — tests/test_chip_compile.py): grid=1, X stays in HBM (`memory_space=ANY`) and the
  kernel double-buffers row chunks HBM→VMEM with explicit async DMAs,
  overlapping the next chunk's copy with the current chunk's compute. (The
  obvious alternative — a 1-D grid over row tiles with auto-pipelining —
  lowers to Mosaic in O(grid²) Python time in this JAX version, minutes for
  billion-row shapes; the manual-DMA kernel lowers in O(1).)
- interpreter path (tests only, `interpreted`): small
  auto-pipelined grid, no manual DMA.

Used automatically by Objective(fused=True) for dense, unnormalized batches;
everything else falls back to the jnp path.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.data.matrix import HybridRows, SparseRows
from photon_tpu.ops.losses import TaskType, loss_fns

# Per-chunk VMEM budget for one X slot (bytes). v5e VMEM is ~16 MB/core and
# the kernel holds two slots plus accumulators.
_X_CHUNK_BYTES = 4 * 1024 * 1024
_MAX_CHUNK_ROWS = 8192


def pick_chunk(n: int, d: int, itemsize: int) -> int | None:
    """Largest power-of-two row chunk (≥128, for lane-aligned aux DMA
    slices) that divides n and fits the VMEM budget. None when n has no
    usable factor (caller falls back to the jnp objective)."""
    rows = _MAX_CHUNK_ROWS
    while rows >= 128:
        if n % rows == 0 and rows * d * itemsize <= _X_CHUNK_BYTES:
            return rows
        rows //= 2
    return None


def _chunk_math(task: TaskType, Xt, aux, w_row):
    """Shared per-chunk compute: (weighted loss sum (1,1), grad (1, d)).
    Xt: (rows, d); aux: (8, rows), rows 0..2 = [y, weight, offset]
    (3..7 padding); w_row: (1, d).
    """
    loss_f, d1_f, _ = loss_fns(task)
    # z = (w Xᵀ) as a row vector: contract the d axes.
    z = jax.lax.dot_general(w_row, Xt, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, rows)
    z = z + aux[2:3, :]
    y, wt = aux[0:1, :], aux[1:2, :]
    lsum = jnp.sum(wt * loss_f(z, y)).reshape(1, 1)
    r = (wt * d1_f(z, y)).astype(Xt.dtype)  # bf16 operand when X is bf16
    g = jax.lax.dot_general(r, Xt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, d)
    return lsum, g


def _dma_kernel(task, rows, n_chunks,
                X_hbm, aux_hbm, w_ref, loss_ref, grad_ref,
                xbuf, abuf, sems):
    """grid=(1,): double-buffered manual DMA over row chunks."""

    def x_dma(slot, i):
        return pltpu.make_async_copy(
            X_hbm.at[pl.ds(i * rows, rows), :], xbuf.at[slot],
            sems.at[slot, 0])

    def a_dma(slot, i):
        return pltpu.make_async_copy(
            aux_hbm.at[:, pl.ds(i * rows, rows)], abuf.at[slot],
            sems.at[slot, 1])

    x_dma(0, 0).start()
    a_dma(0, 0).start()
    loss_ref[:] = jnp.zeros_like(loss_ref)
    grad_ref[:] = jnp.zeros_like(grad_ref)

    def body(i, _):
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_chunks)
        def _prefetch():
            x_dma(nxt, i + 1).start()
            a_dma(nxt, i + 1).start()

        x_dma(slot, i).wait()
        a_dma(slot, i).wait()
        lsum, g = _chunk_math(task, xbuf[slot], abuf[slot], w_ref[:])
        loss_ref[:] += lsum
        grad_ref[:] += g
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)


def _tile_kernel(task, X_ref, w_ref, aux_ref, loss_ref, grad_ref):
    """Auto-pipelined row-tile grid (interpreter/CPU path)."""

    @pl.when(pl.program_id(0) == 0)
    def _init():
        loss_ref[:] = jnp.zeros_like(loss_ref)
        grad_ref[:] = jnp.zeros_like(grad_ref)

    lsum, g = _chunk_math(task, X_ref[:], aux_ref[:], w_ref[:])
    loss_ref[:] += lsum
    grad_ref[:] += g


@functools.partial(jax.jit, static_argnames=("task", "interpret"))
def _fused_call(task, X, w, y, weights, offsets, interpret):
    n, d = X.shape
    rows = pick_chunk(n, d, X.dtype.itemsize)
    w_row = w.astype(X.dtype)[None, :]
    # (8, n): y/weight/offset + 5 zero rows of sublane padding (f32 tile
    # height is 8, so chunk DMAs slice only the lane dimension).
    aux = jnp.concatenate(
        [jnp.stack([y, weights, offsets], axis=0),
         jnp.zeros((5, n), jnp.float32)], axis=0)
    out_shape = [
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
        jax.ShapeDtypeStruct((1, d), jnp.float32),
    ]
    if interpret:
        loss, grad = pl.pallas_call(
            functools.partial(_tile_kernel, task),
            grid=(n // rows,),
            in_specs=[
                pl.BlockSpec((rows, d), lambda i: (i, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((8, rows), lambda i: (0, i)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1), lambda i: (0, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
            ],
            out_shape=out_shape,
            interpret=True,
        )(X, w_row, aux)
        return loss[0, 0], grad[0, :]

    loss, grad = pl.pallas_call(
        functools.partial(_dma_kernel, task, rows, n // rows),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),   # X streams from HBM
            pl.BlockSpec(memory_space=pltpu.HBM),   # aux streams from HBM
            pl.BlockSpec(memory_space=pltpu.VMEM),  # w_row
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, rows, d), X.dtype),
            pltpu.VMEM((2, 8, rows), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )(X, aux, w_row)
    return loss[0, 0], grad[0, :]


# Whether the kernel runs via Pallas ``interpret=True``: only inside a test
# harness's `interpreted` block. Everywhere else it compiles for the
# attached device — or the run fails with the compiler's message; no
# backend check turns interpretation on.
_INTERPRETED = False


@contextlib.contextmanager
def interpreted():
    """TEST HARNESS ONLY (tests/conftest.py): run the kernel in interpret
    mode for the duration, so CPU tests can pin its arithmetic against
    the jnp objective. Clears jit caches on entry and exit (the flag is a
    trace-time fact, not part of jit's cache key)."""
    global _INTERPRETED
    before, _INTERPRETED = _INTERPRETED, True
    if not before:
        jax.clear_caches()
    try:
        yield
    finally:
        _INTERPRETED = before
        if not before:
            jax.clear_caches()


def lowering_available(d: int) -> bool:
    """Whether a lowering of the fused kernel exists here for feature
    width ``d``: the compiled DMA path is a TPU kernel and needs the
    feature dim lane-aligned (Mosaic memref row-slices require the minor
    dim to be a multiple of the 128-lane tile); the interpreter lowering
    runs only inside a test harness's `interpreted` block. Any other
    backend or width takes the jnp objective."""
    return _INTERPRETED or (jax.default_backend() == "tpu" and d % 128 == 0)


def can_fuse(X) -> bool:
    """Dense 2-D X with an available lowering whose row count has a usable
    power-of-two chunk. (train_glm pads dense batches so the latter holds;
    see models/training.py.)"""
    if (isinstance(X, (SparseRows, HybridRows)) or not hasattr(X, "ndim")
            or X.ndim != 2 or not lowering_available(X.shape[1])):
        return False
    return pick_chunk(X.shape[0], X.shape[1], X.dtype.itemsize) is not None


def fused_value_and_grad(task: TaskType, X, w, y, weights, offsets):
    """(Σᵢ wᵢ·loss(zᵢ, yᵢ), Xᵀ(w∘d1)) — LOCAL sums (caller psums).

    Compiled manual-DMA pallas (callers gate on `can_fuse`); the
    interpreter lowering only inside `interpreted` (tests).
    """
    return _fused_call(task, X, w, y, weights, offsets, _INTERPRETED)
