"""The two Pallas TPU kernels behind the blocked-ELL dispatch seam.

STATUS (PR 21): none of the four forms below compiles for the TPU v5e —
Mosaic refuses the in-kernel table gathers (`wt[pc]`, `r[br]`: "Only 2D
gather is supported"; tests/test_chip_compile.py pins the messages at the
flagship layout's shapes). `kernels.active()` therefore keeps mode
``auto`` on the XLA path; what follows describes the design and its
interpret-mode (tests-only) behaviour, not something a chip has run.

Both kernels mirror `data/matrix.py`'s XLA ops PRIMITIVE FOR PRIMITIVE —
the same `_bell_compute` dtype recipe (bf16 storage multiplies in bf16),
the same ``einsum(..., preferred_element_type=f32)`` accumulation, the
same concat order — so Pallas interpret mode on CPU reproduces the XLA
path BITWISE (tests/test_kernels.py pins the full bucket matrix). What
changes is the memory traffic on a real TPU:

- `tail_matvec` fuses the whole tail X pass into ONE kernel: the
  tail-coefficient slice ``w[d_sel:n_prefix]`` loads HBM→VMEM once and
  every per-slot gather — the 12.3% pow2-padded slots included — is a
  VMEM access instead of an HBM granule (the round-12 `StaticCost.
  gather_bytes` wall), and the per-bucket einsum outputs concatenate and
  reassemble through ``row_pos`` inside VMEM, never materializing the
  (B,) intermediate in HBM (the XLA path writes it out and gathers it
  back in — two extra HBM passes over the tail rows per X pass).
- `bucket_rmatvec` fuses the occurrence-bucket gradient block the same
  way: one VMEM-resident read of the cotangent serves every bucket's
  pre-sorted gather + einsum, and the concatenated tail-gradient block
  is emitted directly.

The hot dense block stays on the XLA/MXU path in both passes (it is
already one `jnp.matmul` — nothing to fuse), as do the zero suffix and
the final `hot + tail` add, so kernel-vs-XLA parity reduces to the
bucket arithmetic these kernels own.

Two VMEM regimes, one dispatch ladder (`kernels.route`):

- Single-fused-kernel form (`tail_matvec` / `bucket_rmatvec`): one
  grid-free `pallas_call` with EVERY operand VMEM-resident — the fastest
  form while the whole layout fits `kernels.vmem_budget`.
- Grid-tiled form (`tail_matvec_tiled` / `bucket_rmatvec_tiled`, round
  20): past the budget, each width/occurrence bucket becomes its own
  `pallas_call` with a `grid` over row tiles — only the coefficient tail
  slice (matvec) or the cotangent (rmatvec) stays whole-array
  VMEM-resident (its BlockSpec index_map pins block 0 for every grid
  step), while the bucket's index/value arrays stream through in
  (T, W_b) tiles. Billion-row ladders stay on the kernel path instead
  of falling off to XLA exactly when the layouts get big. Row tiles come
  from `tuning.tile_tuner` (autotuned per backend, cached beside the
  AOT executables; `PHOTON_TPU_KERNELS_TILE` overrides), clamped so the
  resident slice plus one tile still fits the budget. Per-row
  reductions are row-independent, so tiling the row axis cannot move
  the reduction order — the tiled forms stay BITWISE equal to the XLA
  path (tests/test_kernels.py pins both forms on the full bucket
  matrix, including a bucket smaller than one tile).

The XLA path remains the always-available fallback below both forms
(`route` returns None when even one tile would not fit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["tail_matvec", "bucket_rmatvec", "tail_matvec_tiled",
           "bucket_rmatvec_tiled", "kernel_feasible", "tiled_feasible"]

_MIN_TILE = 8  # the f32 sublane quantum: no row tile below this


def _nbytes(a) -> int:
    return int(np.prod(a.shape, dtype=np.int64)) * np.dtype(a.dtype).itemsize


def kernel_feasible(X, w_or_r) -> bool:
    """Whether the single-fused-kernel form fits the VMEM budget for this
    layout (+ the vector it multiplies). No-tail layouts are infeasible
    by definition (there is nothing to fuse)."""
    from photon_tpu import kernels as K

    if not getattr(X, "ell_vals", ()) and not getattr(X, "bucket_vals", ()):
        return False
    budget = K.vmem_budget()
    if budget is None:
        return True
    total = _nbytes(w_or_r)
    for t in (X.ell_pcols, X.ell_vals, X.bucket_rows, X.bucket_vals):
        total += sum(_nbytes(b) for b in t)
    total += _nbytes(X.row_pos)
    return total <= budget


def _resident_nbytes(X, v) -> int:
    """Bytes of the slice of ``v`` a grid-tiled kernel keeps whole-array
    VMEM-resident: the full cotangent for an rmatvec (``v`` has row
    length n), only the ``[d_sel:n_prefix]`` tail slice for a matvec
    (``v`` has row length d)."""
    n = int(X.shape[0])
    rows = int(v.shape[0])
    if rows != n:  # coefficient vector: only the tail slice rides along
        rows = int(X.n_prefix - X.d_sel)
    per_row = _nbytes(v) // max(int(v.shape[0]), 1)
    return rows * per_row


def tiled_feasible(X, w_or_r) -> bool:
    """Whether the grid-tiled form fits the VMEM budget: the resident
    vector slice plus one minimum (``_MIN_TILE``-row) tile of the widest
    bucket's index/value pair. Row tiles shrink toward ``_MIN_TILE`` to
    fit (`_clamp_tile`), so this is the true floor — below it even the
    tiled form steps aside and the XLA path serves."""
    from photon_tpu import kernels as K

    if not getattr(X, "ell_vals", ()) and not getattr(X, "bucket_vals", ()):
        return False
    budget = K.vmem_budget()
    if budget is None:
        return True
    worst = 0
    for t in (X.ell_pcols, X.ell_vals, X.bucket_rows, X.bucket_vals):
        for b in t:
            width = int(np.prod(b.shape[1:], dtype=np.int64))
            worst = max(worst,
                        _MIN_TILE * width * np.dtype(b.dtype).itemsize)
    # one tile's index + value blocks ride together (2x the worst one is
    # a conservative bound: indices are int32, values <= 4 B/elem)
    return _resident_nbytes(X, w_or_r) + 2 * worst <= budget


def _clamp_tile(tile: int, row_bytes: int, budget_left) -> int:
    """Halve the autotuned row tile until one (tile x width) index+value
    block pair fits what the budget leaves after the resident slice."""
    tile = max(int(tile), _MIN_TILE)
    if budget_left is None:
        return tile
    while tile > _MIN_TILE and tile * row_bytes > budget_left:
        tile //= 2
    return tile


def _resolve_tile(kind: str, width: int, row_bytes: int, budget_left) -> int:
    """The row tile for one bucket: ``PHOTON_TPU_KERNELS_TILE`` override,
    else the autotuner's cached per-backend winner (default when never
    tuned), clamped to the VMEM budget."""
    from photon_tpu import kernels as K
    from photon_tpu.tuning.tile_tuner import tile_for

    tile = K.tile_override()
    if tile is None:
        tile = tile_for(kind, width)
    return _clamp_tile(tile, row_bytes, budget_left)


@functools.lru_cache(maxsize=256)
def _tail_call(n_buckets: int, lanes: bool, interp: bool, n: int, G: int):
    """One compiled-form `pallas_call` closure per (structure) key: the
    kernel body is pure python over the STATIC bucket count, so the
    closure caches on structure and jit caches on argument shapes."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    def kernel(*refs):
        # refs: row_pos, wt, (pc_i, pv_i)*, out
        rp_ref, wt_ref = refs[0], refs[1]
        out_ref = refs[-1]
        wt = wt_ref[:]
        parts = []
        for i in range(n_buckets):
            pc = refs[2 + 2 * i][:]
            pv = refs[3 + 2 * i][:]
            g = wt[pc]                      # ([S,] r_b, W_b[, G]) gather
            if g.dtype != pv.dtype:
                g = g.astype(pv.dtype)      # the _bell_compute recipe
            eq = "rw,rwg->rg" if lanes else "rw,rw->r"
            parts.append(jnp.einsum(eq, pv, g,
                                    preferred_element_type=f32))
        zero = jnp.zeros((1, G) if lanes else (1,), f32)
        cat = jnp.concatenate(parts + [zero], axis=0)
        out_ref[:] = cat[rp_ref[:]]

    out_shape = jax.ShapeDtypeStruct((n, G) if lanes else (n,), f32)

    def call(row_pos, wt, *buckets):
        return pl.pallas_call(
            kernel, out_shape=out_shape, interpret=interp,
        )(row_pos, wt, *buckets)

    return call


def _tail_slots(X):
    """(n,) int32: each STORED row's slot in `concatenate(bucket outputs +
    [one zero])` — what the reassembling gather reads. A caller-order
    layout carries it (`row_pos`); in a stored-order layout
    (`X.row_order`) row i is slot i, and every tail-free row reads the
    zero."""
    if X.row_order is None:
        return jnp.asarray(X.row_pos)
    return jnp.minimum(jnp.arange(X.shape[0], dtype=jnp.int32), X.tail_rows)


def tail_matvec(X, w):
    """The fused blocked-ELL tail matvec: (n,)/(n, G) f32 tail
    contributions in the layout's STORED row order (the caller adds the
    hot block's MXU matmul; the in-kernel reassembly reads `_tail_slots`,
    which for a stored-order layout is the concatenation itself). ``w`` is the full permuted (d,)/(d, G) vector; the
    kernel consumes only the contiguous ``w[d_sel:n_prefix]`` tail
    slice. Bitwise-equal to `data.matrix._bell_matvec`'s tail term."""
    from photon_tpu import kernels as K

    lanes = w.ndim == 2
    wt = w[X.d_sel:X.n_prefix]
    row_pos = _tail_slots(X)
    n = int(row_pos.shape[0])
    G = int(w.shape[1]) if lanes else 0
    args = (row_pos, wt) + tuple(
        x for pc, pv in zip(X.ell_pcols, X.ell_vals)
        for x in (jnp.asarray(pc), jnp.asarray(pv)))
    K.KERNEL_SIGNATURES.record("kernels.tail_matvec", args)
    call = _tail_call(len(X.ell_vals), lanes, K.interpret(), n, G)
    return call(*args)


@functools.lru_cache(maxsize=512)
def _tiled_tail_call(W: int, T: int, n_tiles: int, lanes: bool,
                     interp: bool, U: int, G: int):
    """One width-bucket's grid-tiled `pallas_call`: the tail-coefficient
    slice ``wt`` (U rows) is whole-array VMEM-resident (index_map pins
    block 0 every step) while the (R, W) index/value pair streams in
    (T, W) tiles over ``grid=(n_tiles,)``. Per-row arithmetic is the
    fused kernel's, verbatim — rows are reduction-independent, so the
    tiling cannot perturb a single row's bits."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    def kernel(wt_ref, pc_ref, pv_ref, out_ref):
        wt = wt_ref[:]
        pc = pc_ref[:]
        pv = pv_ref[:]
        g = wt[pc]                          # (T, W[, G]) gather
        if g.dtype != pv.dtype:
            g = g.astype(pv.dtype)          # the _bell_compute recipe
        eq = "rw,rwg->rg" if lanes else "rw,rw->r"
        out_ref[:] = jnp.einsum(eq, pv, g, preferred_element_type=f32)

    R = n_tiles * T
    wt_shape = (U, G) if lanes else (U,)
    wt_zero = (0, 0) if lanes else (0,)
    out_spec = (pl.BlockSpec((T, G), lambda i: (i, 0)) if lanes
                else pl.BlockSpec((T,), lambda i: (i,)))

    def call(wt, pc, pv):
        return pl.pallas_call(
            kernel,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec(wt_shape, lambda i: wt_zero),
                pl.BlockSpec((T, W), lambda i: (i, 0)),
                pl.BlockSpec((T, W), lambda i: (i, 0)),
            ],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((R, G) if lanes else (R,), f32),
            interpret=interp,
        )(wt, pc, pv)

    return call


def tail_matvec_tiled(X, w):
    """The grid-tiled blocked-ELL tail matvec: bitwise-equal to both the
    fused form and `data.matrix._bell_matvec`'s tail term, but each
    width bucket runs as its own row-tiled `pallas_call` so only the
    tail slice + one tile occupy VMEM at a time. Buckets pad to a tile
    multiple with zero rows (sliced back off before reassembly — a
    bucket smaller than one tile simply pads up to one); the concat +
    ``row_pos`` reassembly stays on the XLA side, exactly the fallback
    path's ops."""
    from photon_tpu import kernels as K

    lanes = w.ndim == 2
    wt = w[X.d_sel:X.n_prefix]
    row_pos = _tail_slots(X)
    G = int(w.shape[1]) if lanes else 0
    U = int(X.n_prefix - X.d_sel)
    args = (row_pos, wt) + tuple(
        x for pc, pv in zip(X.ell_pcols, X.ell_vals)
        for x in (jnp.asarray(pc), jnp.asarray(pv)))
    K.KERNEL_SIGNATURES.record("kernels.tail_matvec_tiled", args)
    budget = K.vmem_budget()
    left = None if budget is None else budget - _resident_nbytes(X, w)
    interp = K.interpret()
    parts = []
    for pc, pv in zip(X.ell_pcols, X.ell_vals):
        pc, pv = jnp.asarray(pc), jnp.asarray(pv)
        r_b, W = int(pc.shape[0]), int(pc.shape[1])
        row_bytes = (W * (4 + np.dtype(pv.dtype).itemsize)
                     + 4 * max(G, 1))
        T = _resolve_tile("tail_matvec", W, row_bytes, left)
        # a bucket smaller than one tile runs at its EXACT shape (one
        # grid step, no padding): XLA's per-row reduction strategy is a
        # function of the einsum's total row count, so only the exact
        # shape reproduces the fallback path's bits for tiny buckets —
        # at T >= 8 rows the strategy is row-stable and padding is safe
        T = min(T, r_b)
        R = -(-r_b // T) * T
        if R != r_b:
            pad = ((0, R - r_b), (0, 0))
            pc, pv = jnp.pad(pc, pad), jnp.pad(pv, pad)
        call = _tiled_tail_call(W, T, R // T, lanes, interp, U, G)
        parts.append(call(wt, pc, pv)[:r_b])
    zero = jnp.zeros((1, G) if lanes else (1,), jnp.float32)
    cat = jnp.concatenate(parts + [zero], axis=0)
    return cat[row_pos]


@functools.lru_cache(maxsize=256)
def _rmatvec_call(n_buckets: int, lanes: bool, square: bool, interp: bool,
                  U: int, G: int):
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    def kernel(*refs):
        # refs: r, (br_i, bv_i)*, out
        r_ref = refs[0]
        out_ref = refs[-1]
        r = r_ref[:]
        parts = []
        for i in range(n_buckets):
            br = refs[1 + 2 * i][:]
            bv = refs[2 + 2 * i][:]
            g = r[br]                       # (c_b, k_b[, G]) gather
            if square:
                v = bv.astype(f32)
                v, g = v * v, g.astype(f32)
            else:
                v = bv
                if g.dtype != v.dtype:
                    g = g.astype(v.dtype)   # the _bell_compute recipe
            eq = "ck,ckg->cg" if lanes else "ck,ck->c"
            parts.append(jnp.einsum(eq, v, g,
                                    preferred_element_type=f32))
        out_ref[:] = jnp.concatenate(parts, axis=0)

    out_shape = jax.ShapeDtypeStruct((U, G) if lanes else (U,), f32)

    def call(r, *buckets):
        return pl.pallas_call(
            kernel, out_shape=out_shape, interpret=interp,
        )(r, *buckets)

    return call


def bucket_rmatvec(X, r, square: bool = False):
    """The fused occurrence-bucket rmatvec: the (U,)/(U, G) f32
    tail-gradient block in prefix (concat) order, U = n_prefix − d_sel
    (the caller concatenates [hot, this, zero suffix]). Bitwise-equal to
    the bucket terms of `data.matrix._bell_rmatvec`."""
    from photon_tpu import kernels as K

    lanes = r.ndim == 2
    U = int(X.n_prefix - X.d_sel)
    G = int(r.shape[1]) if lanes else 0
    args = (jnp.asarray(r),) + tuple(
        x for br, bv in zip(X.bucket_rows, X.bucket_vals)
        for x in (jnp.asarray(br), jnp.asarray(bv)))
    K.KERNEL_SIGNATURES.record("kernels.bucket_rmatvec", args)
    call = _rmatvec_call(len(X.bucket_vals), lanes, bool(square),
                         K.interpret(), U, G)
    return call(*args)


@functools.lru_cache(maxsize=512)
def _tiled_rmatvec_call(kk: int, T: int, n_tiles: int, lanes: bool,
                        square: bool, interp: bool, n: int, G: int):
    """One occurrence-bucket's grid-tiled `pallas_call`: the cotangent
    ``r`` (n rows) stays whole-array VMEM-resident while the (C, k_b)
    row/value pair streams in (T, k_b) tiles. Same per-column arithmetic
    as the fused kernel — column outputs are reduction-independent."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    def kernel(r_ref, br_ref, bv_ref, out_ref):
        r = r_ref[:]
        br = br_ref[:]
        bv = bv_ref[:]
        g = r[br]                           # (T, k_b[, G]) gather
        if square:
            v = bv.astype(f32)
            v, g = v * v, g.astype(f32)
        else:
            v = bv
            if g.dtype != v.dtype:
                g = g.astype(v.dtype)       # the _bell_compute recipe
        eq = "ck,ckg->cg" if lanes else "ck,ck->c"
        out_ref[:] = jnp.einsum(eq, v, g, preferred_element_type=f32)

    C = n_tiles * T
    r_shape = (n, G) if lanes else (n,)
    r_zero = (0, 0) if lanes else (0,)
    out_spec = (pl.BlockSpec((T, G), lambda i: (i, 0)) if lanes
                else pl.BlockSpec((T,), lambda i: (i,)))

    def call(r, br, bv):
        return pl.pallas_call(
            kernel,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec(r_shape, lambda i: r_zero),
                pl.BlockSpec((T, kk), lambda i: (i, 0)),
                pl.BlockSpec((T, kk), lambda i: (i, 0)),
            ],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((C, G) if lanes else (C,), f32),
            interpret=interp,
        )(r, br, bv)

    return call


def bucket_rmatvec_tiled(X, r, square: bool = False):
    """The grid-tiled occurrence-bucket rmatvec: bitwise-equal to the
    fused form and to the bucket terms of `data.matrix._bell_rmatvec`,
    with each occurrence bucket as its own column-tiled `pallas_call`
    (only the cotangent + one tile VMEM-resident at a time). Buckets
    pad to a tile multiple with zero columns, sliced back off before the
    XLA-side concat."""
    from photon_tpu import kernels as K

    lanes = r.ndim == 2
    r = jnp.asarray(r)
    n = int(r.shape[0])
    G = int(r.shape[1]) if lanes else 0
    args = (r,) + tuple(
        x for br, bv in zip(X.bucket_rows, X.bucket_vals)
        for x in (jnp.asarray(br), jnp.asarray(bv)))
    K.KERNEL_SIGNATURES.record("kernels.bucket_rmatvec_tiled", args)
    budget = K.vmem_budget()
    left = None if budget is None else budget - _resident_nbytes(X, r)
    interp = K.interpret()
    parts = []
    for br, bv in zip(X.bucket_rows, X.bucket_vals):
        br, bv = jnp.asarray(br), jnp.asarray(bv)
        c_b, kk = int(br.shape[0]), int(br.shape[1])
        row_bytes = (kk * (4 + np.dtype(bv.dtype).itemsize)
                     + 4 * max(G, 1))
        T = _resolve_tile("bucket_rmatvec", kk, row_bytes, left)
        T = min(T, c_b)  # sub-tile bucket: exact shape (see tail twin)
        C = -(-c_b // T) * T
        if C != c_b:
            pad = ((0, C - c_b), (0, 0))
            br, bv = jnp.pad(br, pad), jnp.pad(bv, pad)
        call = _tiled_rmatvec_call(kk, T, C // T, lanes, bool(square),
                                   interp, n, G)
        parts.append(call(r, br, bv)[:c_b])
    return jnp.concatenate(parts, axis=0)


# ----------------------------------------------------------------- contracts
# The roofline-closure pins (photon_tpu/analysis): the kernel-dispatched
# X passes keep the blocked-ELL law — ZERO scatters of any kind, every
# sparse dot/einsum accumulating f32 (the walker descends into the
# pallas_call's own jaxpr, so the law holds INSIDE the kernel too) — and
# the dispatch seam never retraces: kernel-on and kernel-off dispatches
# of the same layout record identical call signatures.
from photon_tpu.analysis.contracts import register_contract  # noqa: E402
from photon_tpu.analysis.walker import SCATTER_PRIMITIVES  # noqa: E402


def _contract_X(bf16: bool = True):
    from photon_tpu.data.matrix import _contract_blocked_ell

    return _contract_blocked_ell(bf16=bf16)


@register_contract(
    name="blocked_ell_kernel_x_passes",
    description="BlockedEllRows matvec + rmatvec with the Pallas kernels "
                "dispatched (trace-level law; refused by the v5e's "
                "compiler today): gather-fused tail and "
                "occurrence buckets INSIDE one pallas_call each, ZERO "
                "scatters of any kind, every sparse dot/einsum "
                "accumulating f32 — the walker checks the kernel body's "
                "jaxpr, not just the caller's",
    collectives={}, forbid=SCATTER_PRIMITIVES, require_f32_accum=True,
    tags=("kernels", "sparse", "resident"))
def _contract_kernel_x_passes():
    from photon_tpu import kernels as K
    from photon_tpu.data import matrix as M

    X = _contract_X(bf16=True)
    n, d = X.shape

    def both(Xb, w, r):
        with K.scope("on"):
            z = M.layout_matvec(Xb, w)
            return z, M.rmatvec(Xb, r * z)

    return both, (X, jnp.zeros((d,), jnp.float32),
                  jnp.zeros((n,), jnp.float32))


@register_contract(
    name="blocked_ell_kernel_no_retrace",
    description="the kernel dispatch seam is signature-invariant: the "
                "same blocked-ELL layout dispatched kernels-on and "
                "kernels-off records IDENTICAL call signatures (the "
                "builder replays both modes through TraceSignatureLog "
                "and raises on divergence), so flipping the knob — or "
                "falling back per call — never retraces a caller",
    collectives={}, tags=("kernels", "sparse"))
def _contract_kernel_no_retrace():
    from photon_tpu import kernels as K
    from photon_tpu.analysis.rules import TraceSignatureLog
    from photon_tpu.data import matrix as M

    X = _contract_X(bf16=False)
    n, d = X.shape
    w = jnp.zeros((d,), jnp.float32)
    r = jnp.zeros((n,), jnp.float32)
    log = TraceSignatureLog()
    # The caller-visible dispatch signature is (X, w) — record it under
    # both modes; the seam must not perturb shapes/dtypes/weak types.
    for m in ("off", "on", "off"):
        with K.scope(m):
            log.record("dispatch.matvec", (X, w))
            log.record("dispatch.rmatvec", (X, r))
    for name in ("dispatch.matvec", "dispatch.rmatvec"):
        sigs = log.signatures(name)
        if len(sigs) != 1:
            raise AssertionError(
                f"kernel dispatch seam drifted: {len(sigs)} distinct "
                f"{name} signatures across mode flips (expected 1)")
    if log.hazards():
        raise AssertionError(
            f"kernel dispatch weak-type drift: {log.hazards()}")

    def passes(Xb, wv, rv):
        with K.scope("on"):
            return M.layout_matvec(Xb, wv), M.rmatvec(Xb, rv)

    return passes, (X, w, r)


@register_contract(
    name="blocked_ell_tiled_x_passes",
    description="the grid-tiled middle rung (round 20): tail matvec and "
                "occurrence-bucket rmatvec streamed through VMEM in row "
                "tiles obey the SAME law as the fused forms — ZERO "
                "scatters anywhere (reassembly is concatenate + gather "
                "on the XLA side), every sparse dot/einsum accumulating "
                "f32 inside the tiled pallas_call bodies",
    collectives={}, forbid=SCATTER_PRIMITIVES, require_f32_accum=True,
    tags=("kernels", "sparse", "streamed"))
def _contract_tiled_x_passes():
    from photon_tpu import kernels as K

    X = _contract_X(bf16=True)
    n, d = X.shape

    def both(Xb, w, r):
        with K.scope("on"):
            z = tail_matvec_tiled(Xb, w)
            return z, bucket_rmatvec_tiled(Xb, r)

    return both, (X, jnp.zeros((d,), jnp.float32),
                  jnp.zeros((n,), jnp.float32))
