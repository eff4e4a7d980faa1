"""The fused int8 serving-rung Pallas kernel (round 20).

STATUS (PR 21): this kernel does not compile for the TPU v5e — Mosaic
refuses the per-entity row gather (`q[eids]`: "Shape mismatch in input,
indices and output"; tests/test_chip_compile.py pins it at the flagship
store's shapes). `kernels.active()` keeps mode ``auto`` on the XLA rung;
what follows describes the design and its interpret-mode (tests-only)
behaviour, not something a chip has run.

The quantized serving rungs (`serving/programs.py::_build_score_fn`,
``quantize="int8"``) lower through generic XLA as separate ops: per
coordinate, a dequant (``q.astype(f32) * scale``), then a fixed-effect
matvec or a per-entity gather + rowwise dot. On a real TPU each op is
its own HBM round-trip over the (E+1, d) coefficient blocks — exactly
the serving-side twin of the training gap PR 14 closed. This kernel
fuses ONE WHOLE RUNG into a single `pallas_call`: offsets in, margin
out, every coordinate's dequant + contraction in coordinate order with
the store's quantized hot blocks VMEM-resident for the duration — a
dispatcher flush re-enters the same executable with the same device
blocks, so the blocks stay put across the flush instead of re-streaming
per op.

Parity is the package law: the kernel body mirrors the XLA score
function PRIMITIVE FOR PRIMITIVE — the same ``q.astype(f32) * s``
dequant, the same `data.matrix.matvec` branches for the fixed shards
(dense ``jnp.matmul(..., preferred_element_type=f32)``; sparse
``einsum("nk,nk->n", values.astype(f32), wq[idx])``), the same
`game.model.score_rows` branches for the random shards
(``take_along_axis`` + ``einsum("nk,nk->n", values, gathered)``; dense
``einsum("nd,nd->n", X, rows)``), contributions summed in coordinate
order starting from the offsets — so interpret mode on CPU reproduces
the XLA rung BITWISE, cold-miss row included (row E quantizes at scale
1.0 and dequantizes to exact zeros). tests/test_serving_kernels.py pins
it; the XLA body stays the always-available fallback (the dispatch
branch in `_build_score_fn` is trace-time, guarded by the same
`kernels.scope` cache-clearing seam as the blocked-ELL kernels).

Feasibility: one rung's operands — request shards, entity ids, int8
blocks + scales, offsets — must fit `kernels.vmem_budget` together
(`fused_feasible`); past it the rung stays on XLA. The inverse link
(`mean_fn`) applies OUTSIDE the kernel in both paths, exactly where the
XLA path applies it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fused_feasible", "fused_int8_margin"]


def _leaf_nbytes(leaf) -> int:
    shape = np.shape(leaf)
    dtype = getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
    return (int(np.prod(shape, dtype=np.int64)) if shape else 1) \
        * np.dtype(dtype).itemsize


def fused_feasible(offsets, shards, ids, fixed_ws, re_cs) -> bool:
    """Whether one rung's whole operand set (plus its (B,) f32 margin)
    fits the VMEM budget — the fused kernel keeps everything resident,
    so there is no partial form between it and the XLA fallback."""
    from photon_tpu import kernels as K

    budget = K.vmem_budget()
    if budget is None:
        return True
    leaves = jax.tree_util.tree_leaves(
        (offsets, shards, ids, fixed_ws, re_cs))
    total = sum(_leaf_nbytes(leaf) for leaf in leaves)
    total += int(np.shape(offsets)[0]) * 4  # the margin output
    return total <= budget


def fused_int8_margin(coords, offsets, shards, ids, fixed_ws, re_cs):
    """The fused rung margin: one `pallas_call` over the flattened
    operands of every coordinate in ``coords`` order. Returns the (B,)
    f32 margin (the caller applies the task's inverse link, exactly as
    the XLA path does).

    ``coords`` is the ladder's static ``((name, kind, feature_shard),
    ...)`` tuple; everything array-valued — request shards, ids, int8
    blocks, row scales — enters as a kernel operand, so a coefficient
    hot-swap (new arrays, same shapes) reuses the executable unchanged,
    the same argument discipline as the XLA rung."""
    from jax.experimental import pallas as pl

    from photon_tpu import kernels as K
    from photon_tpu.data.matrix import SparseRows

    f32 = jnp.float32
    ops = [jnp.asarray(offsets)]
    recipe = []  # one static step per coordinate: ref slots + branch
    for name, kind, shard in coords:
        X = shards[shard]
        sparse = isinstance(X, SparseRows)
        base = len(ops)
        if sparse:
            ops += [jnp.asarray(X.indices), jnp.asarray(X.values)]
        else:
            ops += [jnp.asarray(X)]
        if kind == "fixed":
            q, s = fixed_ws[name]
            qpos = len(ops)
            # the fixed scale is a host scalar — ship it as a (1,)
            # operand so a hot-swap's re-quantization never retraces
            ops += [jnp.asarray(q), jnp.reshape(jnp.asarray(s, f32), (1,))]
            recipe.append(("fixed", sparse, base, qpos))
        else:
            ipos = len(ops)
            ops += [jnp.asarray(ids[name])]
            q, s = re_cs[name]
            qpos = len(ops)
            ops += [jnp.asarray(q), jnp.asarray(s)]
            recipe.append(("random", sparse, base, ipos, qpos))
    B = int(ops[0].shape[0])

    def kernel(*refs):
        out_ref = refs[-1]
        margin = refs[0][:]
        for step in recipe:
            if step[0] == "fixed":
                _, sparse, base, qpos = step
                q = refs[qpos][:]
                s = refs[qpos + 1][:]
                wq = q.astype(f32) * s[0]
                if sparse:
                    idx, val = refs[base][:], refs[base + 1][:]
                    # data.matrix.matvec's SparseRows branch, verbatim
                    margin = margin + jnp.einsum(
                        "nk,nk->n", val.astype(f32), wq[idx])
                else:
                    x = refs[base][:]
                    # data.matrix.matvec's dense branch, verbatim
                    margin = margin + jnp.matmul(
                        x, wq.astype(x.dtype), preferred_element_type=f32)
            else:
                _, sparse, base, ipos, qpos = step
                q = refs[qpos][:]
                s = refs[qpos + 1][:]
                eids = refs[ipos][:]
                # the XLA rung's dequant-gather, verbatim: row E carries
                # scale 1.0 over zeros -> exact-zero cold-miss rows
                rows = q[eids].astype(f32) * s[eids][:, None]
                if sparse:
                    idx, val = refs[base][:], refs[base + 1][:]
                    # game.model.score_rows' SparseRows branch, verbatim
                    g = jnp.take_along_axis(rows, idx, axis=1)
                    margin = margin + jnp.einsum("nk,nk->n", val, g)
                else:
                    x = refs[base][:]
                    # score_rows' dense branch, verbatim
                    margin = margin + jnp.einsum("nd,nd->n", x, rows)
        out_ref[:] = margin

    K.KERNEL_SIGNATURES.record("kernels.serving_int8", tuple(ops))
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((B,), f32),
        interpret=K.interpret(),
    )(*ops)


# ----------------------------------------------------------------- contracts
# The serving-side pins: a kernels-routed quantized rung keeps the
# serving-program law (zero collectives, zero host exits, no scatters,
# f32 accumulation INSIDE the fused pallas_call body), and the kernel
# seam never moves a rung's dispatch signature — kernels-on and
# kernels-off record identical call signatures for the same rung args,
# so only the AOT-store key (which carries the route marker) tells the
# two executables apart.
from photon_tpu.analysis.contracts import register_contract  # noqa: E402
from photon_tpu.analysis.walker import SCATTER_PRIMITIVES  # noqa: E402


@register_contract(
    name="serving_kernel_fused_rung",
    description="one int8 serving rung routed through the FUSED Pallas "
                "kernel (kernels.scope('on'); trace-level law — the "
                "v5e's compiler refuses the kernel today): the "
                "whole dequant + fixed matvec + per-entity gather-dot "
                "inside one pallas_call, ZERO collectives, ZERO "
                "scatters, every dot/einsum accumulating f32 — the "
                "walker descends into the kernel body's jaxpr",
    collectives={}, forbid=SCATTER_PRIMITIVES, require_f32_accum=True,
    tags=("serving", "kernels"))
def _contract_fused_rung():
    from photon_tpu import kernels as K
    from photon_tpu.serving.programs import ProgramLadder, _tiny_store

    ladder = ProgramLadder(_tiny_store(), ladder=(8,),
                           sparse_k={"member": 3}, output_mean=True,
                           quantize="int8")
    args = ladder.example_args(8)

    def rung(*a):
        with K.scope("on"):
            return ladder._fn(*a)

    return rung, args


@register_contract(
    name="serving_kernel_mode_invariance",
    description="the serving-kernel seam is signature-invariant: the "
                "same quantized rung args record IDENTICAL dispatch "
                "signatures kernels-on and kernels-off (the builder "
                "replays both modes through TraceSignatureLog and "
                "raises on divergence) — the route lives in the AOT "
                "key, never in the call signature",
    collectives={}, tags=("serving", "kernels"))
def _contract_mode_invariance():
    from photon_tpu import kernels as K
    from photon_tpu.analysis.rules import TraceSignatureLog
    from photon_tpu.serving.programs import ProgramLadder, _tiny_store

    ladder = ProgramLadder(_tiny_store(), ladder=(8,),
                           sparse_k={"member": 3}, output_mean=True,
                           quantize="int8")
    args = ladder.example_args(8)
    log = TraceSignatureLog()
    for m in ("off", "on", "off"):
        with K.scope(m):
            log.record("serving.kernel_rung", args)
    if len(log.signatures("serving.kernel_rung")) != 1:
        raise AssertionError(
            "serving kernel seam drifted: rung args signature moved "
            "across mode flips (expected 1 signature)")
    if log.hazards():
        raise AssertionError(
            f"serving kernel weak-type drift: {log.hazards()}")
    return ladder._fn, args
