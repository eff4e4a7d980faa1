"""Static per-program cost estimates: the MODELED half of the
attribution ledger's modeled-vs-measured roofline story.

PR 3's `analysis/walker.py` walks a jaxpr to pin communication/dtype
LAW; this module rides the same recursive descent to ESTIMATE cost —
FLOPs from `dot_general`/elementwise/reduction shapes, bytes moved from
operand avals, collective payload bytes from the collective primitives'
operands, with `scan` bodies multiplied by their static ``length`` and
`while` bodies by a caller-supplied trip-count hint (solver loops bound
their trips by ``max_iters``; an un-hinted while defaults to 1 and the
estimate is marked a lower bound).

Two deliberate conventions:

- **Per-device view.** Higher-order call eqns (`jit`, `scan`, `while`,
  `cond`, `shard_map`, custom-derivative wrappers) contribute nothing
  themselves — only their leaf equations are costed — so a `shard_map`
  body is costed at its per-device shapes. Roofline utilization is a
  per-chip quantity; aggregate = per-chip × mesh size.
- **Bytes are an operand-traffic proxy.** Each costed leaf equation
  charges its input + output aval bytes. XLA fuses aggressively, so this
  OVERSTATES true HBM traffic (intermediate operands of a fused
  elementwise chain never materialize); the ledger therefore also
  records XLA's own ``compiled.cost_analysis()`` view where available,
  and the utilization fraction is computed against the ESTIMATE that
  binds (the model is a ceiling check, not an exact simulator).
- **Gathers/scatters are costed per SLICE, not per operand** (round 12).
  A w-gather over a 10M-feature table touches ``n_indices`` granules,
  not the 40 MB table — the operand-bytes proxy would claim sparse
  programs are 1000x more bandwidth-hungry than they are. Each slice
  pays ``max(slice_bytes, GATHER_GRANULE_BYTES)`` (the irregular-access
  floor: a 4-byte scalar gather still moves a granule), tallied into
  ``StaticCost.gather_bytes`` so the attribution report can show the
  irregular-access share of a sparse program's roofline.
- **Dot operands are costed at their STORAGE width** (round 15). A
  quantized program dequantizes in-program (``int8 → f32`` convert +
  scale multiply, fused by XLA into the dot), so the dot's operand aval
  says f32 while HBM really streamed 1 byte/element — the aval-width
  proxy would claim the quantized rungs moved 4× their true bytes and
  their roofline intensity would read 4× too low. `estimate_jaxpr`
  therefore tracks each value's PROVENANCE through
  ``convert_element_type`` / broadcast / scale-multiply chains and
  charges every ``dot_general`` operand at the narrowest source dtype
  it was widened from; the narrowing is tallied into
  ``StaticCost.narrowed_bytes`` so the report can say how much of a
  program's traffic the quantization actually removed.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from photon_tpu.analysis.walker import (
    COLLECTIVE_PRIMITIVES,
    as_jaxpr,
    sub_jaxprs,
)

__all__ = ["StaticCost", "estimate_jaxpr", "estimate_fn", "xla_cost"]


# 1 FLOP per output element. Comparison/select/copy ops count here too:
# they occupy the VPU a lane-cycle each, which is what a roofline cares
# about (transcendentals are tallied separately below — on TPU they cost
# several VPU passes, on CPU a libm call).
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "sign",
    "floor", "ceil", "round", "pow", "integer_pow", "rem",
    "and", "or", "xor", "not", "select_n", "clamp", "nextafter",
    "eq", "ne", "lt", "le", "gt", "ge", "square",
    "is_finite", "erf_inv", "copy",
})

_TRANSCENDENTAL = frozenset({
    "exp", "log", "log1p", "expm1", "logistic", "tanh", "sqrt", "rsqrt",
    "sin", "cos", "erf", "lgamma", "digamma", "cbrt",
})

# Accumulator fills: 1 FLOP per INPUT element.
_REDUCTION = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "cumsum", "cummax", "cummin", "cumprod",
    "reduce_window_sum", "argmax", "argmin", "add_any",
})

# Data movement with no arithmetic: bytes only.
_MOVEMENT = frozenset({
    "scatter", "dynamic_update_slice", "slice",
    "concatenate", "reshape", "broadcast_in_dim", "transpose", "rev",
    "pad", "squeeze", "convert_element_type", "bitcast_convert_type",
    "iota", "sort",
})

# Irregular random-access ops (gathers, combining scatters): costed per
# SLICE, not per operand — charging a (d,)-table gather its full table
# bytes would put a 40 MB read on every 10M-feature w-gather and make
# every sparse program look bandwidth-bound at 1000x its real traffic.
# Each slice pays at least one access granule (TPU sublane/cache-line
# scale), which is also what makes narrow scalar gathers honestly more
# expensive per useful byte than wide ones.
_IRREGULAR = frozenset({
    "gather", "scatter-add", "scatter-sub", "scatter-mul", "scatter-min",
    "scatter-max", "dynamic_slice",
})

GATHER_GRANULE_BYTES = 32


def _irregular_bytes(eqn, name: str) -> tuple[int, int]:
    """(random_access_bytes, regular_io_bytes) for a gather/scatter eqn:
    index + produced/consumed bytes move sequentially; the per-slice
    table traffic pays max(slice_bytes, GATHER_GRANULE_BYTES) per slice."""
    try:
        slice_sizes = eqn.params.get("slice_sizes")
        if slice_sizes is None:  # scatter family: updates operand's window
            upd = eqn.invars[2].aval
            slice_elems = 1
            dnums = eqn.params.get("dimension_numbers")
            for i in getattr(dnums, "update_window_dims", ()):
                slice_elems *= int(upd.shape[i])
            ref = eqn.invars[2]
        else:
            slice_elems = int(np.prod(slice_sizes, dtype=np.int64)) or 1
            ref = eqn.outvars[0]
        itemsize = np.dtype(eqn.invars[0].aval.dtype).itemsize
        n_slices = max(_numel(ref) // max(slice_elems, 1), 1)
        random = n_slices * max(slice_elems * itemsize,
                                GATHER_GRANULE_BYTES)
        regular = (sum(_aval_bytes(v) for v in eqn.invars[1:])
                   + sum(_aval_bytes(v) for v in eqn.outvars))
        return int(random), int(regular)
    except Exception:  # noqa: BLE001 — fall back to the io-bytes proxy
        io = (sum(_aval_bytes(v) for v in eqn.invars)
              + sum(_aval_bytes(v) for v in eqn.outvars))
        return 0, int(io)


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "dtype"):
        return 0
    shape = tuple(getattr(aval, "shape", ()))
    try:
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        return n * np.dtype(aval.dtype).itemsize
    except TypeError:  # symbolic dims: not costable statically
        return 0


def _numel(v) -> int:
    aval = getattr(v, "aval", None)
    shape = tuple(getattr(aval, "shape", ())) if aval is not None else ()
    try:
        return int(np.prod(shape, dtype=np.int64)) if shape else 1
    except TypeError:
        return 0


def _dot_general_flops(eqn) -> int:
    """2·batch·M·N·K from the dimension numbers (the MXU convention of
    counting one multiply + one add per contraction element)."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = tuple(eqn.invars[0].aval.shape)
    rhs = tuple(eqn.invars[1].aval.shape)
    batch = int(np.prod([lhs[i] for i in lb], dtype=np.int64)) if lb else 1
    K = int(np.prod([lhs[i] for i in lc], dtype=np.int64)) if lc else 1
    m_dims = [s for i, s in enumerate(lhs) if i not in set(lc) | set(lb)]
    n_dims = [s for i, s in enumerate(rhs) if i not in set(rc) | set(rb)]
    M = int(np.prod(m_dims, dtype=np.int64)) if m_dims else 1
    N = int(np.prod(n_dims, dtype=np.int64)) if n_dims else 1
    return 2 * batch * M * N * K


@dataclasses.dataclass
class StaticCost:
    """One program's modeled cost (per call, per device)."""

    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    transcendentals: float = 0.0
    dot_flops: float = 0.0
    # random-access traffic of gather/scatter slices (granule-rounded;
    # included in `bytes`) — the sparse-program share of the roofline
    gather_bytes: float = 0.0
    # bytes REMOVED from the charge by storage-width provenance: dot
    # operands that were widened in-program (int8/bf16 dequant chains)
    # cost their narrow storage width, and this tallies the difference —
    # the quantized-rung share of the roofline story
    narrowed_bytes: float = 0.0
    eqns: int = 0
    while_loops: int = 0
    while_trips_assumed: int = 1  # the hint applied to un-lengthed loops

    @property
    def lower_bound(self) -> bool:
        """True when the estimate contains a while body costed at the
        default single trip — real cost is at least this."""
        return self.while_loops > 0 and self.while_trips_assumed <= 1

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (FLOPs per byte moved) — the roofline
        x-axis."""
        return self.flops / self.bytes if self.bytes > 0 else 0.0

    def to_json(self) -> dict:
        return {
            "flops": self.flops, "bytes": self.bytes,
            "collective_bytes": self.collective_bytes,
            "transcendentals": self.transcendentals,
            "dot_flops": self.dot_flops,
            "gather_bytes": self.gather_bytes,
            "narrowed_bytes": self.narrowed_bytes, "eqns": self.eqns,
            "while_loops": self.while_loops,
            "while_trips_assumed": self.while_trips_assumed,
            "intensity": round(self.intensity, 4),
            "lower_bound": self.lower_bound,
        }


# Ops through which a value's STORAGE width propagates unchanged — the
# dequant chain (convert + broadcast + scale-multiply) a quantized dot
# rides. `mul`/`div` take the narrowest array operand (q·scale keeps q's
# width: the scale was never the streamed operand).
_STORAGE_TRANSPARENT = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "slice",
    "rev", "copy",
})
_STORAGE_COMBINING = frozenset({"mul", "div"})


def _itemsize(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "dtype"):
        return 0
    return np.dtype(aval.dtype).itemsize


def estimate_jaxpr(jaxpr, while_trips: int = 1) -> StaticCost:
    """Walk a (Closed)Jaxpr and accumulate the modeled cost. ``while_
    trips`` is the per-`while` trip-count hint (e.g. a solver's
    max_iters); `scan` lengths come from the IR itself."""
    cost = StaticCost(while_trips_assumed=int(while_trips))
    # var -> storage itemsize where NARROWER than the aval width (the
    # round-15 dtype-aware operand rule; see the module docstring)
    storage_env: dict = {}

    def _storage(v) -> int:
        try:
            return storage_env.get(v, _itemsize(v))
        except TypeError:  # unhashable (literals): aval width
            return _itemsize(v)

    def walk(j, mult: float) -> None:
        for eqn in as_jaxpr(j).eqns:
            name = eqn.primitive.name
            if name == "convert_element_type" and eqn.invars:
                src = _storage(eqn.invars[0])
                if src and src < _itemsize(eqn.outvars[0]):
                    storage_env[eqn.outvars[0]] = src
            elif name in _STORAGE_TRANSPARENT and eqn.invars:
                src = _storage(eqn.invars[0])
                if src and src < _itemsize(eqn.outvars[0]):
                    storage_env[eqn.outvars[0]] = src
            elif name in _STORAGE_COMBINING and len(eqn.invars) == 2:
                src = min(s for s in (_storage(eqn.invars[0]),
                                      _storage(eqn.invars[1])) if s) \
                    if any((_storage(v) for v in eqn.invars)) else 0
                if src and src < _itemsize(eqn.outvars[0]):
                    storage_env[eqn.outvars[0]] = src
            subs = list(sub_jaxprs(eqn))
            if subs:
                # call eqns are containers: cost only their leaves
                sub_mult = mult
                if name == "scan":
                    sub_mult = mult * int(eqn.params.get("length", 1))
                elif name == "while":
                    cost.while_loops += 1
                    sub_mult = mult * max(int(while_trips), 1)
                for sub in subs:
                    walk(sub, sub_mult)
                continue
            cost.eqns += 1
            io_bytes = (sum(_aval_bytes(v) for v in eqn.invars)
                        + sum(_aval_bytes(v) for v in eqn.outvars))
            if name == "dot_general":
                f = _dot_general_flops(eqn)
                cost.dot_flops += mult * f
                cost.flops += mult * f
                # operands charge their STORAGE width (a fused dequant's
                # int8 source, not the widened f32 aval) — round 15
                op_bytes = (sum(_numel(v) * (_storage(v) or _itemsize(v))
                                for v in eqn.invars)
                            + sum(_aval_bytes(v) for v in eqn.outvars))
                cost.narrowed_bytes += mult * max(io_bytes - op_bytes, 0)
                cost.bytes += mult * op_bytes
            elif name in _ELEMENTWISE:
                n = max((_numel(v) for v in eqn.outvars), default=0)
                cost.flops += mult * n
                cost.bytes += mult * io_bytes
            elif name in _TRANSCENDENTAL:
                n = max((_numel(v) for v in eqn.outvars), default=0)
                cost.flops += mult * n
                cost.transcendentals += mult * n
                cost.bytes += mult * io_bytes
            elif name in _REDUCTION:
                n = max((_numel(v) for v in eqn.invars), default=0)
                cost.flops += mult * n
                cost.bytes += mult * io_bytes
            elif name in COLLECTIVE_PRIMITIVES:
                payload = sum(_aval_bytes(v) for v in eqn.invars)
                cost.collective_bytes += mult * payload
                cost.flops += mult * sum(_numel(v) for v in eqn.invars)
                cost.bytes += mult * io_bytes
            elif name in _IRREGULAR:
                random, regular = _irregular_bytes(eqn, name)
                cost.gather_bytes += mult * random
                cost.bytes += mult * (random + regular)
            elif name in _MOVEMENT:
                cost.bytes += mult * io_bytes
            # anything else (rng, custom calls, ...): uncounted rather
            # than guessed — the estimate stays a defensible floor

    walk(jaxpr, 1.0)
    return cost


def estimate_fn(fn, args, while_trips: int = 1) -> StaticCost:
    """Trace ``fn(*args)`` (jax.make_jaxpr — no lowering, no compile)
    and estimate it. Mirrors `analysis.contracts.trace_contract`'s
    trace-only discipline: safe on any backend, costs milliseconds."""
    import jax

    return estimate_jaxpr(jax.make_jaxpr(fn)(*args),
                          while_trips=while_trips)


def xla_cost(fn, args) -> Optional[dict]:
    """XLA's OWN view of the compiled program: ``flops`` / ``bytes
    accessed`` from ``compiled.cost_analysis()`` plus the
    ``memory_analysis()`` sizes. This LOWERS AND COMPILES (unlike
    everything else in this module) — the ledger only calls it from
    explicit compile probes, never from hot paths. Returns None when the
    backend provides no analysis."""
    import jax

    try:
        compiled = jax.jit(fn).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # jax<=0.4.x returns [dict]
            ca = ca[0] if ca else {}
        out = {"flops": float(ca.get("flops", 0.0)),
               "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
               "transcendentals": float(ca.get("transcendentals", 0.0))}
        try:
            ma = compiled.memory_analysis()
            out["temp_bytes"] = int(ma.temp_size_in_bytes)
            out["argument_bytes"] = int(ma.argument_size_in_bytes)
            out["output_bytes"] = int(ma.output_size_in_bytes)
        except Exception:  # noqa: BLE001 — memory stats are best-effort
            pass
        return out
    except Exception:  # noqa: BLE001 — absence of analysis is not an error
        return None
