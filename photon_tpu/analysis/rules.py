"""Contract rules: each one turns a traced program + its declared budgets
into zero or more `Violation`s.

The five rules (run by `contracts.check_contract` on every registered
`ContractSpec`):

1. ``collective-budget`` — the traced count of every collective primitive
   must EQUAL the spec's declared budget (undeclared collectives budget 0),
   and `ContractSpec.forbid` primitives (e.g. the scatter family on the
   permuted layouts) must not appear at all.
2. ``transfer-lint`` — no `device_put` / `pure_callback` / `io_callback`
   inside the traced program; inside a `scan`/`while` body it is flagged as
   a per-iteration host round-trip (the worst kind).
3. ``dtype-policy`` — no f64 avals (unless allowed), and no bf16
   ACCUMULATION: reductions over bf16 operands and bf16×bf16→bf16
   `dot_general` violate the MXU policy (bf16 inputs, f32 accumulate —
   every matvec in data/matrix.py passes ``preferred_element_type=f32``).
4. ``const-bloat`` — baked-in constants past the spec's byte budget: a
   silent HBM + compile-time blowup shipped with every executable, usually
   a closure that should have been an argument.
5. ``retrace-hazard`` — weak-typed program inputs (a Python scalar passed
   where an array will later arrive retraces the program: weak_type is part
   of jit's cache key) and 0-d baked consts (a captured Python/numpy scalar
   — every new value is a new trace; pass it as an argument).

Rule 5's dynamic face is `TraceSignatureLog`: record the argument
signature of every call to a named program and `hazards()` reports pairs
that differ ONLY in weak_type — the avoidable-retrace pattern (same
shapes, same dtypes, a scalar that was sometimes Python and sometimes
array).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable

import jax
import numpy as np

from photon_tpu.analysis import walker


@dataclasses.dataclass(frozen=True)
class Violation:
    """One contract breach, ready for the human or --json report."""

    rule: str
    spec: str
    message: str
    where: str = ""  # eqn path inside the jaxpr, when site-specific

    def __str__(self) -> str:
        loc = f" [at {self.where}]" if self.where else ""
        return f"{self.spec}: ({self.rule}) {self.message}{loc}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class TracedContract:
    """A ContractSpec traced to its ClosedJaxpr, plus the example args the
    builder supplied (rule 5 inspects them)."""

    spec: object  # contracts.ContractSpec
    closed_jaxpr: object  # jax ClosedJaxpr
    example_args: tuple


# ------------------------------------------------------------------- rules
def rule_collective_budget(t: TracedContract) -> list[Violation]:
    out = []
    spec = t.spec
    budget = dict(spec.collectives or {})
    counts = walker.collective_counts(t.closed_jaxpr)
    for name in sorted(set(budget) | set(counts)):
        want, got = budget.get(name, 0), counts.get(name, 0)
        if got != want:
            sites = [s.where for s in walker.collective_sites(t.closed_jaxpr)
                     if s.name == name]
            out.append(Violation(
                "collective-budget", spec.name,
                f"traced {got} `{name}` against a budget of {want}",
                "; ".join(sites[:4])))
    if spec.forbid:
        forbidden = walker.count_primitives(t.closed_jaxpr, spec.forbid)
        for name, got in sorted(forbidden.items()):
            out.append(Violation(
                "collective-budget", spec.name,
                f"forbidden primitive `{name}` traced {got}x "
                "(this path is {}-free by construction)".format(name)))
    return out


def rule_transfer_lint(t: TracedContract) -> list[Violation]:
    if t.spec.allow_transfers:
        return []
    out = []
    for site in walker.sites(t.closed_jaxpr):
        if site.name not in walker.TRANSFER_PRIMITIVES:
            continue
        if site.loop_depth > 0:
            msg = (f"`{site.name}` inside a traced loop — a host "
                   "round-trip EVERY iteration")
        else:
            msg = (f"`{site.name}` inside a traced hot path — device code "
                   "should never re-enter the host")
        out.append(Violation("transfer-lint", t.spec.name, msg, site.where))
    return out


_WIDE_FLOATS = ("float64", "complex128")


def _aval_dtypes(eqn):
    for v in tuple(eqn.invars) + tuple(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield str(aval.dtype)


# Reductions whose accumulator inherits the operand dtype: bf16 here means
# bf16 accumulation (f32 is the policy — cast first or pass a wider dtype).
# jnp.sum upcasts f16/bf16 itself, but raw lax reductions, cumsum, scatter
# combiners and CROSS-DEVICE psums do not.
_ACCUMULATING = frozenset({
    "reduce_sum", "cumsum", "reduce_window_sum", "add_any", "scatter-add",
    "psum",
})


def rule_dtype_policy(t: TracedContract) -> list[Violation]:
    out = []
    spec = t.spec
    f64_hits = []
    for site in walker.sites(t.closed_jaxpr):
        dtypes = list(_aval_dtypes(site.eqn))
        if not spec.allow_f64 and any(d in _WIDE_FLOATS for d in dtypes):
            f64_hits.append(site.where)
        if site.name in _ACCUMULATING and dtypes \
                and dtypes[0] == "bfloat16":
            out.append(Violation(
                "dtype-policy", spec.name,
                f"`{site.name}` accumulates in bfloat16 (policy: bf16 "
                "inputs, f32 accumulation)", site.where))
        if site.name == "dot_general":
            ins = [str(v.aval.dtype) for v in site.eqn.invars]
            outd = str(site.eqn.outvars[0].aval.dtype)
            if "bfloat16" in ins and outd == "bfloat16":
                out.append(Violation(
                    "dtype-policy", spec.name,
                    "bf16 x bf16 -> bf16 dot_general (pass "
                    "preferred_element_type=float32: bf16 matmul must "
                    "accumulate f32 on the MXU)", site.where))
            elif getattr(spec, "require_f32_accum", False) \
                    and outd in ("bfloat16", "float16"):
                # the strict round-12 sparse pin: ANY narrow-accumulator
                # dot (even mixed-input) is a policy breach on this spec
                out.append(Violation(
                    "dtype-policy", spec.name,
                    f"dot_general accumulates {outd} on a "
                    "require_f32_accum program (every sparse dot/einsum "
                    "must output float32)", site.where))
        if getattr(spec, "require_f32_accum", False) \
                and site.name in _ACCUMULATING and dtypes \
                and dtypes[0] in ("float16",):
            out.append(Violation(
                "dtype-policy", spec.name,
                f"`{site.name}` accumulates in {dtypes[0]} on a "
                "require_f32_accum program", site.where))
    if f64_hits:
        out.append(Violation(
            "dtype-policy", spec.name,
            f"float64 leaked into {len(f64_hits)} equation(s) — every "
            "hot-path aval is f32/bf16 by policy",
            "; ".join(f64_hits[:4])))
    return out


def rule_const_bloat(t: TracedContract) -> list[Violation]:
    total = walker.const_bytes(t.closed_jaxpr)
    if total <= t.spec.max_const_bytes:
        return []
    top = sorted(
        ((getattr(c, "nbytes", None) or np.asarray(c).nbytes,
          getattr(c, "shape", ())) for c, _ in
         walker.iter_consts(t.closed_jaxpr)), reverse=True)[:3]
    detail = ", ".join(f"{s} ({b / 1e6:.1f} MB)" for b, s in top)
    return [Violation(
        "const-bloat", t.spec.name,
        f"{total / 1e6:.1f} MB of baked consts (budget "
        f"{t.spec.max_const_bytes / 1e6:.1f} MB) — biggest: {detail}. "
        "Closure-captured data ships with (and bloats) every executable; "
        "pass it as an argument")]


def rule_retrace_hazard(t: TracedContract) -> list[Violation]:
    if t.spec.allow_weak_args:
        return []
    out = []
    jaxpr = t.closed_jaxpr
    for i, v in enumerate(walker.as_jaxpr(jaxpr).invars):
        aval = getattr(v, "aval", None)
        if aval is not None and getattr(aval, "weak_type", False):
            out.append(Violation(
                "retrace-hazard", t.spec.name,
                f"input {i} is weak-typed (a Python scalar): weak_type is "
                "part of jit's cache key, so mixing scalar and array "
                "callers retraces — pass np.float32(...)/jnp arrays"))
    for c, path in walker.iter_consts(jaxpr):
        if getattr(c, "ndim", None) == 0 or (
                not hasattr(c, "ndim") and np.ndim(c) == 0):
            out.append(Violation(
                "retrace-hazard", t.spec.name,
                "captured scalar baked into the trace as a const — every "
                "new value is a fresh trace (and a fresh executable); "
                "pass it as an argument", "/".join(path)))
    return out


RULES: dict[str, Callable[[TracedContract], list[Violation]]] = {
    "collective-budget": rule_collective_budget,
    "transfer-lint": rule_transfer_lint,
    "dtype-policy": rule_dtype_policy,
    "const-bloat": rule_const_bloat,
    "retrace-hazard": rule_retrace_hazard,
}


# ------------------------------------------- trace-signature registry
def trace_signature(tree) -> tuple:
    """Hashable (structure, leaf avals) signature of a call's arguments —
    exactly the shape/dtype/weak_type triple jit keys its cache on."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sig = []
    for leaf in leaves:
        aval = jax.typeof(leaf)
        sig.append((tuple(getattr(aval, "shape", ())),
                    str(getattr(aval, "dtype", type(leaf).__name__)),
                    bool(getattr(aval, "weak_type", False))))
    return (str(treedef), tuple(sig))


def weak_type_drift(sig_a: tuple, sig_b: tuple) -> bool:
    """True when two signatures differ ONLY in weak_type flags — the
    avoidable retrace (same program, a scalar passed inconsistently)."""
    if sig_a == sig_b or sig_a[0] != sig_b[0]:
        return False
    la, lb = sig_a[1], sig_b[1]
    if len(la) != len(lb):
        return False
    saw_weak_flip = False
    for (sh_a, dt_a, wk_a), (sh_b, dt_b, wk_b) in zip(la, lb):
        if sh_a != sh_b or dt_a != dt_b:
            return False
        saw_weak_flip |= wk_a != wk_b
    return saw_weak_flip


class TraceSignatureLog:
    """Record per-program call signatures; report avoidable retraces.

    Usage: ``log.record("solve", (w, batch))`` at each callsite, then
    ``log.hazards()`` → [(name, sig_a, sig_b), ...] for every signature
    pair of one program that differs only by weak_type drift.
    """

    def __init__(self):
        # record() runs on every instrumented call site, including the
        # serving dispatch thread — the signature buckets are shared
        # state and take a lock (signature hashing stays outside it)
        self._lock = threading.Lock()
        self._seen: dict[str, list] = {}

    def record(self, name: str, args) -> tuple:
        sig = trace_signature(args)
        with self._lock:
            bucket = self._seen.setdefault(name, [])
            if sig not in bucket:
                bucket.append(sig)
        return sig

    def signatures(self, name: str) -> list:
        with self._lock:
            return list(self._seen.get(name, []))

    def hazards(self) -> list[tuple]:
        out = []
        with self._lock:
            snapshot = {k: list(v) for k, v in self._seen.items()}
        for name, sigs in snapshot.items():
            for i, a in enumerate(sigs):
                for b in sigs[i + 1:]:
                    if weak_type_drift(a, b):
                        out.append((name, a, b))
        return out
