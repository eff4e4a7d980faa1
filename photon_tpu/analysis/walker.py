"""Recursive jaxpr walker: the one traversal every contract rule shares.

photon-tpu's performance invariants (one psum per evaluation, no transfers
inside hot loops, f32 accumulation, no captured-scalar retraces) live in
the traced program, not in any single source file — so the checker walks
the jaxpr IR, the XLA analog of the reference Photon-ML auditing its Spark
plans for shuffle boundaries. The walker descends into every sub-jaxpr an
equation carries (`scan`/`while`/`cond` branches, `jit`, `shard_map`,
`custom_vjp`/`custom_jvp`, remat, ...): any param value that IS a jaxpr —
or a tuple/list of them, as `cond`'s ``branches`` is — is recursed into,
so new higher-order primitives are covered without enumeration.

Collectives are counted HERE, at trace level, so a contract needs no
compile. Under `shard_map`'s varying-axes tracking a `lax.psum` of varying
operands binds ``psum_invariant``, and a VARIADIC psum — the (value,
gradient) pair every evaluation closes with — binds one equation PER LEAF.
The law this repo pins is one all-reduce per evaluation: XLA's all-reduce
combiner merges such a run of adjacent, mutually independent, same-axes
reductions into ONE tuple ``all-reduce`` (counted on HLO compiled for the
described v5e:2x2 topology by tests/test_chip_compile.py, and on the chip
by ``chip_smoke.py --chips 4``). So the walker reports the run — not each
leaf — as one ``psum``: `Site.merged` marks the equations that ride the
run head's all-reduce, and the counters skip them.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Iterable, Iterator, Optional

import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

# Cross-device communication primitives (jax.lax.parallel binds).
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "pgather", "psum_invariant",
})
# `Site.name` reports a primitive under its canonical name: the psum of
# varying operands is still the design's ``psum``.
_CANONICAL_NAMES = {"psum_invariant": "psum"}
# Type casts shard_map inserts between the leaves of one variadic psum
# (invariant -> varying): they move no data and do not end a run.
_VARYING_CASTS = frozenset({"pvary"})

# Primitives that move data across the host/device boundary (or call back
# into Python) from INSIDE a traced program.
TRANSFER_PRIMITIVES = frozenset({
    "device_put", "pure_callback", "io_callback", "callback",
    "debug_callback",
})

# Combining scatters: the measured TPU wall the permuted/blocked-ELL
# layouts eliminate by construction (~12 ns/element scatter-add vs
# ~7 ns/index gather, docs/PERF.md) — pinned via `ContractSpec.forbid` on
# scatter-free paths. scatter-sub is jax's subtraction combiner (same
# read-modify-write lowering as scatter-add).
SCATTER_ADD_PRIMITIVES = frozenset({
    "scatter-add", "scatter-sub", "scatter-mul", "scatter-min",
    "scatter-max",
})

# The full family. NOTE: `.at[i].set(x)` with a scalar index traces to a
# plain `scatter` equation that XLA lowers to dynamic-update-slice, so
# whole-SOLVER programs forbid only SCATTER_ADD_PRIMITIVES (the
# performance fact), while single-evaluation programs can forbid the full
# family.
SCATTER_PRIMITIVES = SCATTER_ADD_PRIMITIVES | frozenset({
    "scatter", "scatter_apply",
})

# Irregular random-access READS — the other half of the scatter/gather
# taxonomy. Not forbidden anywhere (gathers are the ~7 ns/index GOOD case
# the blocked layouts are built on); profiling/model.py keys its
# random-access byte costing on this set so sparse-program rooflines are
# honest about per-index traffic instead of charging whole-table bytes.
GATHER_PRIMITIVES = frozenset({"gather", "dynamic_slice"})

# Bodies of these run many times per dispatch: a transfer inside is a
# per-iteration stall, not a one-off.
LOOP_PRIMITIVES = frozenset({"scan", "while"})


def as_jaxpr(jaxpr) -> Jaxpr:
    """The underlying Jaxpr of a ClosedJaxpr (identity on a plain Jaxpr)."""
    return jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr


def sub_jaxprs(eqn) -> Iterator:
    """Every jaxpr carried by one equation's params, in param order.

    Yields ClosedJaxpr | Jaxpr. Handles scalar params (`jit`/`scan`'s
    ``jaxpr``, `while`'s ``cond_jaxpr``/``body_jaxpr``, `shard_map`'s body,
    `custom_vjp_call_jaxpr`'s ``fun_jaxpr``) and sequence params (`cond`'s
    ``branches``) uniformly.
    """
    for v in eqn.params.values():
        for u in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(u, (ClosedJaxpr, Jaxpr)):
                yield u


@dataclasses.dataclass(frozen=True)
class Site:
    """One equation plus where the walk found it."""

    eqn: object
    path: tuple[str, ...]  # primitive names of the enclosing eqns
    loop_depth: int  # enclosing scan/while bodies (×N execution)
    # a later leaf of one variadic psum: rides the run head's all-reduce
    # (module docstring), so the counters skip it
    merged: bool = False

    @property
    def name(self) -> str:
        name = self.eqn.primitive.name
        return _CANONICAL_NAMES.get(name, name)

    @property
    def where(self) -> str:
        return "/".join(self.path + (self.name,))


def sites(jaxpr, _path: tuple = (), _loops: int = 0) -> Iterator[Site]:
    """Depth-first walk over every equation of ``jaxpr`` and all its
    sub-jaxprs. Accepts a ClosedJaxpr or Jaxpr."""
    run_key, run_outs = None, set()  # the open variadic-psum run
    for eqn in as_jaxpr(jaxpr).eqns:
        name = eqn.primitive.name
        merged = False
        if name == "psum_invariant":
            key = (eqn.params.get("axes"),
                   eqn.params.get("axis_index_groups"))
            merged = key == run_key and not any(
                v in run_outs for v in eqn.invars
                if not hasattr(v, "val"))  # Literals are unhashable
            if not merged:
                run_key, run_outs = key, set()
            run_outs.update(eqn.outvars)
        elif name not in _VARYING_CASTS:
            run_key, run_outs = None, set()
        yield Site(eqn, _path, _loops, merged)
        deeper = _loops + (1 if name in LOOP_PRIMITIVES else 0)
        for sub in sub_jaxprs(eqn):
            yield from sites(sub, _path + (name,), deeper)


def count_primitives(jaxpr, names: Optional[Iterable[str]] = None) -> Counter:
    """Occurrence count per primitive name over the whole recursive walk;
    ``names`` restricts the census (None counts everything)."""
    wanted = None if names is None else frozenset(names)
    out: Counter = Counter()
    for site in sites(jaxpr):
        if not site.merged and (wanted is None or site.name in wanted):
            out[site.name] += 1
    return out


def collective_counts(jaxpr) -> Counter:
    """How many of each collective the program traces to, a variadic
    psum counted once (module docstring) — the jaxpr-level communication
    pattern."""
    return count_primitives(jaxpr, COLLECTIVE_PRIMITIVES)


def hlo_all_reduce_count(hlo_text: str) -> int:
    """all-reduce ops in compiled HLO text (``compiled.as_text()``) — where
    the one-all-reduce-per-evaluation law is settled (module docstring)."""
    return sum(" all-reduce(" in line or " all-reduce-start(" in line
               for line in hlo_text.splitlines())


def collective_sites(jaxpr) -> list[Site]:
    return [s for s in sites(jaxpr)
            if s.name in COLLECTIVE_PRIMITIVES and not s.merged]


def iter_consts(jaxpr, _path: tuple = ()) -> Iterator[tuple]:
    """(const, path) for every constant baked into ``jaxpr`` or any
    sub-ClosedJaxpr (sub-jaxpr consts are usually hoisted, but remat and
    custom-derivative wrappers can keep their own)."""
    if isinstance(jaxpr, ClosedJaxpr):
        for c in jaxpr.consts:
            yield c, _path
    for eqn in as_jaxpr(jaxpr).eqns:
        for sub in sub_jaxprs(eqn):
            yield from iter_consts(sub, _path + (eqn.primitive.name,))


def const_bytes(jaxpr) -> int:
    """Total bytes of baked-in constants — silent HBM + compile-time
    payload shipped with every executable of this program."""
    total = 0
    for c, _ in iter_consts(jaxpr):
        total += getattr(c, "nbytes", None) or np.asarray(c).nbytes
    return total
