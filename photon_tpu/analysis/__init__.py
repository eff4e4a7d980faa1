"""Jaxpr static analysis: the performance contracts of every hot path,
checked at trace level on every PR.

photon-tpu's speed rests on invariants the code states only implicitly —
ONE psum per streamed evaluation, communication-free chunk partials,
scatter-free permuted layouts, f32 accumulation, no host round-trips or
captured-scalar retraces inside jitted programs. The reference Photon-ML
audited the analogous facts on Spark's plan inspection (shuffle
boundaries); our IR is the jaxpr, and this package is the auditor:

- `walker`   — recursive traversal over ClosedJaxpr (descends scan/while/
               cond/jit/shard_map/custom_vjp sub-jaxprs).
- `rules`    — the five contract rules (collective budget, transfer lint,
               dtype policy, const bloat, retrace hazard) + the
               trace-signature registry.
- `contracts`— ContractSpec + register_contract + the check engine.
- `registry` — imports every hot-path module so their registrations run;
               NOT imported here to keep this package importable from
               those same modules (they register at import time).

CLI: ``python -m photon_tpu.analysis [--json]`` traces the full registry
and reports violations (exit 1 on any). Docs: docs/ANALYSIS.md.
"""
from photon_tpu.analysis.walker import (  # noqa: F401
    COLLECTIVE_PRIMITIVES,
    LOOP_PRIMITIVES,
    SCATTER_ADD_PRIMITIVES,
    SCATTER_PRIMITIVES,
    TRANSFER_PRIMITIVES,
    Site,
    collective_counts,
    collective_sites,
    const_bytes,
    count_primitives,
    hlo_all_reduce_count,
    sites,
    sub_jaxprs,
)
from photon_tpu.analysis.rules import (  # noqa: F401
    RULES,
    TracedContract,
    TraceSignatureLog,
    Violation,
    trace_signature,
    weak_type_drift,
)
from photon_tpu.analysis.contracts import (  # noqa: F401
    REGISTRY,
    ContractSpec,
    check_contract,
    check_registry,
    register_contract,
    summarize,
    trace_contract,
)

__all__ = [
    "COLLECTIVE_PRIMITIVES", "LOOP_PRIMITIVES", "SCATTER_ADD_PRIMITIVES",
    "SCATTER_PRIMITIVES",
    "TRANSFER_PRIMITIVES", "Site", "collective_counts", "collective_sites",
    "const_bytes", "count_primitives", "hlo_all_reduce_count", "sites",
    "sub_jaxprs",
    "RULES", "TracedContract", "TraceSignatureLog", "Violation",
    "trace_signature", "weak_type_drift",
    "REGISTRY", "ContractSpec", "check_contract", "check_registry",
    "register_contract", "summarize", "trace_contract",
]
