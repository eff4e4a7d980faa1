"""Central ``PHOTON_TPU_*`` environment-knob registry.

Every operator-facing environment variable the package reads is declared
ONCE here, with its one-line contract. Modules read raw values through
:func:`get_raw` (never ``os.environ`` directly — `python -m
photon_tpu.lint`'s ``env_knob_registry`` rule enforces both directions:
an undeclared knob read is a finding, and a declared knob nobody reads
is an orphan). Parsing stays with the single OWNER module named in each
doc line — the registry kills duplicated default-parsing, not the
owner's semantics.

``KNOB_DOCS`` is deliberately a pure literal: the lint rule reads it by
AST without importing jax (or this package).
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["KNOB_DOCS", "get_raw", "declared"]

KNOB_DOCS = {
    "PHOTON_TPU_PEAK_FLOPS": (
        "Modeled per-chip FLOP/s ceiling for roofline-utilization "
        "denominators (overrides the device_kind table's row). Owner: "
        "photon_tpu.profiling.ledger (resolve_peaks())."),
    "PHOTON_TPU_PEAK_BYTES_PER_S": (
        "Modeled per-chip HBM bytes/s ceiling for roofline-utilization "
        "denominators (overrides the device_kind table's row). Owner: "
        "photon_tpu.profiling.ledger (resolve_peaks())."),
    "PHOTON_TPU_LOG_LEVEL": (
        "Process-wide logging level override (a name like DEBUG or a "
        "number); beats every explicit photon_logger(level=) call. "
        "Owner: photon_tpu.utils.logging (_env_level())."),
    "PHOTON_TPU_TEST_CACHE_DIR": (
        "Tier-1 suite's persistent XLA compilation cache directory "
        "(empty string disables; default /tmp/photon_tpu_xla_test_cache)."
        " Owner: tests/conftest.py."),
    "PHOTON_TPU_COORDINATOR": (
        "Multi-process coordinator address (host:port) for "
        "jax.distributed — the launcher exports it to every child; set "
        "it by hand to join an externally-launched cluster. Owner: "
        "photon_tpu.parallel.mesh (initialize_distributed())."),
    "PHOTON_TPU_NUM_PROCESSES": (
        "Multi-process cluster size for jax.distributed (integer >= 1; "
        "read with PHOTON_TPU_COORDINATOR/PHOTON_TPU_PROCESS_ID). Owner: "
        "photon_tpu.parallel.mesh (initialize_distributed())."),
    "PHOTON_TPU_PROCESS_ID": (
        "This process's rank in the multi-process cluster (integer in "
        "[0, PHOTON_TPU_NUM_PROCESSES)). Owner: photon_tpu.parallel.mesh "
        "(initialize_distributed())."),
    "PHOTON_TPU_BARRIER_TIMEOUT_S": (
        "Multi-process barrier timeout in seconds (default 120): how "
        "long the checkpoint store's pre-manifest barrier waits for "
        "every process before failing the commit loudly. Owner: "
        "photon_tpu.checkpoint.store (_barrier())."),
}


def declared(name: str) -> bool:
    return name in KNOB_DOCS


def get_raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """``os.environ.get`` behind the registry: ``name`` must be declared
    in :data:`KNOB_DOCS` (an undeclared read raises — the same contract
    the lint rule enforces statically)."""
    if name not in KNOB_DOCS:
        raise KeyError(
            f"{name!r} is not a declared PHOTON_TPU_* knob — add it to "
            "photon_tpu.utils.env.KNOB_DOCS with a doc line first")
    return os.environ.get(name, default)
