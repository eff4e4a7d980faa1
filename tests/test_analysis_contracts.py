"""Tier-1 contract enforcement: every hot path's registered ContractSpec
must trace clean — THE test that turns the repo's implicit performance
model (one psum per evaluation, communication-free chunk partials,
scatter-free permuted layouts, f32 accumulation, no host exits, no retrace
hazards) into law that fails CI on drift.

Trace-only (jax.make_jaxpr): no compiles, so this module is cheap despite
walking every solver program in the repo. The CLI face of the same
registry is exercised end to end as a subprocess.
"""
import json
import os
import subprocess
import sys

import pytest

from photon_tpu.analysis import check_contract, trace_contract
from photon_tpu.analysis.registry import load_registry

pytestmark = pytest.mark.release_programs

_REGISTRY = load_registry()


def test_registry_is_broad_enough():
    """≥ 46 specs (round 19 added the request-tracing off-state pin:
    `serving_trace_off_is_free` — zero extra primitives + zero rung
    signature drift armed vs disarmed; PR 29 took the five Pallas-kernel
    specs out with the kernels) spanning every workload family."""
    assert len(_REGISTRY) >= 46
    tags = {t for spec in _REGISTRY.values() for t in spec.tags}
    for family in ("resident", "streamed", "mesh-streamed", "lane", "game",
                   "serving", "checkpoint", "profiling", "sparse",
                   "evaluation", "continual", "ingest", "tuning",
                   "multihost"):
        assert family in tags, f"no contract covers the {family} family"


def test_lane_tuner_specs_are_registered():
    """The round-16 acceptance pins, strict: the tuning lane dispatch
    (pow2 proposal padding never changes the screen program's trace
    signature) and the round budget (modeled cost enforced BEFORE
    dispatch; the halving tail's compact_rows + re-solve traces clean)
    both budget ZERO collectives with no transfer/f64 escape hatch."""
    for name in ("tuning_lane_dispatch", "tuning_round_budget"):
        spec = _REGISTRY[name]
        assert dict(spec.collectives or {}) == {}, name
        assert not spec.allow_transfers and not spec.allow_f64, name
        assert "tuning" in spec.tags and "lane" in spec.tags, name
        violations = check_contract(spec)
        assert violations == [], "\n".join(str(v) for v in violations)


def test_serving_trace_off_is_free_spec_is_registered():
    """The round-19 acceptance pin, strict: the serving rung program
    traced with request tracing DISARMED budgets zero collectives and
    forbids transfers (tracing is host bookkeeping around host queues —
    it cannot enter the program), and the builder itself raises if the
    collated rung arguments drift signature between armed and disarmed
    (the zero-retrace half)."""
    spec = _REGISTRY["serving_trace_off_is_free"]
    assert dict(spec.collectives or {}) == {}
    assert not spec.allow_transfers and not spec.allow_f64
    assert "serving" in spec.tags and "telemetry" in spec.tags
    violations = check_contract(spec)
    assert violations == [], "\n".join(str(v) for v in violations)


def test_roofline_closure_specs_are_registered():
    """The round-15 acceptance pins, strict: the donated ring's
    no-retrace invariance and the quantized rung budget ZERO collectives
    with no transfer/f64 escape hatch."""
    for name in ("mesh_stream_donated_no_retrace",
                 "serving_quantized_rung_invariance"):
        spec = _REGISTRY[name]
        assert dict(spec.collectives or {}) == {}, name
        assert not spec.allow_transfers and not spec.allow_f64, name
    assert "serving" in _REGISTRY["serving_quantized_rung_invariance"].tags
    assert "streamed" in _REGISTRY["mesh_stream_donated_no_retrace"].tags


def test_ingest_plane_spec_is_registered():
    """The round-14 acceptance pin: enabling the ingest plane introduces
    zero new trace signatures — the registered contract runs the cache's
    .npy round-trip through TraceSignatureLog against the direct chunk
    and refuses any signature divergence, and the traced streamed chunk
    program stays collective-free with the strict transfer/f64 policy."""
    spec = _REGISTRY["ingest_plane_chunk_invariance"]
    assert dict(spec.collectives or {}) == {}
    assert not spec.allow_transfers and not spec.allow_f64
    assert "ingest" in spec.tags and "streamed" in spec.tags
    violations = check_contract(spec)
    assert violations == [], "\n".join(str(v) for v in violations)


def test_blocked_ell_specs_are_registered():
    """The round-12 acceptance pins: BOTH X passes of the blocked-ELL
    layout forbid the FULL scatter family (not just combining scatters)
    and require f32 accumulation on every sparse dot/einsum, across the
    resident, lane, streamed-chunk, and mesh faces."""
    from photon_tpu.analysis.walker import (SCATTER_ADD_PRIMITIVES,
                                            SCATTER_PRIMITIVES)

    names = ("blocked_ell_x_passes", "blocked_ell_lane_x_passes",
             "streamed_blocked_ell_chunk_partials",
             "lane_blocked_ell_value_and_grad",
             "sharded_blocked_ell_value_and_grad")
    for name in names:
        spec = _REGISTRY[name]
        assert SCATTER_PRIMITIVES <= spec.forbid, name
        assert SCATTER_ADD_PRIMITIVES <= spec.forbid, name
        assert spec.require_f32_accum, name
        assert not spec.allow_transfers and not spec.allow_f64, name
    assert dict(_REGISTRY[
        "sharded_blocked_ell_value_and_grad"].collectives) == {"psum": 1}


def test_blocked_ell_contracts_hold_on_cpu_backend():
    """The blocked-ELL sparse programs' STRUCTURAL contracts
    (scatter-free, f32 accumulation, one psum) hold on the CPU backend —
    structure is a trace fact, independent of any backend's reduction
    order. (This whole module runs on the CPU backend; this test makes
    the blocked-ELL subset's zero-violation status an explicit named
    assertion.)"""
    import jax

    assert jax.default_backend() == "cpu"
    for name in ("blocked_ell_x_passes", "blocked_ell_lane_x_passes",
                 "streamed_blocked_ell_chunk_partials",
                 "lane_blocked_ell_value_and_grad",
                 "sharded_blocked_ell_value_and_grad",
                 "grouped_auc_scatter_free"):
        violations = check_contract(_REGISTRY[name])
        assert violations == [], \
            f"{name} drifted on the CPU backend:\n" + \
            "\n".join(str(v) for v in violations)


def test_game_e2e_specs_are_registered():
    """The round-13 pod-scale GAME acceptance pins: the streamed-mesh
    fixed-effect evaluation budgets EXACTLY one psum, the mesh RE bucket
    solve is collective-free, and the mesh blocked-ELL chunk/score
    programs forbid the full scatter family with f32 accumulation."""
    from photon_tpu.analysis.walker import SCATTER_PRIMITIVES

    assert dict(_REGISTRY["game_streamed_fixed_evaluation"].collectives) \
        == {"psum": 1}
    assert dict(_REGISTRY["game_re_mesh_bucket_solve"].collectives
                or {}) == {}
    for name in ("streamed_mesh_blocked_ell_chunk_partials",
                 "game_score_stream_chunk"):
        spec = _REGISTRY[name]
        assert dict(spec.collectives or {}) == {}
        assert SCATTER_PRIMITIVES <= spec.forbid, name
        assert spec.require_f32_accum, name
        assert not spec.allow_transfers and not spec.allow_f64, name


def test_continual_specs_are_registered():
    """The round-14 continual-flywheel acceptance pins: the compacted
    refresh solve (compact_rows gather + prior-threaded vmapped lanes)
    budgets ZERO collectives with no transfer/f64 escape hatch, and the
    no-retrace spec — whose BUILDER asserts signature equality across
    touched sets of different sizes — is registered and strict too."""
    for name in ("continual_re_refresh_solve",
                 "continual_refresh_no_retrace"):
        spec = _REGISTRY[name]
        assert dict(spec.collectives or {}) == {}
        assert not spec.allow_transfers and not spec.allow_f64, name
        assert "continual" in spec.tags, name


def test_checkpoint_off_specs_are_registered():
    """Disarmed checkpointing must add ZERO transfer/callback primitives
    to jitted solver programs: both checkpoint-off specs are strict
    (no transfers, no f64, empty collective budget) and forbid the
    transfer family outright — the acceptance pin of the elastic-runs
    round, mirroring telemetry_off_is_free."""
    from photon_tpu.analysis.walker import TRANSFER_PRIMITIVES

    for name in ("checkpoint_off_is_free", "checkpoint_off_tron_free"):
        spec = _REGISTRY[name]
        assert dict(spec.collectives or {}) == {}
        assert not spec.allow_transfers and not spec.allow_f64
        assert TRANSFER_PRIMITIVES <= spec.forbid


def test_ledger_off_spec_is_registered():
    """Disarmed profiling must add ZERO transfer/callback primitives to
    jitted solver programs — the attribution-ledger round's acceptance
    pin, same strictness as the telemetry/checkpoint off-specs."""
    from photon_tpu.analysis.walker import TRANSFER_PRIMITIVES

    spec = _REGISTRY["ledger_off_is_free"]
    assert dict(spec.collectives or {}) == {}
    assert not spec.allow_transfers and not spec.allow_f64
    assert TRANSFER_PRIMITIVES <= spec.forbid
    assert "profiling" in spec.tags


def test_checkpoint_selftest_cli_end_to_end():
    """`python -m photon_tpu.checkpoint --selftest --json` — the
    snapshot → kill → restore → bit-parity smoke — exits 0 with every
    check green (exit 1 on drift is the CI contract)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the CLI must self-provision its platform
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "photon_tpu.checkpoint", "--selftest",
         "--json"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["ok"] is True
    assert report["checks"]["resume_bit_identical"]["ok"] is True
    assert report["checks"]["mid_write_resume_bit_identical"]["ok"] is True


def test_serving_request_specs_are_registered():
    """The serving tier's per-request program is pinned: both heads
    (mean + margin), both strict — zero collectives, zero host exits."""
    for name in ("serving_request_program", "serving_request_margin"):
        spec = _REGISTRY[name]
        assert dict(spec.collectives or {}) == {}
        assert not spec.allow_transfers and not spec.allow_f64


def test_serving_overload_specs_are_registered():
    """The overload-round pins: the admission layer adds ZERO device-
    program changes (its builder raises on any signature divergence
    between admission on and off — traced by test_contract_holds), and
    a fleet replica's per-request path over an entity-range shard stays
    collective-free / host-exit-free / f64-free like the unsharded
    program."""
    for name in ("serving_admission_program_invariance",
                 "serving_fleet_request_path"):
        spec = _REGISTRY[name]
        assert dict(spec.collectives or {}) == {}
        assert not spec.allow_transfers and not spec.allow_f64
        assert "serving" in spec.tags


@pytest.mark.parametrize("name", sorted(_REGISTRY))
def test_contract_holds(name):
    spec = _REGISTRY[name]
    violations = check_contract(spec)
    assert violations == [], "\n".join(str(v) for v in violations)


def test_declared_collective_budgets_are_exact():
    """The budgets are EXACT pins, not ceilings: a spec declaring
    {"psum": 1} must actually trace one psum (drift DOWN — a collective
    disappearing — is also a contract change someone must look at)."""
    from photon_tpu.analysis import collective_counts

    checked = 0
    for spec in _REGISTRY.values():
        if spec.collectives:
            traced = trace_contract(spec)
            assert dict(collective_counts(traced.closed_jaxpr)) == \
                dict(spec.collectives), spec.name
            checked += 1
    assert checked >= 4  # the mesh/streamed psum pins exist


def test_cli_json_end_to_end():
    """`python -m photon_tpu.analysis --json` — the CI entry point —
    exits 0 with zero violations over the full registry."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the CLI must self-provision its platform
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "photon_tpu.analysis", "--json"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["ok"] is True
    assert report["n_specs"] >= 8
    assert report["n_violations"] == 0
