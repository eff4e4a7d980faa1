"""The attribution-ledger round's tier-1 coverage.

Three planes:

- `sentinel` — the noise-aware bench gate's acceptance matrix, on
  SYNTHETIC histories (pure python, no jax): a genuine regression is
  caught, normal best-of noise passes, a brand-new leg is admitted
  without tripping, a missing/short history degrades to warn-only, and
  lower-is-better legs gate in the right direction — plus the
  `bench.py --gate` CLI end to end (exit 1 on a synthetically regressed
  trajectory, exit 0 on the repo's real one: THE acceptance bars).
- `model` — static cost estimates are the arithmetic they claim:
  dot_general FLOPs from dimension numbers, scan-length multipliers,
  while-trip hints, collective payload bytes.
- `ledger` — attribution + utilization ∈ (0, 1] on a real instrumented
  streamed solve, compile accounting, detached-state no-ops, and the
  `python -m photon_tpu.profiling --report --json` CLI (the acceptance
  criterion's exact command) as a subprocess.

The umbrella selfcheck (7 subprocesses) is marked ``slow`` — tier-1
runs ``-m 'not slow'`` and each sub-CLI is already exercised on its own.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_tpu import profiling
from photon_tpu.profiling import sentinel

# Deliberately NOT release_programs-marked: this module compiles only a
# handful of tiny single-device programs (the 96×5 streamed solve shares
# shapes with test_telemetry's), and the marker's module-teardown
# jax.clear_caches() would force every LATER module to recompile —
# tens of seconds against the tier-1 870 s budget.

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ sentinel
def _wrap(legs, metric=None, value=None):
    parsed = {"legs": dict(legs)}
    if metric is not None:
        parsed["metric"], parsed["value"] = metric, value
    return {"n": 5, "rc": 0, "parsed": parsed}


def _history(leg="dense_rate", base=1e8, jitter=(1.0, 1.02, 0.98, 1.01, 0.99)):
    return [(f"BENCH_r{i:02d}.json", {leg: base * j})
            for i, j in enumerate(jitter, start=1)]


class TestSentinel:
    def test_regression_is_caught(self):
        hist = _history()
        v = sentinel.gate({"dense_rate": 0.5e8}, hist)["dense_rate"]
        assert v.status == "regressed" and v.z > sentinel.DEFAULT_Z

    def test_normal_noise_passes(self):
        hist = _history()
        for wobble in (0.95, 1.0, 1.05, 1.25):
            v = sentinel.gate({"dense_rate": 1e8 * wobble},
                              hist)["dense_rate"]
            assert v.status == "ok", (wobble, v.to_json())

    def test_improvement_never_trips(self):
        v = sentinel.gate({"dense_rate": 5e8}, _history())["dense_rate"]
        assert v.status == "ok"

    def test_new_leg_admitted_without_tripping(self):
        verdicts = sentinel.gate({"dense_rate": 1e8, "brand_new_leg": 1.0},
                                 _history())
        assert verdicts["brand_new_leg"].status == "new"
        assert verdicts["dense_rate"].status == "ok"

    def test_short_history_degrades_to_warn_only(self):
        short = _history(jitter=(1.0, 1.01))  # < MIN_HISTORY rounds
        v = sentinel.gate({"dense_rate": 0.1e8}, short)["dense_rate"]
        assert v.status == "new"  # admitted, never "regressed"

    def test_missing_history_degrades_to_warn_only(self):
        v = sentinel.gate({"dense_rate": 0.1e8}, [])["dense_rate"]
        assert v.status == "no-history"

    def test_lower_better_legs_gate_in_the_right_direction(self):
        hist = _history(leg="serving_p99_ms", base=2.0)
        worse = sentinel.gate({"serving_p99_ms": 9.0}, hist)
        better = sentinel.gate({"serving_p99_ms": 0.5}, hist)
        assert worse["serving_p99_ms"].status == "regressed"
        assert better["serving_p99_ms"].status == "ok"

    def test_changed_sparse_legs_admit_correctly(self):
        """The round-12 blocked-ELL swap as the sentinel sees it: a big
        IMPROVEMENT on the existing sparse throughput legs is 'ok' (the
        bad side is one-sided), the brand-new pad-waste leg admits as
        'new', and pad waste gates LOWER-better once it has history."""
        leg = "sparse10m_single_lane_rows_iters_per_sec_per_chip"
        hist = _history(leg=leg, base=1.87e7)
        verdicts = sentinel.gate(
            {leg: 5 * 1.87e7, "sparse10m_tail_pad_waste": 0.11}, hist)
        assert verdicts[leg].status == "ok"          # 5x is not a regression
        assert verdicts[leg].z < 0                   # ... and z says "better"
        assert verdicts["sparse10m_tail_pad_waste"].status == "new"
        # pad waste is a lower-better cost once history exists
        assert sentinel.lower_is_better("sparse10m_tail_pad_waste")
        whist = _history(leg="sparse10m_tail_pad_waste", base=0.1)
        worse = sentinel.gate({"sparse10m_tail_pad_waste": 0.9},
                              whist)["sparse10m_tail_pad_waste"]
        assert worse.status == "regressed"
        better = sentinel.gate({"sparse10m_tail_pad_waste": 0.01},
                               whist)["sparse10m_tail_pad_waste"]
        assert better.status == "ok"

    def test_multihost_legs_admit_correctly(self):
        """The round-17 spine legs as the sentinel sees them: the priced
        DCN wire bill gates LOWER-better (a grown psum payload means
        something besides the gradient started riding DCN), the launch
        wall gates lower-better via "_ms", and the verified process
        count is a topology fact the sentinel must never gate."""
        assert sentinel.lower_is_better("multihost_e2e_dcn_bytes_per_eval")
        assert sentinel.lower_is_better("multihost_e2e_launch_4p_wall_ms")
        legs = sentinel.leg_values({"legs": {
            "multihost_e2e_dcn_bytes_per_eval": 196.0,
            "multihost_e2e_launch_4p_wall_ms": 9000.0,
            "multihost_e2e_n_processes": 4,
        }})
        assert "multihost_e2e_n_processes" not in legs
        assert legs["multihost_e2e_dcn_bytes_per_eval"] == 196.0
        hist = _history(leg="multihost_e2e_dcn_bytes_per_eval", base=196.0)
        worse = sentinel.gate(
            {"multihost_e2e_dcn_bytes_per_eval": 24576.0},
            hist)["multihost_e2e_dcn_bytes_per_eval"]
        assert worse.status == "regressed"
        same = sentinel.gate(
            {"multihost_e2e_dcn_bytes_per_eval": 196.0},
            hist)["multihost_e2e_dcn_bytes_per_eval"]
        assert same.status == "ok"

    def test_serving_kernel_legs_admit_correctly(self):
        """The round-20 serving_quantized_kernels legs as the sentinel
        sees them: both admit as 'new' beside existing serving history
        (the same-fingerprint rule still applies — `_history` pairs are
        env-None series), QPS gates higher-better, and the p99 gates
        LOWER-better via "_ms" once it has history — the fused kernel's
        whole claim is the tail."""
        hist = _history(leg="serving_quantized_p99_ms", base=2.0)
        verdicts = sentinel.gate(
            {"serving_quantized_p99_ms": 2.0,
             "serving_quantized_kernels_qps": 900.0,
             "serving_quantized_kernels_p99_ms": 1.4}, hist)
        assert verdicts["serving_quantized_kernels_qps"].status == "new"
        assert verdicts["serving_quantized_kernels_p99_ms"].status == "new"
        assert sentinel.lower_is_better("serving_quantized_kernels_p99_ms")
        assert not sentinel.lower_is_better("serving_quantized_kernels_qps")
        khist = _history(leg="serving_quantized_kernels_p99_ms", base=1.4)
        worse = sentinel.gate(
            {"serving_quantized_kernels_p99_ms": 6.0},
            khist)["serving_quantized_kernels_p99_ms"]
        assert worse.status == "regressed"
        better = sentinel.gate(
            {"serving_quantized_kernels_p99_ms": 0.7},
            khist)["serving_quantized_kernels_p99_ms"]
        assert better.status == "ok"

    def test_layout_split_legs_are_excluded(self):
        """hot/tail split + width-bucket counts are layout CONFIG facts —
        a retuned d_dense moves them by design, so they never gate."""
        verdicts = sentinel.gate(
            {"sparse10m_hot_nnz_frac": 0.7, "sparse10m_tail_nnz_frac": 0.3,
             "sparse10m_ell_width_buckets": 3, "dense_rate": 1e8},
            _history())
        assert "sparse10m_hot_nnz_frac" not in verdicts
        assert "sparse10m_tail_nnz_frac" not in verdicts
        assert "sparse10m_ell_width_buckets" not in verdicts

    def test_config_legs_are_not_gated(self):
        hist = _history(leg="streamed_mesh_n_chips", base=8.0)
        verdicts = sentinel.gate({"streamed_mesh_n_chips": 4.0}, hist)
        assert "streamed_mesh_n_chips" not in verdicts

    def test_ingest_leg_admission(self):
        """The round-14 ingest_throughput legs as the sentinel sees them:
        brand-new legs admit without tripping the gate that merges them;
        the throughput legs + the cached/cold ratio gate higher-better,
        the upload-stall share and the stalled-pass count LOWER-better
        (more stalling at the same workload = the plane got slower);
        once history exists a cached-rate collapse regresses."""
        verdicts = sentinel.gate(
            {"ingest_throughput_cold_rows_per_sec": 3.0e4,
             "ingest_throughput_cached_rows_per_sec": 9.0e5,
             "ingest_throughput_cached_over_cold": 30.0,
             "ingest_throughput_upload_stall_pct": 0.8,
             "ingest_stalled_passes": 0.0,
             "dense_rate": 1e8},
            _history())
        for leg in ("ingest_throughput_cold_rows_per_sec",
                    "ingest_throughput_cached_rows_per_sec",
                    "ingest_throughput_cached_over_cold",
                    "ingest_throughput_upload_stall_pct",
                    "ingest_stalled_passes"):
            assert verdicts[leg].status == "new", leg
        assert verdicts["dense_rate"].status == "ok"
        # directions
        assert not sentinel.lower_is_better(
            "ingest_throughput_cached_rows_per_sec")
        assert not sentinel.lower_is_better(
            "ingest_throughput_cached_over_cold")
        assert sentinel.lower_is_better(
            "ingest_throughput_upload_stall_pct")
        assert sentinel.lower_is_better("ingest_stalled_passes")
        # with history: a cached-rate collapse regresses, a stall-share
        # rise regresses, improvements never trip
        hist = _history(leg="ingest_throughput_cached_rows_per_sec",
                        base=9.0e5)
        worse = sentinel.gate(
            {"ingest_throughput_cached_rows_per_sec": 1.0e5}, hist)
        assert worse["ingest_throughput_cached_rows_per_sec"].status == \
            "regressed"
        shist = _history(leg="ingest_throughput_upload_stall_pct", base=1.0)
        worse = sentinel.gate(
            {"ingest_throughput_upload_stall_pct": 60.0}, shist)
        assert worse["ingest_throughput_upload_stall_pct"].status == \
            "regressed"
        better = sentinel.gate(
            {"ingest_throughput_upload_stall_pct": 0.01}, shist)
        assert better["ingest_throughput_upload_stall_pct"].status == "ok"

    def test_kernel_leg_admission(self):
        """The round-15 kernel-variant leg as the sentinel sees it: a
        brand-new leg admits without tripping the gate that merges it,
        the backend string never becomes a leg, and with history the
        rate gates higher-better like any throughput leg."""
        verdicts = sentinel.gate(
            {"blocked_ell_kernel_rows_iters_per_sec_per_chip": 1.0e7,
             "dense_rate": 1e8},
            _history())
        assert verdicts[
            "blocked_ell_kernel_rows_iters_per_sec_per_chip"].status == \
            "new"
        assert verdicts["dense_rate"].status == "ok"
        legs = sentinel.leg_values(
            {"legs": {"blocked_ell_kernel_backend": "cpu-interpret",
                      "blocked_ell_kernel_rows_iters_per_sec_per_chip":
                          1.0e7}})
        assert "blocked_ell_kernel_backend" not in legs
        assert "blocked_ell_kernel_rows_iters_per_sec_per_chip" in legs
        hist = _history(
            leg="blocked_ell_kernel_rows_iters_per_sec_per_chip",
            base=1.0e7)
        worse = sentinel.gate(
            {"blocked_ell_kernel_rows_iters_per_sec_per_chip": 1.0e6},
            hist)
        assert worse[
            "blocked_ell_kernel_rows_iters_per_sec_per_chip"].status == \
            "regressed"

    def test_serving_quantized_leg_admission(self):
        """The round-15 quantized-rung legs as the sentinel sees them:
        new legs admit, QPS gates higher-better, p99 and the measured
        probe margin maxdiff LOWER-better — a louder quantization at
        the same throughput is a regression."""
        verdicts = sentinel.gate(
            {"serving_quantized_qps": 2.1e4,
             "serving_quantized_p99_ms": 4.5,
             "serving_quantized_margin_maxdiff": 0.02,
             "dense_rate": 1e8},
            _history())
        for leg in ("serving_quantized_qps", "serving_quantized_p99_ms",
                    "serving_quantized_margin_maxdiff"):
            assert verdicts[leg].status == "new", leg
        assert not sentinel.lower_is_better("serving_quantized_qps")
        assert sentinel.lower_is_better("serving_quantized_p99_ms")
        assert sentinel.lower_is_better("serving_quantized_margin_maxdiff")
        hist = _history(leg="serving_quantized_margin_maxdiff", base=0.02)
        worse = sentinel.gate(
            {"serving_quantized_margin_maxdiff": 0.5}, hist)
        assert worse["serving_quantized_margin_maxdiff"].status == \
            "regressed"
        better = sentinel.gate(
            {"serving_quantized_margin_maxdiff": 0.001}, hist)
        assert better["serving_quantized_margin_maxdiff"].status == "ok"

    def test_game_e2e_leg_admission(self):
        """The round-13 game_e2e legs as the sentinel sees them: the new
        throughput legs admit as 'new' without tripping the gate that
        merges them, the chip count is a config leg (never gated), the
        beyond-resident bool is skipped by leg_values, and once history
        exists the aggregate gates like any throughput leg."""
        verdicts = sentinel.gate(
            {"game_e2e_rows_iters_per_sec_aggregate": 2.7e5,
             "game_e2e_resident_rows_iters_per_sec": 4.6e5,
             "game_e2e_streamed_over_resident": 0.6,
             "game_e2e_n_chips": 8.0,
             "dense_rate": 1e8},
            _history())
        assert verdicts[
            "game_e2e_rows_iters_per_sec_aggregate"].status == "new"
        assert verdicts[
            "game_e2e_resident_rows_iters_per_sec"].status == "new"
        assert verdicts["game_e2e_streamed_over_resident"].status == "new"
        assert "game_e2e_n_chips" not in verdicts
        assert verdicts["dense_rate"].status == "ok"
        # bools never become legs (beyond_resident_ok is an existence
        # proof, not a performance quantity)
        legs = sentinel.leg_values(
            {"legs": {"game_e2e_beyond_resident_ok": True,
                      "game_e2e_rows_iters_per_sec_aggregate": 2.7e5}})
        assert "game_e2e_beyond_resident_ok" not in legs
        assert "game_e2e_rows_iters_per_sec_aggregate" in legs
        # with history, the aggregate gates higher-better
        hist = _history(leg="game_e2e_rows_iters_per_sec_aggregate",
                        base=2.7e5)
        worse = sentinel.gate(
            {"game_e2e_rows_iters_per_sec_aggregate": 0.5e5}, hist)
        assert worse[
            "game_e2e_rows_iters_per_sec_aggregate"].status == "regressed"

    def test_refresh_e2e_leg_admission(self):
        """The round-14 continual legs as the sentinel sees them: the new
        speedup/wall legs admit as 'new' without tripping the gate that
        merges them, the touched fraction is a config fact (never
        gated), the wall legs gate LOWER-better once history exists, and
        the speedup gates higher-better."""
        verdicts = sentinel.gate(
            {"refresh_e2e_speedup_vs_full_retrain": 120.0,
             "refresh_e2e_wall_ms": 850.0,
             "refresh_e2e_full_retrain_wall_ms": 95000.0,
             "refresh_e2e_touched_frac": 0.02,
             "dense_rate": 1e8},
            _history())
        assert verdicts[
            "refresh_e2e_speedup_vs_full_retrain"].status == "new"
        assert verdicts["refresh_e2e_wall_ms"].status == "new"
        assert verdicts["refresh_e2e_full_retrain_wall_ms"].status == "new"
        assert "refresh_e2e_touched_frac" not in verdicts
        assert verdicts["dense_rate"].status == "ok"
        # the refresh wall is a latency-like cost: lower is better
        assert sentinel.lower_is_better("refresh_e2e_wall_ms")
        whist = _history(leg="refresh_e2e_wall_ms", base=800.0)
        worse = sentinel.gate({"refresh_e2e_wall_ms": 9000.0},
                              whist)["refresh_e2e_wall_ms"]
        better = sentinel.gate({"refresh_e2e_wall_ms": 200.0},
                               whist)["refresh_e2e_wall_ms"]
        assert worse.status == "regressed" and better.status == "ok"
        # the speedup is a rate: a collapse toward 1x regresses
        shist = _history(leg="refresh_e2e_speedup_vs_full_retrain",
                         base=120.0)
        collapsed = sentinel.gate(
            {"refresh_e2e_speedup_vs_full_retrain": 2.0},
            shist)["refresh_e2e_speedup_vs_full_retrain"]
        assert collapsed.status == "regressed"

    def test_serving_slo_leg_admission(self):
        """The overload-round serving_slo legs as the sentinel sees them:
        new legs admit without tripping the gate that merges them; the
        direction map gates sustained QPS higher-better, p99 and shed
        percentage LOWER-better (more shedding at the same offered rate
        means the tier got slower); the SLO target is a chosen config
        bar (excluded) and the bool verdict is skipped by type."""
        verdicts = sentinel.gate(
            {"serving_slo_sustained_qps": 6500.0,
             "serving_slo_p99_ms": 9.0,
             "serving_slo_overload_p99_ms": 130.0,
             "serving_slo_overload_shed_pct": 55.0,
             "serving_slo_target_ms": 50.0,
             "dense_rate": 1e8},
            _history())
        for leg in ("serving_slo_sustained_qps", "serving_slo_p99_ms",
                    "serving_slo_overload_p99_ms",
                    "serving_slo_overload_shed_pct"):
            assert verdicts[leg].status == "new", leg
        assert "serving_slo_target_ms" not in verdicts  # config bar
        assert verdicts["dense_rate"].status == "ok"
        legs = sentinel.leg_values(
            {"legs": {"serving_slo_ok": True,
                      "serving_slo_sustained_qps": 6500.0}})
        assert "serving_slo_ok" not in legs  # bool verdict, not a leg
        # directions
        assert not sentinel.lower_is_better("serving_slo_sustained_qps")
        assert sentinel.lower_is_better("serving_slo_p99_ms")
        assert sentinel.lower_is_better("serving_slo_overload_shed_pct")
        # a sustained-QPS collapse regresses; shedding MORE at the same
        # offered rate regresses; shedding less is an improvement
        qhist = _history(leg="serving_slo_sustained_qps", base=6500.0)
        assert sentinel.gate({"serving_slo_sustained_qps": 800.0}, qhist)[
            "serving_slo_sustained_qps"].status == "regressed"
        shist = _history(leg="serving_slo_overload_shed_pct", base=40.0)
        assert sentinel.gate({"serving_slo_overload_shed_pct": 90.0},
                             shist)["serving_slo_overload_shed_pct"
                                    ].status == "regressed"
        assert sentinel.gate({"serving_slo_overload_shed_pct": 5.0},
                             shist)["serving_slo_overload_shed_pct"
                                    ].status == "ok"

    def test_observability_leg_admission(self):
        """The round-19 observability legs as the sentinel sees them:
        the staleness gauge (rows-changed -> servable seconds) and the
        slowest-exemplar latency admit as 'new' and gate LOWER-better
        (staler models and fatter tails are the regressions these legs
        exist to catch); the nested exemplar list riding the serving_slo
        sub-dict is structure, not a leg."""
        verdicts = sentinel.gate(
            {"refresh_e2e_staleness_s": 4.2,
             "serving_slo_exemplar_slowest_ms": 31.0,
             "dense_rate": 1e8},
            _history())
        assert verdicts["refresh_e2e_staleness_s"].status == "new"
        assert verdicts["serving_slo_exemplar_slowest_ms"].status == "new"
        assert verdicts["dense_rate"].status == "ok"
        # directions: both are freshness/latency costs
        assert sentinel.lower_is_better("refresh_e2e_staleness_s")
        assert sentinel.lower_is_better("serving_slo_exemplar_slowest_ms")
        # a model going stale regresses; getting fresher is ok
        shist = _history(leg="refresh_e2e_staleness_s", base=4.0)
        assert sentinel.gate({"refresh_e2e_staleness_s": 300.0}, shist)[
            "refresh_e2e_staleness_s"].status == "regressed"
        assert sentinel.gate({"refresh_e2e_staleness_s": 1.0}, shist)[
            "refresh_e2e_staleness_s"].status == "ok"
        # exemplar dicts, the health snapshot, and verdict strings are
        # invisible to leg_values — only scalar legs gate
        legs = sentinel.leg_values(
            {"legs": {"refresh_e2e_staleness_s": 4.2,
                      "serving_slo": {"exemplars": [
                          {"total_ms": 31.0, "slowest_hop": "queue_wait"}]},
                      "health": {"verdict": "OK"}}})
        assert legs == {"refresh_e2e_staleness_s": 4.2}

    def test_tuning_e2e_leg_admission(self):
        """The round-16 lane-tuner legs as the sentinel sees them: the
        configs-per-second rates and the speedup admit as 'new' and gate
        higher-better (a collapse toward point-at-a-time parity is the
        regression the leg exists to catch); the config count is a
        chosen budget, never gated."""
        verdicts = sentinel.gate(
            {"tuning_e2e_configs_per_sec": 62.0,
             "tuning_e2e_sequential_configs_per_sec": 5.9,
             "tuning_e2e_speedup_vs_sequential": 10.7,
             "tuning_e2e_n_configs": 256.0,
             "dense_rate": 1e8},
            _history())
        for leg in ("tuning_e2e_configs_per_sec",
                    "tuning_e2e_sequential_configs_per_sec",
                    "tuning_e2e_speedup_vs_sequential"):
            assert verdicts[leg].status == "new", leg
            assert not sentinel.lower_is_better(leg)
        assert "tuning_e2e_n_configs" not in verdicts  # config budget
        assert verdicts["dense_rate"].status == "ok"
        shist = _history(leg="tuning_e2e_speedup_vs_sequential", base=10.7)
        assert sentinel.gate({"tuning_e2e_speedup_vs_sequential": 1.1},
                             shist)["tuning_e2e_speedup_vs_sequential"
                                    ].status == "regressed"
        rhist = _history(leg="tuning_e2e_configs_per_sec", base=62.0)
        assert sentinel.gate({"tuning_e2e_configs_per_sec": 90.0},
                             rhist)["tuning_e2e_configs_per_sec"
                                    ].status == "ok"

    def test_leg_values_flattens_headline_and_skips_dups(self):
        legs = sentinel.leg_values({
            "metric": "headline", "value": 2.0,
            "legs": {"a": 1.0, "a_vs_baseline": 0.1, "b": True}})
        assert legs == {"headline": 2.0, "a": 1.0}

    def test_history_loader_tolerates_null_and_garbage(self, tmp_path):
        (tmp_path / "BENCH_r01.json").write_text('{"parsed": null}')
        (tmp_path / "BENCH_r02.json").write_text("not json")
        (tmp_path / "BENCH_r03.json").write_text(
            json.dumps(_wrap({"a": 1.0})))
        hist = sentinel.load_history(str(tmp_path))
        assert hist == [("BENCH_r03.json", {"a": 1.0}, None)]

    def test_same_env_slices_single_environment_series(self):
        """A leg's history series is single-environment: ``same_env``
        keeps only rounds whose host fingerprint matches the candidate's
        (the r06 TPU→CPU exclusion policy, automated at the r10
        container-host swap). Legacy pairs/rounds with no fingerprint
        form their own env-``None`` series."""
        hist = [("r1", {"rate": 1.00e8}, "hostA"),
                ("r2", {"rate": 1.01e8}, "hostA"),
                ("r3", {"rate": 0.99e8}, "hostA"),
                ("r4", {"rate": 1.02e8}, None)]
        assert [h[0] for h in sentinel.same_env(hist, "hostA")] == \
            ["r1", "r2", "r3"]
        assert sentinel.same_env(hist, None) == [hist[3]]
        assert sentinel.same_env(hist, "hostB") == []
        # bare (name, legs) pairs (the test/legacy shape) are env None
        assert sentinel.same_env(_history(), None) == _history()
        # a collapse judged against a DIFFERENT host's rounds is
        # warn-only, not a regression — nothing is comparable
        v = sentinel.gate({"rate": 0.3e8},
                          sentinel.same_env(hist, "hostB"))
        assert v["rate"].status == "no-history"

    def test_host_env_fingerprint_shape(self):
        env = sentinel.host_env()
        assert isinstance(env, str) and "/nproc=" in env
        assert env == sentinel.host_env()  # deterministic on one host

    def test_gate_main_env_break_restarts_gating(self, tmp_path, capsys):
        """End-to-end host break: a collapsed round on a SWAPPED host
        fingerprint admits warn-only (new series), and the same collapse
        three rounds INTO the new series trips the gate again."""
        self._write_rounds(tmp_path, [1e8, 1.01e8, 0.99e8, 1.02e8])

        def _env_round(i, v):
            d = _wrap({"rate": v})
            d["parsed"]["env"] = "other-cpu/nproc=1"
            (tmp_path / f"BENCH_r{i:02d}.json").write_text(json.dumps(d))

        _env_round(5, 0.4e8)
        rc = sentinel.gate_main(["--gate"], bench_dir=str(tmp_path))
        out = capsys.readouterr().out
        assert rc == 0
        doc = json.loads(out.strip().splitlines()[-1])
        assert doc["ok"] and doc["env"] == "other-cpu/nproc=1"
        assert doc["n_history_rounds"] == 0  # the old host's rounds
        # rebuild MIN_HISTORY strength on the new host, then collapse
        for i, v in enumerate((1e8, 1.01e8, 0.99e8), start=5):
            _env_round(i, v)
        _env_round(8, 0.4e8)
        rc = sentinel.gate_main(["--gate"], bench_dir=str(tmp_path))
        out = capsys.readouterr().out
        assert rc == 1 and "rate: regressed" in out
        doc = json.loads(out.strip().splitlines()[-1])
        assert doc["n_history_rounds"] == 3  # the old host still sliced

    def _write_rounds(self, tmp_path, values, leg="rate"):
        for i, v in enumerate(values, start=1):
            (tmp_path / f"BENCH_r{i:02d}.json").write_text(
                json.dumps(_wrap({leg: v})))

    def test_gate_main_exit_codes(self, tmp_path, capsys):
        # regressed trajectory: last round collapses -> exit 1, with a
        # one-line verdict per leg in the output
        self._write_rounds(tmp_path, [1e8, 1.01e8, 0.99e8, 1.02e8, 0.4e8])
        rc = sentinel.gate_main(["--gate"], bench_dir=str(tmp_path))
        out = capsys.readouterr().out
        assert rc == 1 and "rate: regressed" in out
        doc = json.loads(out.strip().splitlines()[-1])
        assert doc["regressed"] == ["rate"] and not doc["ok"]
        # healthy trajectory -> exit 0
        self._write_rounds(tmp_path, [1e8, 1.01e8, 0.99e8, 1.02e8, 1.05e8])
        assert sentinel.gate_main(["--gate"],
                                  bench_dir=str(tmp_path)) == 0

    def test_gate_real_trajectory_passes(self, capsys):
        """The gate over the repo's own BENCH_r0*.json history exits 0
        (the acceptance bar) — in-process; the bench.py CLI wiring is
        covered once by the synthetic-regression subprocess below."""
        rc = sentinel.gate_main(["--gate"], bench_dir=_REPO)
        out = capsys.readouterr().out
        assert rc == 0, out
        doc = json.loads(out.strip().splitlines()[-1])
        assert doc["ok"] and doc["schema"] == sentinel.SCHEMA_VERSION

    def test_bench_gate_cli_synthetic_regression(self, tmp_path):
        """bench.py --gate --gate-dir <regressed trajectory>: exit 1."""
        self._write_rounds(tmp_path, [1e8, 1.0e8, 1.01e8, 0.99e8, 0.3e8])
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench.py"), "--gate",
             "--gate-dir", str(tmp_path)],
            capture_output=True, text=True, env=env, timeout=120)
        assert proc.returncode == 1, proc.stdout + proc.stderr


# ------------------------------------------------------------------- model
class TestStaticModel:
    def test_dot_general_flops(self):
        import jax
        import jax.numpy as jnp

        x = jnp.zeros((32, 8), jnp.float32)
        w = jnp.zeros((8, 4), jnp.float32)
        cost = profiling.estimate_fn(lambda a, b: a @ b, (x, w))
        assert cost.dot_flops == 2 * 32 * 8 * 4
        # operand-traffic proxy: inputs + outputs of the matmul
        assert cost.bytes >= (32 * 8 + 8 * 4 + 32 * 4) * 4
        del jax

    def test_elementwise_and_transcendental(self):
        import jax.numpy as jnp

        x = jnp.zeros((64,), jnp.float32)
        cost = profiling.estimate_fn(lambda a: jnp.tanh(a * 2.0), (x,))
        assert cost.transcendentals == 64
        assert cost.flops >= 128  # mul + tanh

    def test_scan_length_multiplies(self):
        import jax
        import jax.numpy as jnp

        def f(xs):
            return jax.lax.scan(lambda c, x: (c + x, x * 2.0),
                                jnp.zeros((16,)), xs)

        cost = profiling.estimate_fn(f, (jnp.zeros((5, 16)),))
        # 5 trips x (add 16 + mul 16) = 160 elementwise FLOPs
        assert cost.flops == 5 * 32

    def test_while_trip_hint(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            return jax.lax.while_loop(lambda c: c[1] < 3,
                                      lambda c: (c[0] * 2.0, c[1] + 1),
                                      (x, 0))

        x = jnp.zeros((16,), jnp.float32)
        c1 = profiling.estimate_fn(f, (x,), while_trips=1)
        c10 = profiling.estimate_fn(f, (x,), while_trips=10)
        assert c1.while_loops == 1 and c1.lower_bound
        assert not c10.lower_bound
        assert c10.flops > c1.flops  # body cost scales with the hint

    def test_gather_costed_per_slice_not_per_table(self):
        """Round 12: a w-gather over a big table charges per-index granule
        traffic (the honest sparse cost), NOT the whole table's bytes."""
        import jax.numpy as jnp

        from photon_tpu.profiling.model import GATHER_GRANULE_BYTES

        d, m = 100_000, 64
        table = jnp.zeros((d,), jnp.float32)
        idx = jnp.zeros((m,), jnp.int32)
        cost = profiling.estimate_fn(lambda t, i: t[i], (table, idx))
        table_bytes = d * 4
        # scalar slices: m granules on the random side
        assert cost.gather_bytes == m * GATHER_GRANULE_BYTES
        assert cost.bytes < table_bytes  # the table is NOT charged
        # index + output move too
        assert cost.bytes >= cost.gather_bytes + m * 4

    def test_wide_gather_slices_charge_slice_bytes(self):
        import jax.numpy as jnp

        d, g, m = 1000, 64, 16  # 256-byte slices > the 32 B granule
        table = jnp.zeros((d, g), jnp.float32)
        idx = jnp.zeros((m,), jnp.int32)
        cost = profiling.estimate_fn(lambda t, i: t[i], (table, idx))
        assert cost.gather_bytes == m * g * 4

    def test_collective_payload_bytes(self):
        import jax

        fn = lambda x: jax.lax.psum(x, "i")  # noqa: E731
        closed = jax.make_jaxpr(fn, axis_env=[("i", 4)])(
            np.zeros((128,), np.float32))
        cost = profiling.estimate_jaxpr(closed)
        assert cost.collective_bytes == 128 * 4

    def test_quantized_dot_charges_storage_width(self):
        import jax.numpy as jnp

        q = np.zeros((256,), np.int8)
        s = np.float32(0.5)
        x = np.zeros((64, 256), np.float32)

        def quant_dot(q, s, x):
            return x @ (q.astype(jnp.float32) * s)

        c = profiling.estimate_fn(quant_dot, (q, s, x))
        assert c.narrowed_bytes == 256 * 3  # int8 charged 1 B, not 4

        def f32_dot(w, x):
            return x @ w

        c2 = profiling.estimate_fn(f32_dot, (np.zeros(256, np.float32), x))
        assert c2.narrowed_bytes == 0
        # the row-wise serving-rung pattern narrows through the gather +
        # per-row scale multiply too
        def rung(qm, sc, ids, xr):
            rows = qm[ids].astype(jnp.float32) * sc[ids][:, None]
            return jnp.einsum("nd,nd->n", xr, rows)

        c3 = profiling.estimate_fn(
            rung, (np.zeros((100, 8), np.int8), np.zeros(100, np.float32),
                   np.zeros(16, np.int32), np.zeros((16, 8), np.float32)))
        assert c3.narrowed_bytes == 16 * 8 * 3


# ------------------------------------------------------------------- ledger
class TestLedger:
    def test_detached_is_noop(self):
        assert profiling.current_ledger() is None
        assert not profiling.enabled()
        assert not profiling.needs_note("anything")
        with profiling.measure("p", "ph") as m:
            assert m is None
        profiling.attribute("p", "ph", 1.0)  # no-op, no error
        profiling.record_signature("p", (1.0,))

    def test_attribution_and_utilization(self):
        import jax.numpy as jnp

        with profiling.ledger("t", peaks=(1e9, 1e9)) as led:
            x = jnp.zeros((64, 64), jnp.float32)
            led.note_program("mm", lambda a: a @ a, (x,))
            led.attribute("mm", "phase", 0.01, calls=10)
            rep = led.report()
        (entry,) = rep["attribution"]
        assert entry["program"] == "mm" and entry["calls"] == 10
        assert entry["flops_modeled"] == 10 * 2 * 64 ** 3
        assert 0.0 < entry["utilization"] <= 1.0
        assert entry["bound"] in ("compute", "bandwidth")
        # the note's trace enters both compile accounts
        assert rep["programs"]["mm"]["retraces"] == 1
        assert rep["compile"]["wall_s"] > 0.0

    def test_utilization_clamped_into_unit_interval(self):
        import jax.numpy as jnp

        with profiling.ledger("t", peaks=(1.0, 1.0)) as led:  # absurd peaks
            x = jnp.zeros((8, 8), jnp.float32)
            led.note_program("mm", lambda a: a @ a, (x,))
            led.attribute("mm", "phase", 1e-6)
            entry = led.report()["attribution"][0]
        assert entry["utilization"] == 1.0

    def test_dispatch_books_compile_on_new_signature_only(self):
        import jax.numpy as jnp

        with profiling.ledger("t") as led:
            x = jnp.zeros((4,), jnp.float32)
            with led.dispatch("prog", (x,)):
                pass
            with led.dispatch("prog", (x,)):  # same signature: no retrace
                pass
            with led.dispatch("prog", (jnp.zeros((8,), jnp.float32),)):
                pass
            rep = led.report()
        prog = rep["programs"]["prog"]
        assert prog["retraces"] == 2
        entry = rep["attribution"][0]
        assert entry["phase"] == "dispatch" and entry["calls"] == 3

    def test_note_error_is_contained(self):
        with profiling.ledger("t") as led:
            led.note_program("bad", lambda: 1 / 0, ())
            rep = led.report()
        assert "ZeroDivisionError" in rep["programs"]["bad"]["note_error"]

    def test_instrumented_streamed_solve(self):
        """The tentpole wiring end to end IN-PROCESS: a streamed-dense
        train_glm under an attached ledger yields per-program entries
        with static estimates, measured durations, and utilization in
        (0, 1] — and zero ledger entries when detached."""
        from photon_tpu.data.dataset import chunk_batch, make_batch
        from photon_tpu.models.training import train_glm
        from photon_tpu.ops.losses import TaskType
        from photon_tpu.optim.config import OptimizerConfig
        from photon_tpu.optim.regularization import l2

        rng = np.random.default_rng(0)
        X = rng.normal(size=(96, 5)).astype(np.float32)
        y = (rng.uniform(size=96) < 0.5).astype(np.float32)
        cb = chunk_batch(make_batch(X, y), 32)
        cfg = OptimizerConfig(max_iters=4, tolerance=1e-7, reg=l2(),
                              reg_weight=0.1, history=3)
        with profiling.ledger("solve") as led:
            train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
            rep = led.report()
        entries = [e for e in rep["attribution"]
                   if e["program"].startswith("streamed.")]
        assert len(entries) >= 2  # init + direction at minimum
        for e in entries:
            assert e["seconds"] > 0.0
            assert e["flops_modeled"] > 0.0 and e["bytes_modeled"] > 0.0
            assert 0.0 < e["utilization"] <= 1.0
        assert rep["compile"]["retraces"] >= 1

    def test_report_cli_json(self):
        """`python -m photon_tpu.profiling --report --json` — THE
        acceptance command — on a small streamed-dense run: every
        streamed attribution entry carries static FLOP/byte estimates,
        a measured duration, and a utilization fraction in (0, 1]."""
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # the CLI self-provisions its platform
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-m", "photon_tpu.profiling", "--report",
             "--json", "--rows", "2048", "--chunk-rows", "512"],
            capture_output=True, text=True, env=env, cwd=_REPO,
            timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout.strip().splitlines()[-1])
        entries = [e for e in doc["ledger"]["attribution"]
                   if e["program"].startswith("streamed.")]
        assert entries, doc["ledger"]["attribution"]
        for e in entries:
            assert e["seconds"] > 0.0
            assert e["flops_modeled"] > 0.0 and e["bytes_modeled"] > 0.0
            assert 0.0 < e["utilization"] <= 1.0
        assert doc["ledger"]["compile"]["retraces"] >= 1
        # the gate verdicts ride along (the repo has a BENCH history)
        assert doc["gate"]


@pytest.mark.slow
def test_umbrella_selfcheck_cli():
    """`python -m photon_tpu --selfcheck --json`: every per-package
    selftest — including the pod-scale GAME e2e smoke (tiny rows,
    mesh 2) and the continual-flywheel loop — aggregates into one
    verdict."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "photon_tpu", "--selfcheck", "--json"],
        capture_output=True, text=True, env=env, timeout=1800)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["ok"]
    from photon_tpu.__main__ import SUITES

    assert set(doc["suites"]) == {name for name, _ in SUITES}
    assert set(doc["suites"]) >= {"analysis", "lint", "telemetry",
                                  "serving", "checkpoint", "profiling",
                                  "game", "continual", "ingest"}
    assert doc["suites"]["game"]["ok"]
    assert doc["suites"]["continual"]["ok"]
    assert doc["suites"]["lint"]["ok"]
