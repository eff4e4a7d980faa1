"""The composed flagship: `run_training` end to end at ≥10M rows from
Avro files on disk (VERDICT r4 item 2 — BASELINE-config-4 evidence
through the PRODUCT path, not synthetic in-memory arrays).

Upstream GameTrainingDriver runs its 100M-row ads-CTR job from HDFS:
read → index → validate → train (fixed + per-user + per-item) → validate
AUC → save. This drives the same pipeline: block-encoded Avro on disk
(benches/_flagship_data.py), streaming ingestion auto-tripped by header
row counts, both random effects, validation AUC from the driver's own
evaluator — and reports the per-phase timings PERF.md records.

Run: python benches/flagship_e2e.py [--rows 10000000] [--runs 2]
Data files cache under --data-dir and are reused across runs (the second
process run measures the persistent-compilation-cache story end to end).

Round 6 — the 100M-row regime (BASELINE config 4's actual number):
`--rows 100000000` exceeds the per-chip HBM budget (est. ~17.6 GB
device-resident vs the 16 GiB default of --hbm-budget-gb), so the driver
auto-trips into STREAMED-OBJECTIVE mode: the fixed shard stays on host in
chunks and every fixed-effect L-BFGS iteration accumulates value+gradient
over streamed device chunks (the literal treeAggregate analog,
optim/streamed.py); random-effect shards and scalars stay resident. Peak
HBM is O(chunk + RE data + solver state), not O(dataset).
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import argparse
import contextlib
import time

import numpy as np


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=10_000_000)
    p.add_argument("--val-rows", type=int, default=1_000_000)
    p.add_argument("--users", type=int, default=100_000)
    p.add_argument("--items", type=int, default=50_000)
    p.add_argument("--sweeps", type=int, default=2)
    p.add_argument("--data-dir", default="/tmp/flagship_data")
    p.add_argument("--out-dir", default="/tmp/flagship_out")
    p.add_argument("--runs", type=int, default=1,
                   help="driver invocations (2nd is jit-warm in-process)")
    p.add_argument("--fixed-only", action="store_true",
                   help="also fit the fixed effect alone for the AUC gap")
    p.add_argument("--hbm-budget-gb", type=float, default=16.0,
                   help="per-chip HBM budget for the streamed-objective "
                        "auto-trip (16 = v5e; --rows 100000000 exceeds it "
                        "and engages the out-of-HBM path)")
    p.add_argument("--objective-chunk-rows", type=int, default=1 << 20,
                   help="host chunk height for streamed-objective shards")
    p.add_argument("--mesh", type=int, default=0,
                   help="shard the fit over an N-device mesh (0 = single "
                        "device). With the streamed objective engaged, "
                        "every host chunk row-shards across the mesh — the "
                        "pod-scale out-of-HBM regime — and the auto-trip "
                        "budgets against the POOLED HBM (per-chip budget "
                        "x N)")
    p.add_argument("--game-e2e-leg", action="store_true",
                   help="also run bench.py's game_e2e leg (the composed "
                        "pod-scale GAME fit: streamed+mesh blocked-ELL "
                        "fixed effect, entity-sharded random-effect "
                        "buckets, host margin-cache score exchange — vs "
                        "the resident single-chip fit) and print its "
                        "JSON line. The full-driver form of the same "
                        "regime is --rows past the HBM budget plus "
                        "--mesh N")
    p.add_argument("--game-re-leg", action="store_true",
                   help="also run bench.py's game_re leg (the pipelined + "
                        "straggler-compacted random-effect block loop vs "
                        "the sequential one, skewed entity sizes) and "
                        "print its JSON line")
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable crash-consistent snapshots of the run's "
                        "solver state in this directory "
                        "(photon_tpu/checkpoint; relative paths land "
                        "under the run's out dir). A killed run rerun "
                        "with --resume restores the last committed "
                        "snapshot and finishes bit-identically")
    p.add_argument("--resume", action="store_true",
                   help="restore from --checkpoint-dir's last committed "
                        "snapshot (also appends to the run's existing "
                        "telemetry JSONL instead of truncating it)")
    p.add_argument("--checkpoint-leg", action="store_true",
                   help="also run bench.py's checkpoint_overhead leg "
                        "(streamed-dense solve with async snapshots "
                        "every K evaluations vs none; rows·iters/s "
                        "delta + snapshot bytes/s) and print its JSON "
                        "line")
    p.add_argument("--xprof-dir", default=None,
                   help="wrap each driver run in telemetry.device_trace, "
                        "writing an XProf capture here, so the "
                        "telemetry spans (mirrored to TraceAnnotation) and "
                        "the attribution ledger's phases line up with the "
                        "device timeline on real TPUs")
    p.add_argument("--serving-leg", action="store_true",
                   help="also run bench.py's serving_qps leg (closed-loop "
                        "online scoring over a zipf entity mix through "
                        "the photon_tpu/serving micro-batching "
                        "dispatcher; QPS + p50/p95/p99 latency, with the "
                        "never-retraces assertion) and print its JSON "
                        "line")
    p.add_argument("--ingest-leg", action="store_true",
                   help="also run bench.py's ingest_throughput leg (cold "
                        "worker-pool Avro decode + cache build vs the "
                        "decode-once mmap'd chunk cache, plus the "
                        "stall-driven prefetch's upload-stall share of a "
                        "streamed pass) and print its JSON line")
    p.add_argument("--tuning-e2e-leg", action="store_true",
                   help="also run bench.py's tuning_e2e leg (the "
                        "lane-batched cost-aware tuner: 256 configs "
                        "through GP-proposed fixed-chunk lane rounds "
                        "with successive halving and warm survivor "
                        "re-solves, vs the point-at-a-time tuner "
                        "architecture — with the two-signature "
                        "no-retrace bound asserted live) and print its "
                        "JSON line")
    p.add_argument("--serving-slo-leg", action="store_true",
                   help="also run bench.py's open-loop serving_slo leg "
                        "(fixed arrival-rate sweep with the admission "
                        "policy armed: SLO verdict line + the graceful-"
                        "degradation curve past saturation — shed "
                        "fraction rises, served p99 stays bounded, zero "
                        "lost futures) and print its JSON line")
    args = p.parse_args()

    import _flagship_data as fd
    from photon_tpu.drivers.train import TrainingParams, run_training

    mesh = None
    if args.mesh:
        from photon_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(n_devices=args.mesh)

    os.makedirs(args.data_dir, exist_ok=True)
    train_path = os.path.join(args.data_dir, f"train_{args.rows}.avro")
    val_path = os.path.join(args.data_dir, f"val_{args.val_rows}.avro")
    truth = fd.planted_truth(args.users, args.items, seed=0)
    for path, rows, seed in ((train_path, args.rows, 1),
                             (val_path, args.val_rows, 2)):
        if os.path.exists(path):
            print(f"reusing {path} ({os.path.getsize(path) / 1e9:.2f} GB)")
            continue
        t0 = time.perf_counter()
        fd.write_flagship_avro(path, rows, args.users, args.items, truth,
                               seed=seed)
        dt = time.perf_counter() - t0
        print(f"wrote {path}: {rows} rows, "
              f"{os.path.getsize(path) / 1e9:.2f} GB in {dt:.0f}s "
              f"({rows / dt:,.0f} rec/s)", flush=True)

    def params(coords, tag):
        return TrainingParams(
            train_path=train_path,
            validation_path=val_path,
            output_dir=os.path.join(args.out_dir, tag),
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_resume=args.resume,
            feature_shards=fd.FEATURE_SHARDS,
            coordinates=coords,
            entity_fields=["userId", "itemId"],
            n_sweeps=args.sweeps,
            streaming=None,  # tri-state auto: 10M rows must trip it
            # tri-state auto: 100M rows exceed the budget and must trip
            # the out-of-HBM streamed objective; 10M stays resident
            streamed_objective=None,
            hbm_budget_bytes=int(args.hbm_budget_gb * 2**30),
            objective_chunk_rows=args.objective_chunk_rows,
            evaluators=["AUC"],
        )

    import json

    from photon_tpu import profiling, telemetry

    for run in range(args.runs):
        # each driver invocation records a telemetry run (spans for the
        # driver phases, stall/eval/retrace counters, live iteration
        # events from any streamed solve — JSONL under the run's out
        # dir, compact report embedded in the JSON line printed below)
        # AND an attribution ledger (photon_tpu/profiling: per-program
        # modeled FLOPs/bytes vs measured wall, compile accounting —
        # ledger.json beside the telemetry JSONL)
        jsonl = os.path.join(args.out_dir, f"game_r{run}",
                             "telemetry.jsonl")
        ledger_json = os.path.join(args.out_dir, f"game_r{run}",
                                   "ledger.json")
        # a --resume rerun APPENDS to the dead run's event log (the sink
        # repairs a crash-torn tail record first) instead of truncating
        trun = telemetry.start_run(f"flagship_r{run}", jsonl_path=jsonl,
                                   append=args.resume)
        profiling.start_ledger(f"flagship_r{run}")
        t0 = time.perf_counter()
        with (telemetry.device_trace(args.xprof_dir) if args.xprof_dir
              else contextlib.nullcontext()):
            out = run_training(params(fd.COORDINATES, f"game_r{run}"),
                               mesh=mesh)
        total = time.perf_counter() - t0
        telemetry.finish_run()
        ledger_report = profiling.finish_ledger()
        # photon: allow(durable_write, bench-run report artifact — nothing resumes from it; a torn file just re-runs the bench)
        with open(ledger_json, "w") as fh:
            json.dump(ledger_report, fh)
        cluster_json = None
        if args.mesh:
            # mesh runs also get the cross-rank view beside the ledger:
            # this process's event log as rank 0 (a multi-process launch
            # drops its p<k>.jsonl files into the same directory and the
            # same call merges them all), spans wall-clock aligned
            from photon_tpu.telemetry.aggregate import (aggregate_cluster,
                                                        rank_files)

            cluster_json = os.path.join(args.out_dir, f"game_r{run}",
                                        "cluster_report.json")
            rank_map = {0: jsonl}
            rank_map.update(rank_files(os.path.dirname(jsonl)))
            cluster = aggregate_cluster(rank_map)
            cluster["timeline"] = cluster["timeline"][:256]
            # photon: allow(durable_write, bench-run report artifact — nothing resumes from it; a torn file just re-runs the bench)
            with open(cluster_json, "w") as fh:
                json.dump(cluster, fh)
        phases = {k: round(v, 1) for k, v in sorted(out.timings.items())}
        print(f"run {run}: total {total:.0f}s  phases {phases}", flush=True)
        print(f"run {run}: validation AUC {out.best.validation_score:.4f} "
              f"({args.sweeps} sweeps, fixed + per_user + per_item)",
              flush=True)
        print(json.dumps({"run": run, "total_s": round(total, 1),
                          "telemetry_jsonl": jsonl,
                          "ledger_json": ledger_json,
                          **({"cluster_report_json": cluster_json}
                             if cluster_json else {}),
                          "telemetry": trun.report_compact()}),
              flush=True)

    if args.fixed_only:
        t0 = time.perf_counter()
        out = run_training(params({"fixed": fd.COORDINATES["fixed"]},
                                  "fixed_only"), mesh=mesh)
        print(f"fixed-only: total {time.perf_counter() - t0:.0f}s  "
              f"AUC {out.best.validation_score:.4f}", flush=True)

    if args.game_e2e_leg:
        # bench.py's game_e2e leg verbatim: the composed pod-scale GAME
        # fit measured against its resident twin, beside the full-driver
        # flagship run above.
        import bench

        ge = bench.game_e2e_problem()
        res = bench.run_game_e2e(ge, streamed=False)
        stm = bench.run_game_e2e(ge, streamed=True)
        print(json.dumps({
            "leg": "game_e2e",
            "rows_iters_per_sec_aggregate":
                round(stm["rows_iters_per_sec"], 1),
            "resident_rows_iters_per_sec":
                round(res["rows_iters_per_sec"], 1),
            "streamed_over_resident":
                round(stm["rows_iters_per_sec"]
                      / res["rows_iters_per_sec"], 3),
            "n_chips": stm["n_chips"],
            "beyond_resident_ok": bool(stm.get("beyond_resident_ok",
                                               False))}), flush=True)

    if args.game_re_leg:
        # The SAME leg bench.py's JSON line carries (one problem
        # definition, two numbers): the random-effect block-loop rate with
        # and without the round-8 pipeline + straggler compaction.
        import bench

        ds_gr, rows_gr = bench.game_re_problem()
        seq = bench.run_game_re(ds_gr, rows_gr, pipelined=False)
        pipe = bench.run_game_re(ds_gr, rows_gr, pipelined=True)
        print(json.dumps({
            "leg": "game_re",
            "rows_iters_per_sec_per_chip": round(pipe, 1),
            "sequential_rows_iters_per_sec_per_chip": round(seq, 1),
            "speedup_vs_sequential": round(pipe / seq, 3)}), flush=True)

    if args.checkpoint_leg:
        # bench.py's checkpoint_overhead leg verbatim: the elasticity tax
        # of async snapshots on the streamed-dense solve, beside the
        # flagship run they protect.
        import bench

        ck = bench.run_checkpoint_overhead()
        print(json.dumps({
            "leg": "checkpoint_overhead",
            "rows_iters_per_sec": round(ck["rows_iters_per_sec"], 1),
            "baseline_rows_iters_per_sec":
                round(ck["baseline_rows_iters_per_sec"], 1),
            "overhead_pct": round(ck["overhead_pct"], 2),
            "cadence_evals": ck["cadence_evals"],
            "snapshots": ck["snapshots"],
            "snapshot_bytes_per_sec":
                round(ck["snapshot_bytes_per_sec"], 1)}), flush=True)

    if args.ingest_leg:
        # bench.py's ingest_throughput leg verbatim: the round-14 data
        # plane measured beside the flagship run it feeds.
        import bench

        ing = bench.run_ingest(bench.ingest_problem())
        print(json.dumps({
            "leg": "ingest_throughput",
            "cold_rows_per_sec": round(ing["cold_rows_per_sec"], 1),
            "cached_rows_per_sec": round(ing["cached_rows_per_sec"], 1),
            "cached_over_cold": round(ing["cached_over_cold"], 2),
            "upload_stall_pct": round(ing["upload_stall_pct"], 2),
            "stalled_passes": ing["stalled_passes"]}), flush=True)

    if args.tuning_e2e_leg:
        # bench.py's tuning_e2e leg verbatim: the lane-batched tuner's
        # configs-per-wall-clock measured against the point-at-a-time
        # architecture, beside the flagship runs it would tune.
        import bench

        tu = bench.run_tuning_e2e(bench.tuning_problem())
        print(json.dumps({
            "leg": "tuning_e2e",
            "configs_per_sec": round(tu["configs_per_sec"], 2),
            "sequential_configs_per_sec":
                round(tu["sequential_configs_per_sec"], 2),
            "speedup_vs_sequential":
                round(tu["speedup_vs_sequential"], 2),
            "n_configs": tu["n_configs"],
            "n_rounds": tu["n_rounds"]}), flush=True)

    if args.serving_leg or args.serving_slo_leg:
        # bench.py's serving legs verbatim: the online-scoring regime
        # (many tiny micro-batched requests) measured and retrace-checked
        # beside the training flagship it serves.
        import bench

        sv_ladder, sv_pool = bench.serving_problem()
        capacity = None
        if args.serving_leg:
            stats = bench.run_serving(sv_ladder, sv_pool)
            capacity = stats["qps"]
            print(json.dumps({
                "leg": "serving_qps",
                "qps": round(stats["qps"], 1),
                "p50_ms": round(stats["p50_ms"], 3),
                "p95_ms": round(stats["p95_ms"], 3),
                "p99_ms": round(stats["p99_ms"], 3),
                "n_requests": stats["n_requests"]}), flush=True)
        if args.serving_slo_leg:
            # the open-loop overload face: fixed arrival rates, admission
            # policy armed, SLO verdict + degradation curve. Calibrates
            # its own capacity unless the closed-loop leg just ran.
            slo = bench.run_serving_slo(sv_ladder, sv_pool,
                                        capacity_qps=capacity)
            print(json.dumps({
                "leg": "serving_slo",
                "sustained_qps": round(slo["sustained_qps"], 1),
                "p99_ms": round(slo["p99_ms"], 3),
                "overload_p99_ms": round(slo["overload_p99_ms"], 3),
                "overload_shed_pct": slo["overload_shed_pct"],
                "lost_futures": slo["lost_futures"],
                "ok": slo["ok"],
                "verdict": slo["verdict"],
                "curve": slo["curve"]}), flush=True)


if __name__ == "__main__":
    main()
