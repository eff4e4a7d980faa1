#!/usr/bin/env python3
"""Prove one cell in ONE chip call: a cold run (compiles), the repeat runs
in sets with the same seeds, and one traced run — each a process of its own
running BENCHMARK.json's command, so they share the persistent compile
cache and nothing else.

    chiprun -- python3 benchmark/prove.py <cell> [--seconds S] [--runs 6]

Every run's lines land in ``<out>/runs.jsonl`` (default
``chiprun_out/prove/<cell>/``), the spreads in ``<out>/summary.json``. A
spread is the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median; the bound
to set is about five times the widest spread over the cells. This file
never imports jax: a parent that touched it would hold the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# large and small, one above 2**31: the driver's seeds are large
SEEDS = [2147483659, 1000003, 3000000019, 42, 987654321, 2500000001]
KEEP_TRACE_BYTES = 40 << 20


def json_lines(text: str) -> list:
    out = []
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def one_run(command, cell, seed, seconds, trace, timeout) -> dict:
    argv = command + ["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(argv, cwd=ROOT, capture_output=True,
                              text=True, timeout=timeout)
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc = 124
        out, err = ((s.decode() if isinstance(s, bytes) else s or "")
                    for s in (e.stdout, e.stderr))
    lines = json_lines(out)
    result = lines[-1] if rc == 0 and lines and "metrics" in lines[-1] \
        else None
    return {"seed": seed, "trace": trace, "rc": rc,
            "wall_s": time.perf_counter() - t0, "result": result,
            "log": lines[:-1] if result else lines,
            "stderr_tail": "" if rc == 0 else err[-4000:]}


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def summarize(records) -> dict:
    """Per metric: each set's values, median and spread; the second set's
    median against the first's; five times the widest spread."""
    sets: dict = {}
    for r in records:
        if r["kind"] == "repeat" and r["result"]:
            for name, m in r["result"]["metrics"].items():
                sets.setdefault(name, {}).setdefault(r["set"], []).append(
                    m["value"])
    out = {}
    for name, by_set in sets.items():
        per = {str(k): {"values": v, "median": statistics.median(v),
                        "spread": spread(v) if len(v) >= 2 else None}
               for k, v in sorted(by_set.items())}
        spreads = [p["spread"] for p in per.values()
                   if p["spread"] is not None]
        medians = [p["median"] for p in per.values()]
        out[name] = {
            "sets": per,
            "widest_spread": max(spreads) if spreads else None,
            "five_times_widest": 5 * max(spreads) if spreads else None,
            "set_median_shift": (medians[-1] / medians[0] - 1
                                 if len(medians) > 1 else None)}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("cell")
    p.add_argument("--seconds", type=float, default=None,
                   help="default: BENCHMARK.json's run_seconds")
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6, help="runs to a set")
    p.add_argument("--no-cold", action="store_true")
    p.add_argument("--no-trace", action="store_true")
    p.add_argument("--out", default=None)
    p.add_argument("--rehearse", action="store_true",
                   help="pass --rehearse on: CPU, tiny sizes, no results")
    p.add_argument("--timeout", type=float, default=1500.0,
                   help="seconds allowed to one run")
    args = p.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    seconds = args.seconds if args.seconds is not None \
        else spec["run_seconds"]
    out_dir = args.out or os.path.join(ROOT, "chiprun_out", "prove",
                                       args.cell)
    os.makedirs(out_dir, exist_ok=True)
    plan = [] if args.no_cold else [("cold", 0, SEEDS[0], 0)]
    plan += [("repeat", s, seed, 0) for s in range(1, args.sets + 1)
             for seed in SEEDS[:args.runs]]
    if not args.no_trace:
        plan.append(("traced", 0, SEEDS[0], 1))

    command = spec["command"] + (["--rehearse"] if args.rehearse else [])
    records = []
    with open(os.path.join(out_dir, "runs.jsonl"), "a") as sink:
        for kind, set_no, seed, trace in plan:
            rec = {"cell": args.cell, "kind": kind, "set": set_no,
                   "seconds": seconds,
                   **one_run(command, args.cell, seed, seconds, trace,
                             args.timeout)}
            records.append(rec)
            sink.write(json.dumps(rec) + "\n")
            sink.flush()
            brief = {k: rec[k] for k in ("kind", "set", "seed", "rc")}
            brief["wall_s"] = round(rec["wall_s"], 1)
            if rec["result"]:
                brief["correct"] = rec["result"]["correct"]
                brief["metrics"] = {k: v["value"] for k, v in
                                    rec["result"]["metrics"].items()}
            else:
                brief["stderr_tail"] = rec["stderr_tail"][-1500:]
            print(json.dumps(brief), flush=True)
    if not args.no_trace:
        from benchmark.lib.trace_reduce import newest_xplane  # no jax here

        try:
            xplane = newest_xplane(os.path.join(BENCH, ".cache", "trace",
                                                args.cell))
            if os.path.getsize(xplane) <= KEEP_TRACE_BYTES:
                shutil.copy(xplane, os.path.join(out_dir, "trace.xplane.pb"))
        except FileNotFoundError:
            pass
    summary = summarize(records)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"summary": {
        k: {"widest_spread": v["widest_spread"],
            "five_times_widest": v["five_times_widest"],
            "set_median_shift": v["set_median_shift"],
            "medians": [s["median"] for s in v["sets"].values()]}
        for k, v in summary.items()}}), flush=True)
    failures = [r for r in records if r["rc"] != 0 or not r["result"]
                or not r["result"]["correct"]]
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
