#!/usr/bin/env python3
"""One run of one cell of photon-tpu's benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name: ``workloads/<cell>.json`` names its
configuration (``configs/<config>.json``) and its traffic module
(``traffic/<module>.py``); BENCHMARK.json says which metrics the cell
reports and in which unit; each per-layer metric is read by
``layer_metrics/<name>.py``. Nothing here names a cell, a configuration, a
module or a metric, so a later PR adds any of them as files of its own.

Set-up (data from ``--seed``, layout, placement, one whole warm-up unit)
ends where the window opens; then whole units run back to back for
``--seconds``. ``--trace 0`` prints the end-to-end metrics; ``--trace 1``
takes a profiler trace of a few units instead and prints the per-layer
metrics, ``busy_s`` / ``window_s`` and the breakdown. The last line of
standard output is the result; without a TPU (or with another number of
chips than the cell asks for) the exit code is not 0 and no result is
printed. ``--rehearse`` runs the same control flow at the configuration's
tiny ``rehearse`` sizes on the CPU and prints no result line either.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def cell_metrics(spec: dict, cell: str) -> tuple:
    """(end-to-end, per-layer) metric entries of BENCHMARK.json that this
    cell reports: those with no ``workloads`` key, or with the cell in it."""
    def mine(entries):
        return [m for m in entries
                if "workloads" not in m or cell in m["workloads"]]
    return mine(spec["end_to_end"]), mine(spec["per_layer"])


def measure_traced(jax, traffic, state, sections, params, per_layer, about,
                   args) -> tuple:
    """The traced run of a cell: (unit results, per-layer metrics,
    busy_s / window_s for the device object, the breakdown)."""
    from benchmark.lib import harness, trace_reduce

    trace_dir = os.path.join(BENCH, ".cache", "trace", args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    traced = harness.run_traced(jax, traffic, state, sections,
                                int(params.get("traced_units", 1)),
                                trace_dir)
    reduced = trace_reduce.reduce_trace(
        trace_reduce.load(trace_reduce.newest_xplane(trace_dir),
                          rehearse=args.rehearse),
        steady={"unit"}, labels=getattr(traffic, "GAP_LABELS", {}))
    log(event="trace", sections=reduced["sections"],
        n_device_ops=reduced["n_device_ops"], telemetry=traced["telemetry"])
    ctx = {**about, "params": params, "state": state, "trace": reduced,
           "results": traced["results"], "telemetry": traced["telemetry"]}
    metrics = {}
    for m in per_layer:
        value = importlib.import_module(
            f"benchmark.layer_metrics.{m['name']}").read(ctx)
        if value is not None:
            metrics[m["name"]] = value
    return (traced["results"].get("unit", []), metrics,
            {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]},
            {"breakdown": {"device_ops": reduced["device_ops"],
                           "idle_gaps": reduced["idle_gaps"]}})


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU, the configuration's tiny sizes, the same "
                        "control flow; prints no result line")
    args = p.parse_args(argv)

    from benchmark.lib import harness

    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        raise SystemExit(f"run.py: BENCHMARK.json has no workload "
                         f"{args.workload!r}")
    workload = harness.load_json(
        os.path.join(BENCH, "workloads", f"{args.workload}.json"))
    config = harness.load_json(
        os.path.join(BENCH, "configs", f"{entry['config']}.json"))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = {**config, **config.get("rehearse", {})}
    end_to_end, per_layer = cell_metrics(spec, args.workload)
    units_of = {m["name"]: m["unit"] for m in end_to_end + per_layer}

    import jax

    from photon_tpu.utils.compile_cache import enable_compilation_cache

    from benchmark.lib.clock import CompileClock
    from benchmark.lib.peaks import device_peaks

    device = harness.device_info(jax)
    if not args.rehearse:
        if device["platform"] != "tpu":
            raise SystemExit(f"run.py: no TPU (jax found "
                             f"{device['platform']!r}); --rehearse is the "
                             "only CPU mode")
        if device["count"] != entry["chips"]:
            raise SystemExit(f"run.py: the cell asks for {entry['chips']} "
                             f"chip(s), jax sees {device['count']}")
        peaks = device_peaks(device["kind"])
    else:
        peaks = None
    # the repo's one rule: JAX_COMPILATION_CACHE_DIR if set, else the
    # fixed <checkout>/.jax_cache — the drivers resolve to the same place
    cache_dir = enable_compilation_cache()
    clock = CompileClock()
    traffic = importlib.import_module(
        f"benchmark.traffic.{workload['module']}")
    # where a run writes: "work" is this cell's, fixed and emptied first;
    # "shared" keeps what no seed changes, for every later run
    work_dir = os.path.join(BENCH, ".cache", "work", args.workload)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    dirs = {"work": work_dir,
            "shared": os.path.join(BENCH, ".cache", "shared")}

    state = traffic.setup(config, workload["params"], args.seed, dirs)
    t_warm = time.perf_counter()
    warm = traffic.unit(state, keep=True)
    t_warmed = time.perf_counter()
    # a traced run also compiles its micro sections before the window
    sections = (traffic.traced_sections(state)
                if args.trace and hasattr(traffic, "traced_sections") else [])
    t_open = time.perf_counter()
    setup_clock = clock.snapshot()
    log(event="setup", workload=args.workload, seed=args.seed,
        setup_s=t_open - T_START, warm_unit_s=t_warmed - t_warm,
        setup_clocks=state.clocks,
        compile=setup_clock, compile_cache_dir=cache_dir,
        rehearse=args.rehearse, **device)

    if args.trace:
        results, metrics, traced_device, extra = measure_traced(
            jax, traffic, state, sections, workload["params"], per_layer,
            {"config": config, "peaks": peaks}, args)
        device.update(traced_device)
    else:
        units, elapsed = harness.run_window(traffic.unit, state,
                                            args.seconds)
        log(event="window", n_units=len(units), elapsed_s=elapsed,
            unit_walls_s=[w for w, _ in units])
        results, extra = [r for _, r in units], {}
        values = {**traffic.metrics(state, units, elapsed),
                  "setup_s": t_open - T_START}
        metrics = {m["name"]: values[m["name"]] for m in end_to_end}
    in_window = CompileClock.delta(setup_clock, clock.snapshot())

    # outside the window: the warm-up's result against the plain reference
    verdict = traffic.check(state, warm.pop("evidence"))
    built = CompileClock.programs_built(in_window)
    failed = sum(1 for r in results if r["failed"])
    correct = bool(verdict["ok"]) and built == 0 and not warm["failed"]
    log(event="check", verdict=verdict, programs_built_in_window=built,
        compile_in_window=in_window)
    device["memory_peak_bytes"] = harness.memory_peak_bytes(jax)
    shutil.rmtree(work_dir, ignore_errors=True)
    if args.rehearse:
        log(event="rehearsal", correct=correct, attempted=len(results),
            failed=failed, metric_names=sorted(metrics), **extra)
        return 0
    print(harness.final_line(correct, len(results), failed, metrics, units_of,
                             device, **extra), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
