"""Umbrella selfcheck CLI: one line over every subsystem's own smoke.

    python -m photon_tpu --selfcheck            # one summary line, exit != 0
    python -m photon_tpu --selfcheck --json     # machine report
    python -m photon_tpu --selfcheck --only telemetry profiling

Runs the twelve per-package selftests as subprocesses (each CLI
self-provisions its 8-device CPU platform, so results match CI exactly
and one crashed subsystem cannot take the others down). A SELF-TEST, NOT
CHIP EVIDENCE: the children default to the CPU backend (a chip belongs to
one process at a time). Whether the program runs on the chip is
`chip_smoke.py`'s job;
whether a kernel compiles for it is tests/test_chip_compile.py's.

- ``analysis``   — `python -m photon_tpu.analysis --json` (the full
                   contract registry traces clean; exit 1 on drift)
- ``lint``       — `python -m photon_tpu.lint --json` (the source-level
                   convention auditor: durable writes, fault-site/
                   telemetry/env-knob registries, lock + spawn +
                   exception hygiene, contract/sentinel coverage —
                   jax-free, milliseconds)
- ``threads``    — `python -m photon_tpu.lint --threads --json` (the
                   whole-program concurrency auditor: thread inventory,
                   lock-order graph acyclic, blocking-under-lock, and
                   the pinned guarded-by bindings — jax-free)
- ``telemetry``  — `--selftest`: sinks, spans, iteration stream, both
                   off-is-free contracts (telemetry + request tracing),
                   tail-exemplar attribution, quantile-digest accuracy,
                   watchdog verdicts, cross-rank aggregation
- ``serving``    — `--selftest`: store + dispatcher offline parity,
                   cold-miss fallback, retrace bound
- ``checkpoint`` — `--selftest`: kill → restore → bit parity + both
                   checkpoint-off contracts
- ``profiling``  — `--selftest`: attribution ledger report smoke
                   (static estimates + utilization ∈ (0, 1] on a
                   streamed-dense run, compile accounting, the
                   ledger-off-is-free contract)
- ``game``       — `--selftest`: the pod-scale GAME e2e smoke (tiny
                   rows, mesh 2) — streamed-mesh vs resident parity,
                   the blocked-ELL mesh chunk ladder, the
                   beyond-resident regime completing, and the four
                   pod-scale GAME contracts
- ``continual``  — `--selftest`: the train→serve flywheel — delta plan,
                   prior warm-started partial refresh (untouched
                   entities bit-identical, zero new trace signatures),
                   parity-probed atomic hot-swap with kill-mid-swap
                   falling back to the old model, and both continual
                   contracts
- ``ingest``     — `--selftest`: the round-14 ingest data plane —
                   one-pass scan, worker-pool decode parity (incl.
                   worker-kill degrade), decode-once chunk cache
                   (cold==cached bitwise, torn-commit fallback, CRC
                   corruption detection, key invalidation), the
                   blocked-ELL ladder cache round-trip, the
                   stall-driven prefetch controller, and the
                   chunk-program-invariance contract
- ``tuning``     — `--selftest`: the lane-batched cost-aware tuner —
                   fixed-chunk GP proposal rounds with successive
                   halving (two dispatch signatures for a whole tune),
                   the pow2 GP observation ladder, cost-aware q-EI
                   edges, the pre-dispatch round budget raising on a
                   starved cap, and both tuning contracts
- ``parallel``   — `--selftest`: the multi-process data-parallel spine —
                   1/2/4-process launches of the same 8-device mesh
                   producing BIT-identical psums, a 2-process snapshot
                   restored bit-identically by a 1-process cluster, and
                   the barrier-correct commit failing loudly when a rank
                   dies between payload write and manifest (reports
                   ``available: false`` + exit 0 in sandboxes that block
                   the localhost gRPC coordinator)

Exit status: 0 iff every suite passed; the summary line names each
suite's verdict so a red CI run says WHICH plane drifted.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

SUITES: tuple = (
    ("analysis", ("photon_tpu.analysis", "--json")),
    ("lint", ("photon_tpu.lint", "--json")),
    ("threads", ("photon_tpu.lint", "--threads", "--json")),
    ("telemetry", ("photon_tpu.telemetry", "--selftest", "--json")),
    ("serving", ("photon_tpu.serving", "--selftest", "--json")),
    ("checkpoint", ("photon_tpu.checkpoint", "--selftest", "--json")),
    ("profiling", ("photon_tpu.profiling", "--selftest", "--json")),
    ("game", ("photon_tpu.game", "--selftest", "--json")),
    ("continual", ("photon_tpu.continual", "--selftest", "--json")),
    ("ingest", ("photon_tpu.ingest", "--selftest", "--json")),
    ("tuning", ("photon_tpu.tuning", "--selftest", "--json")),
    ("parallel", ("photon_tpu.parallel", "--selftest", "--json")),
)


def run_selfcheck(only=None, timeout_s: float = 600.0) -> dict:
    """{suite: {"rc", "ok", "seconds"}} — subprocess per suite."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    out: dict = {}
    for name, argv in SUITES:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            proc = subprocess.run(
                [sys.executable, "-m", *argv], env=env,
                capture_output=True, text=True, timeout=timeout_s)
            rc = proc.returncode
            detail = (proc.stdout or proc.stderr).strip().splitlines()
            detail = detail[-1] if detail else ""
        except subprocess.TimeoutExpired:
            rc, detail = 124, f"timed out after {timeout_s:.0f}s"
        out[name] = {"rc": rc, "ok": rc == 0,
                     "seconds": round(time.perf_counter() - t0, 1),
                     "detail": detail}
    return out


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--selfcheck" not in argv:
        print(__doc__)
        return 2
    only = None
    if "--only" in argv:
        only = [a for a in argv[argv.index("--only") + 1:]
                if not a.startswith("--")]
    results = run_selfcheck(only=only)
    ok = all(r["ok"] for r in results.values()) and bool(results)
    if "--json" in argv:
        print(json.dumps({"ok": ok, "suites": results}))
    else:
        parts = []
        for name, r in results.items():
            verdict = "ok" if r["ok"] else "FAIL(rc=%d)" % r["rc"]
            parts.append(f"{name}={verdict}")
        n_ok = sum(r["ok"] for r in results.values())
        print(f"selfcheck: {' '.join(parts)} — {n_ok}/{len(results)} ok")
        for name, r in results.items():
            if not r["ok"]:
                print(f"  {name}: {r['detail']}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
