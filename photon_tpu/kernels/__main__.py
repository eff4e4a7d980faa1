"""Kernels selftest CLI: the roofline-closure round as one smoke.

    python -m photon_tpu.kernels --selftest            # one line, exit != 0
    python -m photon_tpu.kernels --selftest --json     # machine report

Runs the Pallas-kernel dispatch seam end to end on the CPU backend inside
`kernels.interpreted()` — Pallas interpret mode, a self-test of the
kernels' arithmetic against the XLA path, NOT evidence about the chip
(whether a kernel compiles for the v5e is tests/test_chip_compile.py's
job). The umbrella ``python -m photon_tpu --selfcheck`` wires this in as
the 9th suite:

- ``parity``     — kernel-vs-XLA matvec/rmatvec/lanes/sq_rmatvec
  BITWISE across a multi-width blocked-ELL layout, f32 and bf16 storage.
- ``streamed``   — a blocked-ELL chunk-ladder streamed solve with
  kernels on equals the kernels-off solve bit for bit (the chunk
  programs dispatch the kernels inside jit).
- ``dispatch``   — the seam is signature-invariant across mode flips and
  walks the fused → grid-tiled → XLA route ladder: no-tail layouts and
  sub-tile budgets fall to XLA, past the fused budget the grid-tiled
  rung serves (bitwise), never erroring.
- ``ring``       — the donated DeviceChunkRing rotates across passes
  with ONE chunk-program signature and yields chunks in order.
- ``contracts``  — the roofline-closure ContractSpecs
  (`blocked_ell_kernel_x_passes`, `blocked_ell_kernel_no_retrace`,
  `blocked_ell_tiled_x_passes`, `serving_kernel_fused_rung`,
  `serving_kernel_mode_invariance`, `mesh_stream_donated_no_retrace`,
  `serving_quantized_rung_invariance`) trace clean.

Exit status: 0 iff every check passed.
"""
from __future__ import annotations

import os
import sys


def _default_env() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()


def run_selftest() -> dict:
    from photon_tpu import kernels as K

    with K.interpreted():
        return _run_checks()


def _run_checks() -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from photon_tpu import kernels as K
    from photon_tpu.data import matrix as M

    checks: dict = {}

    def check(name, ok, **detail):
        checks[name] = {"ok": bool(ok), **detail}

    # ---- parity: the full op surface, f32 + bf16 storage, bitwise
    rng = np.random.default_rng(0)
    ok_parity, worst = True, 0.0
    for bf16 in (False, True):
        X = M._contract_blocked_ell(n=64, d=128, k=7, d_dense=16, bf16=bf16)
        n, d = X.shape
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        r = jnp.asarray(rng.normal(size=n).astype(np.float32))
        W = jnp.asarray(rng.normal(size=(d, 3)).astype(np.float32))
        R = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
        with K.scope("off"):
            ref = [np.asarray(f(X, v)) for f, v in (
                (M.matvec, w), (M.rmatvec, r), (M.matvec_lanes, W),
                (M.rmatvec_lanes, R), (M.sq_rmatvec, r))]
        with K.scope("on"):
            got = [np.asarray(f(X, v)) for f, v in (
                (M.matvec, w), (M.rmatvec, r), (M.matvec_lanes, W),
                (M.rmatvec_lanes, R), (M.sq_rmatvec, r))]
        for a, b in zip(ref, got):
            worst = max(worst, float(np.max(np.abs(a - b))))
            ok_parity &= bool((a == b).all())
    check("parity_bitwise", ok_parity, max_abs_diff=worst)

    # ---- streamed chunk path: kernels on == off, bit for bit
    from photon_tpu.data.dataset import chunk_blocked_ell, make_batch
    from photon_tpu.models.training import train_glm
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    ind = rng.integers(0, 96, size=(128, 4)).astype(np.int32)
    val = rng.normal(size=(128, 4)).astype(np.float32)
    sp = M.SparseRows(ind, val, 96)
    y = (rng.uniform(size=128) < 0.5).astype(np.float32)
    cb = chunk_blocked_ell(make_batch(sp, y), 32, d_dense=16)
    cfg = OptimizerConfig(max_iters=5, tolerance=0.0, reg=l2(),
                          reg_weight=1e-3, history=4)
    import dataclasses as _dc

    w_off = np.asarray(train_glm(cb, TaskType.LOGISTIC_REGRESSION,
                                 _dc.replace(cfg, kernels="off"))[1].w)
    w_on = np.asarray(train_glm(cb, TaskType.LOGISTIC_REGRESSION,
                                _dc.replace(cfg, kernels="on"))[1].w)
    check("streamed_bitwise", (w_off == w_on).all(),
          max_abs_diff=float(np.max(np.abs(w_off - w_on))))

    # ---- dispatch: the route ladder (fused → tiled → XLA) + invariance
    X = M._contract_blocked_ell(bf16=False)
    nO, dO = X.shape
    wv = jnp.zeros((dO,), jnp.float32)
    no_tail = M.to_blocked_ell(
        M.SparseRows(np.zeros((8, 2), np.int32),
                     np.zeros((8, 2), np.float32), 16), 16)
    with K.scope("on"):
        fallback_ok = M._kernel_route(no_tail, wv[:16]) is None
        os.environ[K.ENV_VMEM] = "1"
        try:
            # one byte: even one tile cannot fit — XLA serves
            floor_ok = M._kernel_route(X, wv) is None
        finally:
            del os.environ[K.ENV_VMEM]
        active_ok = M._kernel_route(X, wv) == "fused"
    # past the fused budget but above the tiled floor: the ladder's
    # middle rung engages (and stays bitwise) instead of falling to XLA
    from photon_tpu.kernels import blocked_ell as BE

    total = BE._nbytes(wv) + BE._nbytes(X.row_pos)
    for t in (X.ell_pcols, X.ell_vals, X.bucket_rows, X.bucket_vals):
        total += sum(BE._nbytes(b) for b in t)
    wr = jnp.asarray(rng.normal(size=dO).astype(np.float32))
    rr = jnp.asarray(rng.normal(size=nO).astype(np.float32))
    with K.scope("off"):
        ref_mv = np.asarray(M.matvec(X, wr))
        ref_rm = np.asarray(M.rmatvec(X, rr))
    os.environ[K.ENV_VMEM] = str(total - 1)
    try:
        with K.scope("on"):
            tiled_ok = M._kernel_route(X, wv) == "tiled"
            tiled_bitwise = (
                (np.asarray(M.matvec(X, wr)) == ref_mv).all()
                and (np.asarray(M.rmatvec(X, rr)) == ref_rm).all())
    finally:
        del os.environ[K.ENV_VMEM]
    from photon_tpu.analysis.rules import TraceSignatureLog

    log = TraceSignatureLog()
    for m in ("off", "on"):
        with K.scope(m):
            log.record("seam", (X, wv))
    check("dispatch_seam", fallback_ok and floor_ok and active_ok
          and tiled_ok and bool(tiled_bitwise)
          and len(log.signatures("seam")) == 1 and not log.hazards())

    # ---- ring: rotation order + one signature across passes
    from photon_tpu.data.dataset import chunk_batch

    Xd = rng.normal(size=(64, 8)).astype(np.float32)
    cb2 = chunk_batch(make_batch(Xd, (rng.uniform(size=64) < 0.5)
                                 .astype(np.float32)), 16)
    ring = cb2.device_ring(prefetch=2)
    log2 = TraceSignatureLog()
    order = []
    for _ in range(2):
        for i, b in ring.stream_pass():
            order.append(i)
            log2.record("ring", (b,))
    check("ring_rotation", order == [0, 1, 2, 3] * 2
          and len(log2.signatures("ring")) == 1)

    # ---- contracts
    from photon_tpu.analysis import check_contract
    from photon_tpu.analysis.registry import load_registry

    reg = load_registry()
    bad = {}
    for name in ("blocked_ell_kernel_x_passes",
                 "blocked_ell_kernel_no_retrace",
                 "blocked_ell_tiled_x_passes",
                 "serving_kernel_fused_rung",
                 "serving_kernel_mode_invariance",
                 "mesh_stream_donated_no_retrace",
                 "serving_quantized_rung_invariance"):
        violations = check_contract(reg[name])
        if violations:
            bad[name] = [str(v) for v in violations]
    check("contracts", not bad, violations=bad)

    ok = all(c["ok"] for c in checks.values())
    return {"ok": ok, "backend": jax.default_backend(), "checks": checks}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--selftest" not in argv:
        print(__doc__)
        return 2
    _default_env()
    import json

    report = run_selftest()
    if "--json" in argv:
        print(json.dumps(report))
    else:
        parts = [f"{k}={'ok' if v['ok'] else 'FAIL'}"
                 for k, v in report["checks"].items()]
        print(f"kernels selftest: {' '.join(parts)} — "
              f"{'ok' if report['ok'] else 'FAIL'}")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
