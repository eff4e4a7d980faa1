"""Roofline-closure round (15): the Pallas kernel dispatch seam, the
donated upload ring, and the quantized serving rungs.

The load-bearing facts, each pinned bitwise where the design claims
bitwise:

- Pallas INTERPRET mode on this CPU backend reproduces the XLA
  blocked-ELL X passes bit for bit — across every nnz width bucket the
  pow2 ladder produces, empty buckets, non-dividing row counts, f32 and
  bf16 storage, single-vector and lane-minor forms, and the squared
  (Hessian-diagonal) rmatvec.
- The dispatch seam (PHOTON_TPU_KERNELS / OptimizerConfig.kernels) is
  pure routing: kernels-on solves equal kernels-off solves bitwise on
  the resident AND streamed-chunk paths, fallbacks (no tail, VMEM
  budget) never error, and mode flips never change call signatures.
- The DeviceChunkRing rotates across passes in order, pre-arms the next
  pass at exhaustion, and resets cleanly when a pass is abandoned — the
  crash/kill path of the donated double-buffer round.
- Quantized rungs: the warmup accuracy gate REFUSES a breach
  (`QuantizationRefused`, counted), the cold-miss row dequantizes to
  exact zeros (fixed-effect-only degradation is bit-identical to the
  f32 ladder), and mixed-size quantized dispatch never retraces.
"""
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu import kernels as K
from photon_tpu.data import matrix as M
from photon_tpu.data.dataset import (chunk_batch, chunk_blocked_ell,
                                     make_batch)
from photon_tpu.data.matrix import SparseRows, to_blocked_ell
from photon_tpu.models.training import train_glm
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.regularization import l2

pytestmark = pytest.mark.release_programs


def _wide_bucket_problem(n=51, d=160, d_dense=8, seed=0, bf16=False):
    """A blocked-ELL layout exercising MANY width buckets: row i carries
    (i % 18) + 1 tail nnz on top of 2 hot columns, so the pow2 width
    ladder spans 1/2/4/8/16/32 and n=51 divides nothing."""
    rng = np.random.default_rng(seed)
    rows_ind, rows_val = [], []
    kmax = 21
    for i in range(n):
        tail = (i % 18) + 1
        cols = rng.permutation(np.arange(2, d - 1))[:tail]  # distinct
        ind = np.concatenate([[0, 1], cols, np.zeros(kmax - 2 - tail,
                                                     np.int64)])
        val = np.concatenate([rng.normal(size=2 + tail),
                              np.zeros(kmax - 2 - tail)])
        rows_ind.append(ind)
        rows_val.append(val)
    sp = SparseRows(np.asarray(rows_ind, np.int32),
                    np.asarray(rows_val, np.float32), d)
    X = to_blocked_ell(sp, d_dense)
    if bf16:
        bf = jnp.bfloat16
        X = dataclasses.replace(
            X, dense=jnp.asarray(X.dense).astype(bf),
            ell_vals=tuple(jnp.asarray(v).astype(bf) for v in X.ell_vals),
            bucket_vals=tuple(jnp.asarray(v).astype(bf)
                              for v in X.bucket_vals))
    return X


def _fused_nbytes(X, v):
    """The fused form's whole operand set in bytes — one byte past this
    the route ladder's middle (grid-tiled) rung takes over."""
    from photon_tpu.kernels import blocked_ell as BE

    total = BE._nbytes(v) + BE._nbytes(X.row_pos)
    for t in (X.ell_pcols, X.ell_vals, X.bucket_rows, X.bucket_vals):
        total += sum(BE._nbytes(b) for b in t)
    return total


class TestKernelParity:
    @pytest.mark.parametrize("bf16", [False, True])
    def test_full_bucket_matrix_bitwise(self, bf16):
        """Every op, every width bucket, non-dividing rows: kernel == XLA
        bit for bit."""
        X = _wide_bucket_problem(bf16=bf16)
        assert len(X.ell_vals) >= 4  # widths 1/2/4/8/16…: real coverage
        n, d = X.shape
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        r = jnp.asarray(rng.normal(size=n).astype(np.float32))
        W = jnp.asarray(rng.normal(size=(d, 3)).astype(np.float32))
        R = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
        cases = ((M.matvec, w), (M.rmatvec, r), (M.matvec_lanes, W),
                 (M.rmatvec_lanes, R), (M.sq_rmatvec, r))
        with K.scope("off"):
            ref = [np.asarray(f(X, v)) for f, v in cases]
        with K.scope("on"):
            assert K.active()
            got = [np.asarray(f(X, v)) for f, v in cases]
        for (f, _), a, b in zip(cases, ref, got):
            np.testing.assert_array_equal(a, b, err_msg=f.__name__)

    def test_empty_bucket_fallback(self):
        """A layout with no tail routes to the XLA path (nothing to
        fuse) — same answer, no error."""
        sp = SparseRows(np.zeros((8, 2), np.int32),
                        np.ones((8, 2), np.float32), 16)
        X = to_blocked_ell(sp, 16)
        assert X.ell_vals == ()
        w = jnp.ones((16,), jnp.float32)
        with K.scope("on"):
            assert M._kernel_route(X, w) is None
            out = np.asarray(M.matvec(X, w))
        with K.scope("off"):
            np.testing.assert_array_equal(out, np.asarray(M.matvec(X, w)))

    def test_vmem_budget_fallback(self):
        """The route ladder walks down under pressure: past the fused
        budget the grid-tiled rung serves (same bits), and at one byte —
        below even one tile — the seam steps aside to XLA entirely.
        Never an error, never different bits."""
        X = _wide_bucket_problem()
        w = jnp.ones((X.shape[1],), jnp.float32)
        total = _fused_nbytes(X, w)
        with K.scope("on"):
            assert M._kernel_route(X, w) == "fused"
            ref = np.asarray(M.matvec(X, w))
        os.environ[K.ENV_VMEM] = str(total - 1)
        try:
            with K.scope("on"):
                assert M._kernel_route(X, w) == "tiled"
                np.testing.assert_array_equal(ref, np.asarray(M.matvec(X, w)))
        finally:
            del os.environ[K.ENV_VMEM]
        os.environ[K.ENV_VMEM] = "1"
        try:
            with K.scope("on"):
                assert M._kernel_route(X, w) is None
                np.testing.assert_array_equal(ref, np.asarray(M.matvec(X, w)))
        finally:
            del os.environ[K.ENV_VMEM]

    def test_jit_solve_parity_resident(self):
        """A resident blocked-ELL train_glm with kernels on equals the
        XLA solve (the seam dispatches inside jit): to the bit through the
        first iteration, which runs every X pass and the line search on an
        empty history; after it to f32 reduction noise — the solves are
        two compiled programs, and a reduction the compiler may FUSE (the
        history's inner products) is summed in the order each program's
        fusion gives it. The old bitwise pin at 6 iterations held by the
        two-loop's dots being library calls: the PARENT's recursion with
        `jnp.sum(a * b)` for `jnp.dot(a, b)` reads 9.5e-7 here too
        (PERF.md §6, PR 28). The streamed path below pushes through ONE
        jitted program on either route and stays bitwise."""
        rng = np.random.default_rng(3)
        ind = rng.integers(0, 96, size=(128, 5)).astype(np.int32)
        val = rng.normal(size=(128, 5)).astype(np.float32)
        y = (rng.uniform(size=128) < 0.5).astype(np.float32)
        batch = jax.device_put(make_batch(SparseRows(ind, val, 96), y))
        batch = batch._replace(X=jax.device_put(
            to_blocked_ell(SparseRows(ind, val, 96), 16)))
        cfg = OptimizerConfig(max_iters=6, tolerance=0.0, reg=l2(),
                              reg_weight=1e-3, history=4)
        w_off = np.asarray(train_glm(
            batch, TaskType.LOGISTIC_REGRESSION,
            dataclasses.replace(cfg, kernels="off"))[1].w)
        w_on = np.asarray(train_glm(
            batch, TaskType.LOGISTIC_REGRESSION,
            dataclasses.replace(cfg, kernels="on"))[1].w)
        np.testing.assert_allclose(w_off, w_on, rtol=0, atol=4e-6)
        first = dataclasses.replace(cfg, max_iters=1)
        np.testing.assert_array_equal(*(np.asarray(train_glm(
            batch, TaskType.LOGISTIC_REGRESSION,
            dataclasses.replace(first, kernels=k))[1].w)
            for k in ("off", "on")))

    def test_streamed_chunk_path_parity(self):
        """The streamed blocked-ELL chunk ladder with kernels on equals
        kernels off bit for bit (the chunk programs carry the seam)."""
        rng = np.random.default_rng(4)
        ind = rng.integers(0, 64, size=(96, 4)).astype(np.int32)
        val = rng.normal(size=(96, 4)).astype(np.float32)
        y = (rng.uniform(size=96) < 0.5).astype(np.float32)
        cb = chunk_blocked_ell(make_batch(SparseRows(ind, val, 64), y),
                               32, d_dense=16)
        cfg = OptimizerConfig(max_iters=5, tolerance=0.0, reg=l2(),
                              reg_weight=1e-3, history=4)
        w_off = np.asarray(train_glm(
            cb, TaskType.LOGISTIC_REGRESSION,
            dataclasses.replace(cfg, kernels="off"))[1].w)
        w_on = np.asarray(train_glm(
            cb, TaskType.LOGISTIC_REGRESSION,
            dataclasses.replace(cfg, kernels="on"))[1].w)
        np.testing.assert_array_equal(w_off, w_on)


class TestDispatchSeam:
    def test_env_knob(self, monkeypatch):
        monkeypatch.setenv(K.ENV_KNOB, "on")
        assert K.mode() == "on" and K.active()
        monkeypatch.setenv(K.ENV_KNOB, "off")
        assert not K.active()
        # auto never routes to a kernel the chip's compiler refuses — and
        # it refuses all of this package's (tests/test_chip_compile.py)
        monkeypatch.setenv(K.ENV_KNOB, "auto")
        assert not K.active()
        monkeypatch.setenv(K.ENV_KNOB, "bogus")
        with pytest.raises(ValueError, match="PHOTON_TPU_KERNELS"):
            K.mode()

    def test_on_never_interprets_outside_the_harness(self, rng, monkeypatch):
        """Interpret mode is the tests' (conftest's `kernels.interpreted`
        block), not a backend fallback: with it off, mode ``on`` on a
        device that is not a TPU raises the lowering's own error instead
        of quietly interpreting, and the default mode takes the XLA path."""
        monkeypatch.setattr(K, "_INTERPRETED", False)
        X = M._contract_blocked_ell(bf16=False)
        w = jnp.asarray(rng.normal(size=X.shape[1]).astype(np.float32))
        assert not K.interpret() and K.route(X, w) is None
        ref = np.asarray(M.matvec(X, w))
        with K.scope("on"):
            assert K.route(X, w) == "fused"
            with pytest.raises(ValueError, match="interpret mode"):
                jax.block_until_ready(M.matvec(X, w))
        np.testing.assert_array_equal(np.asarray(M.matvec(X, w)), ref)

    def test_scope_nesting_and_restore(self):
        base = K.active()
        with K.scope("on"):
            assert K.active()
            with K.scope("off"):
                assert not K.active()
            assert K.active()
        assert K.active() == base

    def test_signature_invariance_across_modes(self):
        from photon_tpu.analysis.rules import TraceSignatureLog

        X = _wide_bucket_problem()
        w = jnp.zeros((X.shape[1],), jnp.float32)
        log = TraceSignatureLog()
        for m in ("off", "on", "off", "on"):
            with K.scope(m):
                log.record("seam", (X, w))
        assert len(log.signatures("seam")) == 1
        assert log.hazards() == []


class TestDeviceChunkRing:
    def test_rotation_order_and_prearm(self):
        rng = np.random.default_rng(5)
        Xd = rng.normal(size=(64, 8)).astype(np.float32)
        cb = chunk_batch(make_batch(
            Xd, (rng.uniform(size=64) < 0.5).astype(np.float32)), 16)
        ring = cb.device_ring(prefetch=2)
        for p in range(3):
            seen = [(i, np.asarray(b.y)) for i, b in ring.stream_pass()]
            assert [i for i, _ in seen] == [0, 1, 2, 3]
            for i, yb in seen:
                np.testing.assert_array_equal(yb, cb.y[i * 16:(i + 1) * 16])
            # pre-arm: the next pass's first uploads are already issued
            assert len(ring._window) == 2

    def test_abandoned_pass_resets(self):
        rng = np.random.default_rng(6)
        Xd = rng.normal(size=(48, 4)).astype(np.float32)
        cb = chunk_batch(make_batch(
            Xd, np.zeros(48, np.float32)), 16)
        ring = cb.device_ring(prefetch=2)
        it = ring.stream_pass()
        next(it)  # consume chunk 0, abandon mid-pass
        it.close()
        assert len(ring._window) == 0 and ring._next == 0
        order = [i for i, _ in ring.stream_pass()]
        assert order == [0, 1, 2]  # restarts at chunk 0, nothing stale

    def test_streamed_solve_unchanged_by_ring(self):
        """The ring + donated programs are pure overlap: streamed ==
        resident at the documented tolerance, twice in a row (ring state
        carries across solves of the same backend instance only)."""
        rng = np.random.default_rng(7)
        Xd = rng.normal(size=(256, 12)).astype(np.float32)
        y = (rng.uniform(size=256) < 0.5).astype(np.float32)
        cfg = OptimizerConfig(max_iters=8, tolerance=0.0, reg=l2(),
                              reg_weight=1e-3, history=4)
        res = train_glm(make_batch(Xd, y), TaskType.LOGISTIC_REGRESSION,
                        cfg)[1]
        cb = chunk_batch(make_batch(Xd, y), 64)
        s1 = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)[1]
        s2 = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)[1]
        np.testing.assert_array_equal(np.asarray(s1.w), np.asarray(s2.w))
        np.testing.assert_allclose(np.asarray(res.w), np.asarray(s1.w),
                                   atol=2e-4, rtol=2e-4)


class TestQuantizedRungs:
    def _ladder(self, quantize=None, eps=0.5, E=32, df=12, dr=6, k=3):
        from photon_tpu import serving
        from photon_tpu.game.model import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
        from photon_tpu.models.glm import (Coefficients,
                                           GeneralizedLinearModel)

        rng = np.random.default_rng(8)
        task = TaskType.LOGISTIC_REGRESSION
        keys = np.asarray(sorted(str(i) for i in range(E)))
        model = GameModel({
            "fixed": FixedEffectModel(GeneralizedLinearModel(
                Coefficients(jnp.asarray(
                    rng.normal(size=df).astype(np.float32))), task),
                "global"),
            "perMember": RandomEffectModel(
                entity_name="memberId", feature_shard="member", task=task,
                coefficients=jnp.asarray(
                    rng.normal(size=(E, dr)).astype(np.float32)),
                entity_keys=keys,
                key_to_index={kk: i for i, kk in enumerate(keys.tolist())}),
        }, task)
        store = serving.CoefficientStore.from_game_model(model)
        return serving.ProgramLadder(
            store, floor=8, max_batch=16, sparse_k={"member": k},
            quantize=quantize, quant_epsilon=eps), (df, dr, k, E)

    def test_epsilon_refusal_and_counter(self):
        from photon_tpu import telemetry
        from photon_tpu.serving.programs import QuantizationRefused

        ladder, _ = self._ladder(quantize="int8", eps=1e-9)
        run = telemetry.start_run("quant_refusal_test")
        try:
            with pytest.raises(QuantizationRefused, match="exceeds"):
                ladder.warmup()
            assert run.counters.get("serving.quant_refusals", 0) == 1
        finally:
            telemetry.finish_run()
        assert ladder.quant_report["max_abs_diff"] > 0.0

    def test_gate_passes_and_reports(self):
        ladder, _ = self._ladder(quantize="int8", eps=0.5)
        assert ladder.warmup() >= 1
        rep = ladder.quant_report
        assert rep["mode"] == "int8"
        assert 0.0 < rep["max_abs_diff"] <= 0.5

    def test_cold_miss_row_bitwise(self):
        """An unseen entity's quantized score equals the f32 ladder's bit
        for bit: the all-zero cold-miss row quantizes at scale 1.0 and
        dequantizes to exact zeros."""
        ladder, (df, dr, k, E) = self._ladder(quantize="int8")
        f32, _ = self._ladder(quantize=None)
        ladder.warmup()
        f32.warmup()
        rng = np.random.default_rng(9)
        off = np.zeros(8, np.float32)
        shards = {"global": np.zeros((8, df), np.float32),
                  "member": SparseRows(
                      rng.integers(0, dr, size=(8, k)).astype(np.int32),
                      rng.normal(size=(8, k)).astype(np.float32), dr)}
        ids = {"perMember": np.full(8, E, np.int32)}  # the cold row
        np.testing.assert_array_equal(
            np.asarray(f32.score_padded(off, shards, ids)),
            np.asarray(ladder.score_padded(off, shards, ids)))

    @pytest.mark.parametrize("mode", ["int8", "bf16"])
    def test_mixed_sizes_never_retrace(self, mode):
        ladder, (df, dr, k, _E) = self._ladder(quantize=mode)
        ladder.warmup()
        rng = np.random.default_rng(10)
        for B in (8, 16, 8, 16, 8):
            shards = {"global": rng.normal(size=(B, df)).astype(np.float32),
                      "member": SparseRows(
                          rng.integers(0, dr, size=(B, k)).astype(np.int32),
                          rng.normal(size=(B, k)).astype(np.float32), dr)}
            ids = {"perMember": np.zeros(B, np.int32)}
            ladder.score_padded(np.zeros(B, np.float32), shards, ids)
        assert ladder.assert_no_retrace() <= len(ladder.ladder)

    def test_hot_swap_requantizes(self):
        """A reload_coefficients swap invalidates the quantized-block
        cache: the next dispatch scores the NEW model (tracked via a
        margin that flips sign when every coefficient is negated)."""
        ladder, (df, dr, k, _E) = self._ladder(quantize="int8")
        ladder.warmup()
        rng = np.random.default_rng(11)
        shards = {"global": rng.normal(size=(8, df)).astype(np.float32),
                  "member": SparseRows(
                      np.zeros((8, k), np.int32),
                      np.zeros((8, k), np.float32), dr)}
        ids = {"perMember": np.zeros(8, np.int32)}
        before = np.asarray(ladder.score_padded(
            np.zeros(8, np.float32), shards, ids))
        import copy

        other = copy.copy(ladder.store)
        neg_fixed = {n: dataclasses.replace(
            b, weights=-np.asarray(b.weights)) for n, b in
            ladder.store.fixed.items()}
        neg_rand = {n: dataclasses.replace(
            b, coefficients=-np.asarray(b.coefficients)) for n, b in
            ladder.store.random.items()}
        other.fixed, other.random = neg_fixed, neg_rand
        other._device = None
        ladder.store.reload_coefficients(other)
        after = np.asarray(ladder.score_padded(
            np.zeros(8, np.float32), shards, ids))
        # logistic mean head: negated margins mirror around 0.5
        np.testing.assert_allclose(np.asarray(before) + np.asarray(after),
                                   1.0, atol=1e-6)


class TestStaticCostNarrowing:
    def test_quantized_dot_charges_storage_width(self):
        from photon_tpu.profiling.model import estimate_fn

        q = np.zeros((256,), np.int8)
        s = np.float32(0.5)
        x = np.zeros((64, 256), np.float32)

        def quant_dot(q, s, x):
            return x @ (q.astype(jnp.float32) * s)

        c = estimate_fn(quant_dot, (q, s, x))
        assert c.narrowed_bytes == 256 * 3  # int8 charged 1 B, not 4

        def f32_dot(w, x):
            return x @ w

        c2 = estimate_fn(f32_dot, (np.zeros(256, np.float32), x))
        assert c2.narrowed_bytes == 0
        # the row-wise serving-rung pattern narrows through the gather +
        # per-row scale multiply too
        def rung(qm, sc, ids, xr):
            rows = qm[ids].astype(jnp.float32) * sc[ids][:, None]
            return jnp.einsum("nd,nd->n", xr, rows)

        c3 = estimate_fn(rung, (np.zeros((100, 8), np.int8),
                                np.zeros(100, np.float32),
                                np.zeros(16, np.int32),
                                np.zeros((16, 8), np.float32)))
        assert c3.narrowed_bytes == 16 * 8 * 3


class TestTiledForms:
    """Round 20: the grid-tiled middle rung of the route ladder — bitwise
    vs the XLA path across tile choices, including a tail bucket SMALLER
    than one tile (which must run at its exact shape: padding a tiny
    einsum changes XLA CPU's per-row reduction strategy and the bits)."""

    def _refs(self, X, w, r, W, R):
        cases = ((M.matvec, w), (M.rmatvec, r), (M.matvec_lanes, W),
                 (M.rmatvec_lanes, R), (M.sq_rmatvec, r))
        with K.scope("off"):
            return cases, [np.asarray(f(X, v)) for f, v in cases]

    @pytest.mark.parametrize("bf16", [False, True])
    @pytest.mark.parametrize("tile", [None, "8"])
    def test_tiled_route_full_surface_bitwise(self, monkeypatch, bf16,
                                              tile):
        """Every op through the seam with the route pinned to "tiled"
        (one byte past the fused budget): kernel == XLA bit for bit, at
        the default tile AND at the minimum tile where sub-tile buckets
        take the exact-shape path."""
        X = _wide_bucket_problem(bf16=bf16)
        # the sub-tile regime is real: some bucket has fewer rows than
        # even the minimum 8-row tile (it must run at its exact shape)
        assert min(int(b.shape[0])
                   for t in (X.ell_vals, X.bucket_rows) for b in t) < 8
        n, d = X.shape
        rng = np.random.default_rng(20)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        r = jnp.asarray(rng.normal(size=n).astype(np.float32))
        W = jnp.asarray(rng.normal(size=(d, 3)).astype(np.float32))
        R = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
        cases, ref = self._refs(X, w, r, W, R)
        if tile is not None:
            monkeypatch.setenv(K.ENV_TILE, tile)
        monkeypatch.setenv(K.ENV_VMEM, str(_fused_nbytes(X, w) - 1))
        with K.scope("on"):
            assert M._kernel_route(X, w) == "tiled"
            got = [np.asarray(f(X, v)) for f, v in cases]
        for (f, _), a, b in zip(cases, ref, got):
            np.testing.assert_array_equal(a, b, err_msg=f.__name__)

    def test_tiled_direct_forms_bitwise(self):
        """The tiled forms called directly equal the fused forms bit for
        bit — same inputs, same outputs, only the VMEM schedule moves."""
        X = _wide_bucket_problem()
        n, d = X.shape
        rng = np.random.default_rng(21)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        r = jnp.asarray(rng.normal(size=n).astype(np.float32))
        with K.scope("on"):
            np.testing.assert_array_equal(
                np.asarray(K.tail_matvec(X, w)),
                np.asarray(K.tail_matvec_tiled(X, w)))
            np.testing.assert_array_equal(
                np.asarray(K.bucket_rmatvec(X, r)),
                np.asarray(K.bucket_rmatvec_tiled(X, r)))
            np.testing.assert_array_equal(
                np.asarray(K.bucket_rmatvec(X, r, square=True)),
                np.asarray(K.bucket_rmatvec_tiled(X, r, square=True)))

    def test_vmem_knob_validation(self, monkeypatch):
        """Satellite 1: a malformed PHOTON_TPU_KERNELS_VMEM raises a
        ValueError NAMING the knob — not a bare int() parse error from
        deep inside a jitted X pass."""
        monkeypatch.setenv(K.ENV_VMEM, "lots")
        with pytest.raises(ValueError, match="PHOTON_TPU_KERNELS_VMEM"):
            K.vmem_budget()
        monkeypatch.setenv(K.ENV_VMEM, "-4096")
        with pytest.raises(ValueError, match="PHOTON_TPU_KERNELS_VMEM"):
            K.vmem_budget()
        monkeypatch.setenv(K.ENV_VMEM, "4096")
        assert K.vmem_budget() == 4096
        monkeypatch.delenv(K.ENV_VMEM)
        assert K.vmem_budget() is None  # interpret mode: unbounded

    def test_tile_knob_validation(self, monkeypatch):
        for bad in ("wide", "12", "4", "-8", "0"):
            monkeypatch.setenv(K.ENV_TILE, bad)
            with pytest.raises(ValueError,
                               match="PHOTON_TPU_KERNELS_TILE"):
                K.tile_override()
        monkeypatch.setenv(K.ENV_TILE, "64")
        assert K.tile_override() == 64
        monkeypatch.delenv(K.ENV_TILE)
        assert K.tile_override() is None


class TestTileTuner:
    """Round 20: the ledger-driven tile autotuner — measures once per
    (backend, kind, width), persists beside the AOT store, and a warm
    run reuses the cached winner WITHOUT re-measuring."""

    def _problem(self):
        X = M._contract_blocked_ell(n=24, d=48, k=3, d_dense=8)
        n, d = X.shape
        rng = np.random.default_rng(22)
        return (X, jnp.asarray(rng.normal(size=d).astype(np.float32)),
                jnp.asarray(rng.normal(size=n).astype(np.float32)))

    def test_cold_measures_warm_reuses(self, tmp_path):
        from photon_tpu import telemetry
        from photon_tpu.tuning import tile_tuner as TT

        X, w, r = self._problem()
        TT.reset_memo()
        try:
            run = telemetry.start_run("tile_tuner_cold")
            try:
                cold = TT.autotune_tiles(X, w, r, cache_dir=str(tmp_path),
                                         candidates=(64, 128), repeats=1)
                assert cold  # layout exercises at least one key
                assert run.counters.get("kernels.tile_measures", 0) \
                    == 2 * len(cold)
                assert run.counters.get("kernels.tile_cache_hits", 0) == 0
            finally:
                telemetry.finish_run()
            assert os.path.exists(TT.tile_cache_path(str(tmp_path)))
            TT.reset_memo()  # simulate a fresh process, same cache_dir
            run = telemetry.start_run("tile_tuner_warm")
            try:
                warm = TT.autotune_tiles(X, w, r, cache_dir=str(tmp_path),
                                         candidates=(64, 128), repeats=1)
                assert warm == cold  # the cached choice, verbatim
                assert run.counters.get("kernels.tile_measures", 0) == 0
                assert run.counters.get("kernels.tile_cache_hits", 0) \
                    == len(cold)
            finally:
                telemetry.finish_run()
            # the warm winners drive dispatch: tile_for resolves them
            kind, width = next(iter(warm)).split(":")
            assert TT.tile_for(kind, int(width)) == warm[f"{kind}:{width}"]
        finally:
            TT.reset_memo()

    def test_untuned_process_runs_default(self):
        from photon_tpu.tuning import tile_tuner as TT

        TT.reset_memo()
        assert TT.tile_for("tail_matvec", 16) == TT.DEFAULT_TILE

    def test_corrupt_cache_is_cold_cache(self, tmp_path):
        from photon_tpu.tuning import tile_tuner as TT

        path = TT.tile_cache_path(str(tmp_path))
        with open(path, "w") as f:
            f.write("{not json")
        X, w, r = self._problem()
        TT.reset_memo()
        try:
            out = TT.autotune_tiles(X, w, r, cache_dir=str(tmp_path),
                                    candidates=(64,), repeats=1)
            assert out  # re-measured, no crash
            import json

            with open(path) as f:
                doc = json.load(f)  # rewritten well-formed
            assert doc["format"] == TT._FORMAT
        finally:
            TT.reset_memo()
