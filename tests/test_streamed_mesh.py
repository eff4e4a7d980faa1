"""Mesh-sharded streamed training (the ISSUE 2 tentpole): an out-of-HBM
ChunkedBatch trains on a whole (virtual 8-device CPU) mesh — every chunk
row-sharded across the mesh, chunk partials device-local under shard_map,
ONE hierarchical psum per evaluation.

The contract under test: streamed-mesh == streamed single-chip == resident
to f32 accumulation tolerance, across L-BFGS and OWL-QN, a row count that
does not divide the mesh (weight-0 padded tail shard), and a hybrid
replica×data mesh; plus the communication-pattern pin (chunk programs
compile to ZERO collectives, the evaluation finish to exactly ONE
all-reduce) and the driver's pooled-budget auto-trip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import chunk_batch, make_batch
from photon_tpu.data.matrix import SparseRows
from photon_tpu.models.training import train_glm
from photon_tpu.ops.losses import TaskType
from photon_tpu.ops.objective import Objective
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.regularization import elastic_net, l1, l2
from photon_tpu.parallel.mesh import (
    fetch_local_rows,
    local_row_slots,
    make_hybrid_mesh,
    shard_local_rows,
    shard_rows,
)


def _problem(rng, task, n=2048, d=10, sparse=False):
    if sparse:
        k = 4
        ind = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        X = SparseRows(ind, val, d)
        Xd = np.zeros((n, d), np.float32)
        np.add.at(Xd, (np.arange(n)[:, None], ind), val)
    else:
        X = Xd = rng.normal(size=(n, d)).astype(np.float32)
    w_true = (rng.normal(size=d) * 0.5).astype(np.float32)
    margin = Xd @ w_true
    if task is TaskType.LOGISTIC_REGRESSION:
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
            np.float32)
    else:
        y = (margin + rng.normal(size=n) * 0.3).astype(np.float32)
    wt = rng.uniform(0.5, 2.0, n).astype(np.float32)
    off = (rng.normal(size=n) * 0.1).astype(np.float32)
    return make_batch(X, y, wt, off)


@pytest.fixture(scope="module")
def hybrid_mesh():
    return make_hybrid_mesh(n_replicas=2, devices=jax.devices("cpu"))


# Drop this module's compiled 8-device shard_map programs at teardown —
# without this the accumulated executables make the virtual-CPU XLA client
# segfault compiling LATER unrelated programs (test_tuning's GP
# while_loop). The fixture lives in conftest.py now; the marker opts in.
pytestmark = pytest.mark.release_programs


TASKS = [TaskType.LOGISTIC_REGRESSION, TaskType.LINEAR_REGRESSION]


# ---------------------------------------------------------------- helpers
class TestRowSlotHelpers:
    def test_shard_fetch_round_trip(self, rng, mesh8):
        host = rng.normal(size=(300, 3)).astype(np.float32)  # 300 % 8 != 0
        arr = shard_rows(host, mesh8)
        assert arr.shape == (304, 3)  # padded to the device multiple
        np.testing.assert_array_equal(np.asarray(arr)[:300], host)
        np.testing.assert_array_equal(np.asarray(arr)[300:], 0.0)
        local = fetch_local_rows(arr, mesh8)
        assert local.shape == (8, 38, 3)  # one slice per (local) slot
        back = shard_local_rows(local, mesh8)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(arr))

    def test_local_slots_single_process(self, mesh8):
        assert local_row_slots(mesh8) == list(range(8))

    def test_explicit_pad_rows(self, mesh8):
        arr = shard_rows(np.ones(16, np.float32), mesh8, pad_rows=32)
        assert arr.shape == (32,)
        assert float(jnp.sum(arr)) == 16.0


class TestMeshChunkIterator:
    def test_mesh_chunks_shard_and_pad(self, rng, mesh8):
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION, n=1000)
        cb = chunk_batch(batch, 300)
        assert cb.mesh_chunk_rows(mesh8) == 304
        seen = []
        for i, b in cb.iter_device(mesh=mesh8):
            seen.append(i)
            assert b.X.shape == (304, 10)
            assert len(b.y.sharding.device_set) == 8
            # pad rows carry weight 0, so no reduction can see them
            assert float(jnp.sum(b.weights[300:])) == 0.0
        assert seen == [0, 1, 2, 3]
        # total real weight survives the per-chunk mesh padding exactly
        total = sum(float(jnp.sum(b.weights))
                    for _, b in cb.iter_device(mesh=mesh8))
        np.testing.assert_allclose(total, float(np.sum(cb.weights)),
                                   rtol=1e-6)

    def test_stall_logging_signal(self, caplog):
        """The upload-vs-compute imbalance logs at INFO exactly when
        transfer stalls exceed compute over a multi-chunk pass."""
        import logging

        from photon_tpu.data.dataset import _log_stream_stall

        with caplog.at_level(logging.INFO, logger="photon_tpu.streamed"):
            _log_stream_stall(stall=0.2, compute=1.0, n_chunks=4,
                              prefetch=2)  # compute-bound: silent
            assert not caplog.records
            _log_stream_stall(stall=1.0, compute=0.2, n_chunks=1,
                              prefetch=2)  # single chunk: nothing to overlap
            assert not caplog.records
            _log_stream_stall(stall=1.0, compute=0.2, n_chunks=4,
                              prefetch=2)  # upload-bound: the signal
        assert any("deeper prefetch or bigger chunks" in r.message
                   for r in caplog.records)

    def test_prefetch_depths_yield_same_chunks(self, rng, mesh8):
        cb = chunk_batch(_problem(rng, TaskType.LOGISTIC_REGRESSION, n=600),
                         200)
        for prefetch in (1, 2, 4, 99):
            ys = [np.asarray(b.y) for _, b in cb.iter_device(
                mesh=mesh8, prefetch=prefetch)]
            assert len(ys) == 3
            np.testing.assert_array_equal(np.concatenate(ys)[:600], cb.y[:600])
        # single-device path honors the knob too
        ys = [np.asarray(b.y) for _, b in cb.iter_device(prefetch=3)]
        np.testing.assert_array_equal(np.concatenate(ys), cb.y)


# ----------------------------------------------------------------- parity
class TestStreamedMeshParity:
    @pytest.mark.parametrize("task", TASKS)
    def test_lbfgs_three_way(self, rng, task, mesh8):
        """resident == streamed single-chip == streamed mesh, on a row
        count that divides neither the chunk size nor the mesh."""
        batch = _problem(rng, task, n=1900)
        cb = chunk_batch(batch, 300)
        cfg = OptimizerConfig(max_iters=60, tolerance=1e-7, reg=l2(),
                              reg_weight=0.5)
        m_r, r_r = train_glm(batch, task, cfg)
        m_s, r_s = train_glm(cb, task, cfg)
        m_m, r_m = train_glm(cb, task, cfg, mesh=mesh8)
        assert bool(r_m.converged) == bool(r_r.converged)
        np.testing.assert_allclose(float(r_m.value), float(r_r.value),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(m_m.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=2e-3, atol=2e-5)
        np.testing.assert_allclose(np.asarray(m_m.coefficients.means),
                                   np.asarray(m_s.coefficients.means),
                                   rtol=2e-3, atol=2e-5)

    @pytest.mark.parametrize("task", TASKS)
    def test_owlqn_three_way(self, rng, task, mesh8):
        """OWL-QN's candidate-lane ladder shards the same way."""
        batch = _problem(rng, task, n=1900)
        cb = chunk_batch(batch, 300)
        cfg = OptimizerConfig(max_iters=60, tolerance=1e-7,
                              reg=elastic_net(0.5), reg_weight=0.3)
        m_r, r_r = train_glm(batch, task, cfg)
        m_s, _ = train_glm(cb, task, cfg)
        m_m, r_m = train_glm(cb, task, cfg, mesh=mesh8)
        np.testing.assert_allclose(float(r_m.value), float(r_r.value),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(m_m.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(m_m.coefficients.means),
                                   np.asarray(m_s.coefficients.means),
                                   rtol=2e-3, atol=2e-4)

    def test_pure_l1_sparsity_preserved(self, rng, mesh8):
        """The orthant projection's exact zeros survive the mesh psum."""
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION)
        cb = chunk_batch(batch, 512)
        cfg = OptimizerConfig(max_iters=60, tolerance=1e-7, reg=l1(),
                              reg_weight=8.0)
        m_r, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
        m_m, _ = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg,
                           mesh=mesh8)
        zeros_r = np.asarray(m_r.coefficients.means) == 0.0
        zeros_m = np.asarray(m_m.coefficients.means) == 0.0
        assert zeros_m.any()
        np.testing.assert_array_equal(zeros_r, zeros_m)

    def test_sparse_rows_mesh(self, rng, mesh8):
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION, sparse=True)
        cb = chunk_batch(batch, 512)
        cfg = OptimizerConfig(max_iters=50, tolerance=1e-7, reg=l2(),
                              reg_weight=0.3)
        m_r, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
        m_m, _ = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg,
                           mesh=mesh8)
        np.testing.assert_allclose(np.asarray(m_m.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=2e-3, atol=2e-5)

    def test_hybrid_replica_data_mesh(self, rng, hybrid_mesh):
        """2-D replica×data mesh: the per-evaluation psum runs over BOTH
        axes (hierarchical lowering), same answer."""
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION, n=1900)
        cb = chunk_batch(batch, 300)
        cfg = OptimizerConfig(max_iters=60, tolerance=1e-7, reg=l2(),
                              reg_weight=0.5)
        m_r, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
        m_h, _ = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg,
                           mesh=hybrid_mesh)
        np.testing.assert_allclose(np.asarray(m_h.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=2e-3, atol=2e-5)

    def test_normalization_mesh(self, rng, mesh8):
        """The norm-shifts gsum partial rides the same single psum."""
        from photon_tpu.data.normalization import (
            NormalizationContext,
            NormalizationType,
        )

        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION)
        norm = NormalizationContext.build(
            np.asarray(batch.X),
            NormalizationType.SCALE_WITH_STANDARD_DEVIATION)
        cb = chunk_batch(batch, 512)
        cfg = OptimizerConfig(max_iters=50, tolerance=1e-7, reg=l2(),
                              reg_weight=0.2)
        m_r, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                           normalization=norm)
        m_m, _ = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg,
                           mesh=mesh8, normalization=norm)
        np.testing.assert_allclose(np.asarray(m_m.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=2e-3, atol=1e-4)

    def test_host_chunks_stay_numpy(self, rng, mesh8):
        """The peak-HBM contract survives the mesh: after a full sharded
        streamed solve the dataset is still host numpy."""
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION)
        cb = chunk_batch(batch, 256)
        cfg = OptimizerConfig(max_iters=15, tolerance=1e-7, reg=l2(),
                              reg_weight=0.5)
        model, _ = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg,
                             mesh=mesh8)
        for c in cb.X.chunks:
            assert isinstance(c, np.ndarray)
        assert isinstance(cb.y, np.ndarray)
        # the returned coefficients are NOT mesh-committed: downstream
        # scoring runs on the default device
        w = model.coefficients.means
        assert len(w.sharding.device_set) == 1


# -------------------------------------------------- communication pattern
class TestCollectivePattern:
    def _example(self, rng, mesh):
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION, n=256)
        cb = chunk_batch(batch, 256)
        obj = Objective(TaskType.LOGISTIC_REGRESSION, l2=0.4)
        w = jnp.zeros((10,), jnp.float32)
        from photon_tpu.optim.streamed import _MeshStream

        be = _MeshStream(cb, mesh)
        b = cb.mesh_chunk(0, mesh)
        return be, obj, w, b

    def test_chunk_program_has_no_collective(self, rng, mesh8):
        """The per-chunk partial program is communication-FREE: partials
        stay device-local until the evaluation's single finishing psum.
        Pinned with the shared jaxpr walker (photon_tpu.analysis)."""
        from photon_tpu.analysis import collective_counts

        be, obj, w, b = self._example(rng, mesh8)
        jaxpr = jax.make_jaxpr(
            lambda o, wv, bv: be.ops.chunk_init(o, wv, bv))(obj, w, b)
        assert not collective_counts(jaxpr)
        compiled = be.ops.chunk_init.lower(obj, w, b).compile()
        hlo = compiled.as_text()
        for bad in ("all-reduce(", "all-to-all(", "collective-permute(",
                    "all-gather(", "reduce-scatter("):
            assert bad not in hlo, f"unexpected collective {bad}"

    def test_finish_is_one_psum(self, rng, mesh8):
        """One evaluation = one hierarchical psum: value and gradient
        partials ride the SAME collective (the treeAggregate). Pinned at
        the jaxpr level — whether XLA's combiner then emits the variadic
        all-reduce as one HLO op is a backend concern (the CPU test
        backend splits it; see test_multihost's pre-existing pin)."""
        from photon_tpu.analysis import collective_counts

        be, obj, w, b = self._example(rng, mesh8)
        _, parts = be.ops.chunk_init(obj, w, b)
        jaxpr = jax.make_jaxpr(
            lambda o, wv, pv: be.ops.finish(o, wv, pv))(obj, w, parts)
        counts = collective_counts(jaxpr)
        assert counts == {"psum": 1}, \
            f"expected 1 psum per evaluation, traced {dict(counts)}"

    def test_trial_totals_are_one_psum(self, rng, mesh8):
        """A line-search trial's (φ, φ') totals also close with a single
        psum — trials never multiply the collective count."""
        from photon_tpu.analysis import collective_counts

        be, obj, w, b = self._example(rng, mesh8)
        _, (wl, wd) = be.ops.chunk_dz_phi(obj, jnp.ones(10), b.offsets,
                                          np.float32(1.0), b)
        jaxpr = jax.make_jaxpr(
            lambda t: be.ops.psum_tree(t))((wl, wd))
        counts = collective_counts(jaxpr)
        assert counts == {"psum": 1}, \
            f"expected 1 psum per trial, traced {dict(counts)}"

    def test_finish_matches_resident_value_grad(self, rng, mesh8):
        """Accumulated sharded chunk partials + the single psum == the
        resident value_and_grad, exactly the treeAggregate contract."""
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION, n=1024)
        cb = chunk_batch(batch, 300)
        obj = Objective(TaskType.LOGISTIC_REGRESSION, l2=0.4)
        w = jnp.asarray(rng.normal(size=10).astype(np.float32) * 0.3)
        from photon_tpu.optim.streamed import _MeshStream, _acc

        be = _MeshStream(cb, mesh8)
        acc = None
        for _, b in be.iter_chunks():
            _, parts = be.ops.chunk_init(obj, w, b)
            acc = parts if acc is None else _acc(acc, parts)
        f_m, g_m = be.finish(obj, w, acc)
        f_r, g_r = obj.value_and_grad(w, batch)
        np.testing.assert_allclose(float(f_m), float(f_r), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g_m), np.asarray(g_r),
                                   rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------ driver
class TestPooledBudget:
    def test_detect_budget_uses_mesh_devices(self, mesh8):
        from photon_tpu.drivers.train import _detect_hbm_budget

        per_chip = _detect_hbm_budget(mesh8)
        assert per_chip > 0
        # CPU test devices report no limit and take the device table's
        # stand-in row; the mesh path must agree with itself
        assert per_chip == _detect_hbm_budget(mesh8)

    def test_resolution_pools_budget_and_logs(self, rng, mesh8, caplog):
        """A dataset over the per-chip budget but under the pooled budget
        stays resident under the mesh; over the pooled budget it streams —
        and both verdicts are logged at INFO."""
        import logging

        from photon_tpu.data.index_map import IndexMap
        from photon_tpu.drivers.train import (TrainingParams,
                                              _resolve_streamed_objective)

        imap = IndexMap({f"f{i}\x01": i for i in range(64)}, frozen=True)
        params = TrainingParams(
            train_path="unused", output_dir="unused",
            feature_shards={"fx": {"bags": ["b"], "has_intercept": False}},
            coordinates={"fixed": {"feature_shard": "fx"}},
        )
        log = logging.getLogger("test_streamed_mesh")
        n_rows = 10_000
        # estimate = 12*n + 64*4*n = 268 B/row ≈ 2.68 MB
        per_chip = 1 << 20  # 1 MiB per chip: over per-chip, under 8x pool
        object.__setattr__(params, "hbm_budget_bytes", per_chip)
        with caplog.at_level(logging.INFO, logger="test_streamed_mesh"):
            assert _resolve_streamed_objective(
                params, {"fx": imap}, n_rows, mesh8, log) is False
            assert _resolve_streamed_objective(
                params, {"fx": imap}, n_rows, None, log) is True
        msgs = [r.message for r in caplog.records]
        assert any("verdict resident" in m and "8 device(s)" in m
                   for m in msgs)
        assert any("verdict STREAM" in m for m in msgs)

    def test_forced_streamed_with_mesh_allowed(self, rng, mesh8):
        """streamed_objective=True + mesh no longer raises — it shards."""
        import logging

        from photon_tpu.data.index_map import IndexMap
        from photon_tpu.drivers.train import (TrainingParams,
                                              _resolve_streamed_objective)

        imap = IndexMap({"a\x01": 0}, frozen=True)
        params = TrainingParams(
            train_path="unused", output_dir="unused",
            feature_shards={"fx": {"bags": ["b"], "has_intercept": False}},
            coordinates={"fixed": {"feature_shard": "fx"}},
            streamed_objective=True,
        )
        log = logging.getLogger("test_streamed_mesh")
        assert _resolve_streamed_objective(
            params, {"fx": imap}, 100, mesh8, log) is True
