"""Streamed (out-of-HBM) objective mode: chunk partials, the host-driven
L-BFGS/OWL-QN solvers, and the training driver's HBM-budget auto-trip.

The contract under test is the ISSUE's acceptance line: a streamed fit's
value/gradient and FINAL COEFFICIENTS match the resident path to f32
accumulation tolerance, across logistic + linear and L-BFGS + OWL-QN, and
the dataset itself never becomes device-resident (host chunks stay numpy).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import (
    ChunkedBatch,
    ChunkedMatrix,
    chunk_batch,
    make_batch,
)
from photon_tpu.data.matrix import SparseRows
from photon_tpu.models.training import train_glm, train_glm_grid
from photon_tpu.ops.losses import TaskType
from photon_tpu.ops.objective import Objective
from photon_tpu.optim.config import OptimizerConfig, OptimizerType
from photon_tpu.optim.regularization import elastic_net, l1, l2


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


TASKS = [TaskType.LOGISTIC_REGRESSION, TaskType.LINEAR_REGRESSION]


class TestChunkedContainers:
    def test_chunk_batch_shapes_and_padding(self, rng):
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION, n=1000)
        cb = chunk_batch(batch, 256)
        assert cb.n == 1000
        assert cb.n_chunks == 4  # ceil(1000/256)
        assert cb.chunk_rows == 256
        assert cb.X.n_padded == 1024
        # padding rows are weight-0, so no reduction can see them
        assert (cb.weights[1000:] == 0.0).all()
        assert (cb.y[1000:] == 0.0).all()
        # chunks are HOST numpy — the whole point of the regime
        for c in cb.X.chunks:
            assert isinstance(c, np.ndarray)
        # concatenating the chunks reproduces the dataset
        np.testing.assert_array_equal(
            np.concatenate(cb.X.chunks)[:1000], np.asarray(batch.X))

    def test_iter_device_yields_device_chunks(self, rng):
        cb = chunk_batch(_problem(rng, TaskType.LOGISTIC_REGRESSION, n=600),
                         200)
        seen = []
        for i, b in cb.iter_device():
            seen.append(i)
            assert isinstance(b.X, jax.Array)
            assert b.X.shape == (200, 10)
        assert seen == [0, 1, 2]

    def test_sparse_chunking(self, rng):
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION, n=700,
                         sparse=True)
        cb = chunk_batch(batch, 256)
        assert all(isinstance(c, SparseRows) for c in cb.X.chunks)
        assert all(isinstance(c.indices, np.ndarray) for c in cb.X.chunks)
        assert cb.X.n_features == 10

    def test_hybrid_rejected(self, rng):
        from photon_tpu.data.dataset import chunk_matrix
        from photon_tpu.data.matrix import to_hybrid

        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION, n=128,
                         sparse=True)
        H = to_hybrid(jax.device_get(batch.X), d_dense=4)
        with pytest.raises(TypeError, match="host-chunked"):
            chunk_matrix(H, 64)


class TestChunkPartials:
    @pytest.mark.parametrize("task", TASKS)
    def test_partials_match_value_and_grad(self, rng, task):
        """Accumulated chunk partials == the resident single-pass (f, g):
        the treeAggregate leaf is exact, not approximate."""
        batch = _problem(rng, task, n=1024)
        cb = chunk_batch(batch, 256)
        obj = Objective(task, l2=0.4)
        w = jnp.asarray(rng.normal(size=10).astype(np.float32) * 0.3)
        f_r, g_r = obj.value_and_grad(w, batch)
        acc = None
        for i, b in cb.iter_device():
            _, parts = (obj.chunk_value_grad_partials(w, b))
            acc = parts if acc is None else obj.add_partials(acc, parts)
        f_s, g_s = obj.finish_value_grad(w, acc)
        np.testing.assert_allclose(f_r, f_s, rtol=1e-5)
        np.testing.assert_allclose(g_r, g_s, rtol=1e-4, atol=1e-4)

    def test_phi_partials_match_margin_api(self, rng):
        """chunk_phi_partials over chunks + ray coefficients ==
        Objective.phi_at on the full batch."""
        task = TaskType.LOGISTIC_REGRESSION
        batch = _problem(rng, task, n=1024)
        cb = chunk_batch(batch, 256)
        obj = Objective(task, l2=0.2)
        w = jnp.asarray(rng.normal(size=10).astype(np.float32) * 0.3)
        p = jnp.asarray(rng.normal(size=10).astype(np.float32))
        z = obj.margin(w, batch)
        dz = obj.direction_margin(p, batch)
        a = 0.37
        f_r, d_r = obj.phi_at(z, dz, a, w, p, batch)
        wl = wd = 0.0
        for i, b in cb.iter_device():
            zc = obj.margin(w, b)
            dzc = obj.direction_margin(p, b)
            wl_i, wd_i = obj.chunk_phi_partials(zc, dzc, a, b.y, b.weights)
            wl, wd = wl + wl_i, wd + wd_i
        c0, c1, c2 = obj.ray_reg_coeffs(w, p)
        f_s = wl + c0 + a * (c1 + 0.5 * a * c2)
        d_s = wd + c1 + a * c2
        np.testing.assert_allclose(f_r, f_s, rtol=1e-5)
        np.testing.assert_allclose(d_r, d_s, rtol=1e-4, atol=1e-5)


class TestStreamedSolvers:
    @pytest.mark.parametrize("task", TASKS)
    def test_lbfgs_matches_resident(self, rng, task):
        batch = _problem(rng, task)
        cb = chunk_batch(batch, 300)  # uneven tail chunk on purpose
        cfg = OptimizerConfig(max_iters=60, tolerance=1e-7, reg=l2(),
                              reg_weight=0.5)
        m_r, r_r = train_glm(batch, task, cfg)
        m_s, r_s = train_glm(cb, task, cfg)
        assert bool(r_s.converged) == bool(r_r.converged)
        np.testing.assert_allclose(float(r_s.value), float(r_r.value),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(m_s.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=2e-3, atol=2e-5)

    @pytest.mark.parametrize("task", TASKS)
    def test_owlqn_matches_resident(self, rng, task):
        batch = _problem(rng, task)
        cb = chunk_batch(batch, 300)
        cfg = OptimizerConfig(max_iters=60, tolerance=1e-7,
                              reg=elastic_net(0.5), reg_weight=0.3)
        m_r, r_r = train_glm(batch, task, cfg)
        m_s, r_s = train_glm(cb, task, cfg)
        np.testing.assert_allclose(float(r_s.value), float(r_r.value),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(m_s.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=2e-3, atol=2e-4)

    def test_pure_l1_sparsity_preserved(self, rng):
        """Streamed OWL-QN keeps the orthant projection's exact zeros."""
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION)
        cb = chunk_batch(batch, 512)
        cfg = OptimizerConfig(max_iters=60, tolerance=1e-7, reg=l1(),
                              reg_weight=8.0)
        m_r, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
        m_s, _ = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
        zeros_r = np.asarray(m_r.coefficients.means) == 0.0
        zeros_s = np.asarray(m_s.coefficients.means) == 0.0
        assert zeros_s.any()  # the weight is strong enough to zero coords
        np.testing.assert_array_equal(zeros_r, zeros_s)

    def test_sparse_rows_streamed(self, rng):
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION, sparse=True)
        cb = chunk_batch(batch, 512)
        cfg = OptimizerConfig(max_iters=50, tolerance=1e-7, reg=l2(),
                              reg_weight=0.3)
        m_r, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
        m_s, _ = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
        np.testing.assert_allclose(np.asarray(m_s.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=2e-3, atol=2e-5)

    def test_single_chunk_degenerates_to_resident(self, rng):
        """chunk_rows >= n: one chunk, still the streamed code path."""
        batch = _problem(rng, TaskType.LINEAR_REGRESSION, n=500)
        cb = chunk_batch(batch, 4096)
        assert cb.n_chunks == 1
        cfg = OptimizerConfig(max_iters=40, tolerance=1e-7, reg=l2(),
                              reg_weight=0.2)
        m_r, _ = train_glm(batch, TaskType.LINEAR_REGRESSION, cfg)
        m_s, _ = train_glm(cb, TaskType.LINEAR_REGRESSION, cfg)
        np.testing.assert_allclose(np.asarray(m_s.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=1e-3, atol=1e-5)

    def test_normalization_round_trip(self, rng):
        from photon_tpu.data.normalization import (
            NormalizationContext,
            NormalizationType,
        )

        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION)
        Xh = np.asarray(batch.X)
        norm = NormalizationContext.build(
            Xh, NormalizationType.SCALE_WITH_STANDARD_DEVIATION)
        cb = chunk_batch(batch, 512)
        cfg = OptimizerConfig(max_iters=50, tolerance=1e-7, reg=l2(),
                              reg_weight=0.2)
        m_r, _ = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                           normalization=norm)
        m_s, _ = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg,
                           normalization=norm)
        # atol covers near-zero coordinates, where the normalization
        # unfold amplifies f32 accumulation-order noise
        np.testing.assert_allclose(np.asarray(m_s.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=2e-3, atol=1e-4)

    def test_host_chunks_stay_numpy(self, rng):
        """The peak-device-memory contract's observable: after a full
        streamed solve the dataset is still host numpy — nothing pinned
        it to the device."""
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION)
        cb = chunk_batch(batch, 256)
        cfg = OptimizerConfig(max_iters=20, tolerance=1e-7, reg=l2(),
                              reg_weight=0.5)
        train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
        for c in cb.X.chunks:
            assert isinstance(c, np.ndarray)
        assert isinstance(cb.y, np.ndarray)

    def test_chunked_scoring_matches_resident(self, rng):
        batch = _problem(rng, TaskType.LOGISTIC_REGRESSION)
        cb = chunk_batch(batch, 300)
        cfg = OptimizerConfig(max_iters=30, tolerance=1e-7, reg=l2(),
                              reg_weight=0.5)
        m_s, _ = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
        scores_chunked = np.asarray(m_s.score(cb.X))
        scores_resident = np.asarray(m_s.score(batch.X))
        assert scores_chunked.shape == (batch.n,)
        np.testing.assert_allclose(scores_chunked, scores_resident,
                                   rtol=1e-5, atol=1e-5)

    def test_tron_rejected(self, rng):
        cb = chunk_batch(_problem(rng, TaskType.LOGISTIC_REGRESSION, n=256),
                         128)
        cfg = OptimizerConfig(optimizer=OptimizerType.TRON, reg=l2(),
                              reg_weight=0.1)
        with pytest.raises(ValueError, match="TRON"):
            train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)

    def test_grid_rejected_mesh_dispatches(self, rng, mesh8):
        """The lane grid still refuses ChunkedBatch (every lane would
        multiply the host stream), but a mesh now DISPATCHES to the
        sharded streamed solve (tests/test_streamed_mesh.py pins its
        parity) instead of raising."""
        cb = chunk_batch(_problem(rng, TaskType.LOGISTIC_REGRESSION, n=256),
                         128)
        cfg = OptimizerConfig(max_iters=10, reg=l2(), reg_weight=0.1)
        with pytest.raises(ValueError, match="sequential"):
            train_glm_grid(cb, TaskType.LOGISTIC_REGRESSION, cfg,
                           [0.1, 1.0])
        model, res = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg,
                               mesh=mesh8)
        assert np.isfinite(np.asarray(model.coefficients.means)).all()


# ------------------------------------------------------ device chunk ring
class _Running:
    """Stands for a chunk program's outputs that are still being computed
    when the ring asks."""

    def is_ready(self):
        return False

    def block_until_ready(self):
        return self


def _ring_problem(seed, n=64, d=8, chunk=16):
    rng = np.random.default_rng(seed)
    Xd = rng.normal(size=(n, d)).astype(np.float32)
    return chunk_batch(make_batch(
        Xd, (rng.uniform(size=n) < 0.5).astype(np.float32)), chunk)


def _alive(chunk):
    return any(not leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(chunk))


class TestDeviceChunkRing:
    @pytest.mark.parametrize("speaks", [False, True],
                             ids=["silent", "speaks"])
    def test_rotation_order_and_prearm(self, speaks):
        cb = _ring_problem(5)
        ring = cb.device_ring(prefetch=2)
        for p in range(3):
            seen = []
            for i, b in ring.stream_pass():
                seen.append((i, np.asarray(b.y)))
                if speaks:
                    ring.consumed(jnp.sum(b.X))
            assert [i for i, _ in seen] == [0, 1, 2, 3]
            for i, yb in seen:
                np.testing.assert_array_equal(yb, cb.y[i * 16:(i + 1) * 16])
            # pre-arm: the next pass's first upload(s) are already issued —
            # a ring that was told of the last chunk's program holds that
            # chunk and ONE primed chunk, issued behind that program
            if speaks:
                assert len(ring._window) == 1 and ring._next == 1
                assert _alive(ring._spoken[0])
            else:
                assert len(ring._window) == 2 and ring._next == 2
        ring.close()
        assert not ring._window and ring._spoken is None

    @pytest.mark.parametrize("speaks", [False, True],
                             ids=["silent", "speaks"])
    def test_abandoned_pass_resets(self, speaks):
        rng = np.random.default_rng(6)
        Xd = rng.normal(size=(48, 4)).astype(np.float32)
        cb = chunk_batch(make_batch(
            Xd, np.zeros(48, np.float32)), 16)
        ring = cb.device_ring(prefetch=2)
        it = ring.stream_pass()
        _, b = next(it)  # consume chunk 0, abandon mid-pass
        if speaks:
            ring.consumed(jnp.sum(b.X))
        it.close()
        assert len(ring._window) == 0 and ring._next == 0
        assert ring._spoken is None and ring._handed is None
        order = [i for i, _ in ring.stream_pass()]
        assert order == [0, 1, 2]  # restarts at chunk 0, nothing stale

    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_never_more_than_depth_chunks_alive_at_a_put(self, depth):
        """A chunk is freed before the upload that would make one more
        than the ring's depth: at every `put` of a consumer that speaks,
        fewer than `depth` of the chunks uploaded so far are alive."""
        cb = _ring_problem(8)
        ring = cb.device_ring(prefetch=depth)
        made, alive_at_put = [], []
        put = ring._put

        def spy(i):
            alive_at_put.append(sum(_alive(c) for c in made))
            made.append(put(i))
            return made[-1]

        ring._put = spy
        for _ in range(3):
            for _, b in ring.stream_pass():
                out = jnp.sum(b.X)
                del b  # the ring's name is the only one left
                ring.consumed(out)
        assert len(made) == 12 + max(depth - 1, 1)
        assert max(alive_at_put) == depth - 1
        ring.close()
        assert not ring._window and ring._spoken is None
        # every consumed chunk is freed; what was primed for a fourth pass
        # lives on under this test's names alone
        assert sum(_alive(c) for c in made) == len(made) - 12

    @pytest.mark.parametrize("speaks", [False, True],
                             ids=["silent", "speaks"])
    def test_uploads_behind_compute_counts_what_a_program_hides(self,
                                                                speaks):
        """`stream.uploads_behind_compute` is the uploads issued while the
        program `consumed` was told of had not finished: every consumed
        chunk's successor but the very first (primed before anything was
        handed out) for a consumer that speaks, none for one that does
        not."""
        from photon_tpu import telemetry

        cb = _ring_problem(9)
        ring = cb.device_ring(prefetch=2)
        with telemetry.run("t") as run:
            for _ in range(2):
                for _, b in ring.stream_pass():
                    if speaks:
                        ring.consumed(_Running())
            c = run.report_compact()["counters"]
        assert c["stream.chunk_uploads"] == 8
        assert c.get("stream.uploads_behind_compute", 0) == (
            8 - 1 if speaks else 0)

    def test_outputs_already_computed_are_not_counted_behind(self):
        from photon_tpu import telemetry

        cb = _ring_problem(10)
        ring = cb.device_ring(prefetch=2)
        with telemetry.run("t") as run:
            for _, b in ring.stream_pass():
                ring.consumed(jax.block_until_ready(jnp.sum(b.X)))
            c = run.report_compact()["counters"]
        assert c.get("stream.uploads_behind_compute", 0) == 0

    @pytest.mark.parametrize("solver", ["lbfgs", "owlqn"])
    def test_solve_is_the_old_ring_orders_bits(self, solver, monkeypatch):
        """Only the order of two host calls moved: a streamed solve whose
        ring uploads behind the chunk program returns the very bits of the
        old order (upload first), re-enacted by a consumer that tells the
        ring nothing."""
        from photon_tpu.data.dataset import DeviceChunkRing

        rng = np.random.default_rng(12)
        cb = chunk_batch(_problem(rng, TaskType.LOGISTIC_REGRESSION,
                                  n=512, sparse=True), 128)
        if solver == "lbfgs":
            cfg = OptimizerConfig(max_iters=8, tolerance=0.0, reg=l2(),
                                  reg_weight=1e-2, history=4)
        else:
            cfg = OptimizerConfig(max_iters=8, tolerance=0.0,
                                  reg=elastic_net(0.5), reg_weight=1e-2,
                                  history=4, optimizer=OptimizerType.OWLQN)
        new = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)[1]
        puts = []
        real_init = DeviceChunkRing.__init__

        def init(self, *a, **kw):
            real_init(self, *a, **kw)
            put = self._put
            self._put = lambda i: (puts.append(i), put(i))[1]

        monkeypatch.setattr(DeviceChunkRing, "__init__", init)
        monkeypatch.setattr(DeviceChunkRing, "consumed",
                            lambda self, outputs: outputs)
        old = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)[1]
        assert puts[:3] == [0, 1, 2] and len(puts) % 4 == 2  # two primed
        np.testing.assert_array_equal(np.asarray(new.w), np.asarray(old.w))
        np.testing.assert_array_equal(np.asarray(new.loss_history),
                                      np.asarray(old.loss_history))
        assert int(new.evaluations) == int(old.evaluations)

    @pytest.mark.parametrize("solver", ["lbfgs", "owlqn"])
    def test_solve_is_the_same_bits_under_a_run(self, solver):
        """The chunk timeline's spans are host bookkeeping around the host
        loop's own turns: a solve with a telemetry run attached (every
        span recorded, every clock reading taken by the span) returns the
        very bits of one with none (every site the shared no-op)."""
        from photon_tpu import telemetry

        rng = np.random.default_rng(13)
        cb = chunk_batch(_problem(rng, TaskType.LOGISTIC_REGRESSION,
                                  n=512, sparse=True), 128)
        if solver == "lbfgs":
            cfg = OptimizerConfig(max_iters=8, tolerance=0.0, reg=l2(),
                                  reg_weight=1e-2, history=4)
        else:
            cfg = OptimizerConfig(max_iters=8, tolerance=0.0,
                                  reg=elastic_net(0.5), reg_weight=1e-2,
                                  history=4, optimizer=OptimizerType.OWLQN)
        assert telemetry.current_run() is None
        bare = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)[1]
        with telemetry.run("t") as run:
            seen = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)[1]
            counts = run.report_compact()["span_counts"]
        assert any(p.endswith("stream.pass/stream.dispatch")
                   for p in counts)
        np.testing.assert_array_equal(np.asarray(bare.w),
                                      np.asarray(seen.w))
        np.testing.assert_array_equal(np.asarray(bare.loss_history),
                                      np.asarray(seen.loss_history))
        assert int(bare.evaluations) == int(seen.evaluations)

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


# ------------------------------------------------------------------ driver
def _write_game_parts(root, n_files=2, rows_per_file=260, seed=0):
    from photon_tpu.data.avro_io import write_avro
    from photon_tpu.data.ingest import training_example_schema

    rng = np.random.default_rng(seed)
    schema = training_example_schema(feature_bags=("global", "puser"),
                                     entity_fields=("userId",))
    os.makedirs(root, exist_ok=True)
    for fi in range(n_files):
        records = []
        for i in range(rows_per_file):
            age = float(rng.normal())
            ctr = float(rng.normal(2.0, 3.0))
            u = int(rng.integers(0, 9))
            margin = 1.1 * age - 0.3 * (ctr - 2.0) + 0.2 * (u - 4)
            y = float(rng.uniform() < 1 / (1 + np.exp(-margin)))
            records.append({
                "response": y, "offset": None, "weight": None,
                "uid": f"r{fi}_{i}", "userId": f"u{u}",
                "global": [
                    {"name": "age", "term": "", "value": age},
                    {"name": "ctr", "term": "", "value": ctr},
                ],
                "puser": [{"name": "bias", "term": "", "value": 1.0}],
            })
        write_avro(root / f"part-{fi:03d}.avro", records, schema,
                   block_records=64)
    return root


_SHARDS = {
    "fixedShard": {"bags": ["global"], "has_intercept": True},
    "userShard": {"bags": ["puser"], "has_intercept": False},
}
_COORDS = {
    "fixed": {"feature_shard": "fixedShard", "reg_type": "l2",
              "reg_weight": 0.5, "max_iters": 40},
    "perUser": {"feature_shard": "userShard", "entity_name": "userId",
                "reg_type": "l2", "reg_weight": 2.0, "max_iters": 20},
}


@pytest.fixture(scope="module")
def streamed_job(tmp_path_factory):
    root = tmp_path_factory.mktemp("streamed_job")
    _write_game_parts(root / "train", seed=1)
    _write_game_parts(root / "val", n_files=1, rows_per_file=150, seed=2)
    return root


def _params(root, out, **kw):
    from photon_tpu.drivers import TrainingParams

    base = dict(
        train_path=str(root / "train"),
        validation_path=str(root / "val"),
        output_dir=str(out),
        feature_shards=_SHARDS,
        coordinates=_COORDS,
        entity_fields=["userId"],
        n_sweeps=2,
    )
    base.update(kw)
    return TrainingParams(**base)


class TestStreamedDriver:
    def test_forced_streamed_matches_resident(self, streamed_job, tmp_path):
        """The mixed-residency GAME fit (fixed shard host-chunked, RE shard
        resident) converges to the resident driver's model."""
        from photon_tpu.drivers import run_training

        a = run_training(_params(streamed_job, tmp_path / "resident",
                                 streaming=False, streamed_objective=False))
        b = run_training(_params(streamed_job, tmp_path / "streamed",
                                 streamed_objective=True,
                                 objective_chunk_rows=128,
                                 streaming_chunk_rows=128))
        assert b.best.validation_score == pytest.approx(
            a.best.validation_score, abs=5e-3)
        wa = np.asarray(
            a.best.model.coordinates["fixed"].model.coefficients.means)
        wb = np.asarray(
            b.best.model.coordinates["fixed"].model.coefficients.means)
        np.testing.assert_allclose(wb, wa, rtol=5e-3, atol=5e-4)
        np.testing.assert_allclose(
            np.asarray(b.best.model.coordinates["perUser"].coefficients),
            np.asarray(a.best.model.coordinates["perUser"].coefficients),
            rtol=5e-3, atol=5e-4)

    def test_forced_streamed_with_mesh_matches_resident(
            self, streamed_job, tmp_path, mesh8):
        """The whole driver pipeline with a mesh + streamed objective: the
        fixed shard's chunks row-shard across the mesh (the pod-scale
        treeAggregate), RE shards stay resident, and the fit matches the
        resident single-device driver."""
        from photon_tpu.drivers import run_training

        a = run_training(_params(streamed_job, tmp_path / "resident",
                                 streaming=False, streamed_objective=False))
        b = run_training(_params(streamed_job, tmp_path / "mesh_streamed",
                                 streamed_objective=True,
                                 objective_chunk_rows=100,
                                 streaming_chunk_rows=128), mesh=mesh8)
        assert b.best.validation_score == pytest.approx(
            a.best.validation_score, abs=5e-3)
        wa = np.asarray(
            a.best.model.coordinates["fixed"].model.coefficients.means)
        wb = np.asarray(
            b.best.model.coordinates["fixed"].model.coefficients.means)
        np.testing.assert_allclose(wb, wa, rtol=5e-3, atol=5e-4)

    def test_auto_trip_on_tiny_budget(self, streamed_job, tmp_path,
                                      monkeypatch):
        """streamed_objective=None + an HBM budget smaller than the data
        estimate engages the out-of-HBM read (and the fixed shard really is
        host-chunked inside the fit)."""
        import photon_tpu.data.streaming as streaming_mod
        from photon_tpu.drivers import run_training

        captured = {}
        real = streaming_mod.stream_to_host

        def spy(*a, **kw):
            data, n_real = real(*a, **kw)
            captured["shards"] = data.shards
            return data, n_real

        monkeypatch.setattr(streaming_mod, "stream_to_host", spy)
        out = run_training(_params(
            streamed_job, tmp_path / "auto", streamed_objective=None,
            hbm_budget_bytes=1024,  # far below the ~520-row dataset
            streaming=True, objective_chunk_rows=100))
        assert np.isfinite(out.best.validation_score)
        assert isinstance(captured["shards"]["fixedShard"], ChunkedMatrix)
        assert captured["shards"]["fixedShard"].n_chunks >= 2
        # the RE shard must stay resident (bucketing gathers rows)
        assert not isinstance(captured["shards"]["userShard"], ChunkedMatrix)

    def test_big_budget_stays_resident(self, streamed_job, tmp_path,
                                       monkeypatch):
        import photon_tpu.data.streaming as streaming_mod
        from photon_tpu.drivers import run_training

        calls = []
        real = streaming_mod.stream_to_host

        def spy(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(streaming_mod, "stream_to_host", spy)
        run_training(_params(streamed_job, tmp_path / "big",
                             streamed_objective=None,
                             hbm_budget_bytes=1 << 40, streaming=True))
        assert not calls
