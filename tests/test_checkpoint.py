"""Elastic runs (photon_tpu/checkpoint): crash-consistent snapshot/restore
with deterministic fault injection.

THE acceptance property, in PR-5's bit-parity discipline: kill a streamed
(and streamed-mesh) GLM solve and a GAME run (straggler budgeting on, so
the pipelined block loop runs) at EVERY registered fault-injection site —
chunk upload, evaluation close, bucket retire, mid-snapshot-write, and
the commit rename itself — restore from the last committed snapshot, and
finish with coefficients EXACTLY equal (f64-compared) to the
uninterrupted run's. Plus the restore edge cases: mesh-8 snapshots onto
mesh-4/single-device, a NEWER snapshot schema refused with a clear error,
empty-history resume at iteration 0 == cold start, and the store-level
retention/async-writer/retry machinery.
"""
import json
import os

import numpy as np
import pytest

from photon_tpu import checkpoint
from photon_tpu.data.dataset import chunk_batch, make_batch
from photon_tpu.models.training import train_glm
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim import regularization as reg
from photon_tpu.optim.config import OptimizerConfig

pytestmark = pytest.mark.release_programs

TASK = TaskType.LOGISTIC_REGRESSION
# tolerance=0 forces the full iteration budget: the kill/restore matrix
# then exercises mid-run cuts, not an early-converged triviality
CFG = OptimizerConfig(max_iters=10, tolerance=0.0, reg=reg.l2(),
                      reg_weight=1e-2, history=4)
# the registered KILL sites (snapshot_io is a retry site, not a kill site)
KILL_SITES = ("chunk_upload", "evaluation", "snapshot_write", "commit")


def _stream_data(chunk_rows=32):
    rng = np.random.default_rng(0)
    n, d = 96, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    return chunk_batch(make_batch(X, y), chunk_rows)


@pytest.fixture(scope="module")
def cb():
    return _stream_data()


def _solve(cb, mesh=None, cfg=CFG):
    _, res = train_glm(cb, TASK, cfg, mesh=mesh)
    return np.asarray(res.w, np.float64)


def _kill_then_resume(ckdir, run_fn, site, occ, async_writer=False):
    """Arm (site, occ), run; on the injected kill, resume from the last
    committed snapshot. Returns (final_w, was_killed)."""
    try:
        with checkpoint.session(str(ckdir), every_evals=1, every_s=None,
                                async_writer=async_writer):
            with checkpoint.fault_plan(
                    checkpoint.FaultPlan.kill_at(site, occ)):
                return run_fn(), False
    except checkpoint.InjectedFault:
        pass
    with checkpoint.session(str(ckdir), every_evals=1, every_s=None,
                            async_writer=async_writer):
        return run_fn(), True


def _occurrences(n):
    """First / middle / last — the spread each site is killed at."""
    return sorted({1, (n + 1) // 2, n})


# ------------------------------------------------------------- streamed GLM
class TestStreamedBitParity:
    def test_armed_but_unkilled_run_is_bit_identical(self, cb, tmp_path):
        """Checkpointing must observe, never perturb: a fully-armed run
        (snapshots every evaluation) equals the unarmed run bitwise."""
        w_ref = _solve(cb)
        with checkpoint.session(str(tmp_path / "ck"), every_evals=1,
                                every_s=None, async_writer=False):
            w_armed = _solve(cb)
        np.testing.assert_array_equal(w_ref, w_armed)

    def test_kill_every_site_resume_bit_identical(self, cb, tmp_path):
        """THE acceptance matrix (single chip): every kill site, killed at
        first/middle/last occurrence, restores and finishes bit-identical
        — including kills DURING a snapshot write and during the commit
        rename (restore falls back to the previous committed manifest)."""
        w_ref = _solve(cb)
        with checkpoint.session(str(tmp_path / "rec"), every_evals=1,
                                every_s=None, async_writer=False):
            with checkpoint.record_sites() as rec:
                _solve(cb)
        counts = dict(rec.hits)
        for site in KILL_SITES:
            assert counts.get(site, 0) > 0, f"site {site} never hit"
        for site in KILL_SITES:
            for occ in _occurrences(counts[site]):
                w, killed = _kill_then_resume(
                    tmp_path / f"{site}_{occ}", lambda: _solve(cb),
                    site, occ)
                assert killed, (site, occ)
                np.testing.assert_array_equal(
                    w_ref, w, err_msg=f"drift after kill at {site}#{occ}")

    def test_empty_history_resume_at_it0_equals_cold_start(self, cb,
                                                           tmp_path):
        """Kill right after the it=0 snapshot (before iteration 1
        completes): the restored state has an EMPTY curvature history and
        must replay the whole solve bit-identically to a cold start."""
        w_ref = _solve(cb)
        ckdir = tmp_path / "it0"
        # evaluation #1 is the initial pass (snapshotted at it=0);
        # evaluation #2 is iteration 1's direction pass — kill there
        w, killed = _kill_then_resume(ckdir, lambda: _solve(cb),
                                      "evaluation", 2)
        assert killed
        assert checkpoint.SnapshotStore(str(ckdir)).latest_seq() >= 0
        np.testing.assert_array_equal(w_ref, w)

    def test_async_writer_kill_resume(self, cb, tmp_path):
        """The production shape: snapshots committed on the writer
        thread. A kill mid-run still restores bit-identically, and the
        session close drains the queue."""
        w_ref = _solve(cb)
        w, killed = _kill_then_resume(tmp_path / "async",
                                      lambda: _solve(cb),
                                      "evaluation", 9, async_writer=True)
        assert killed
        np.testing.assert_array_equal(w_ref, w)

    def test_owlqn_streamed_kill_resume(self, cb, tmp_path):
        cfg = OptimizerConfig(max_iters=8, tolerance=0.0, reg=reg.l1(),
                              reg_weight=1e-3, history=4)
        w_ref = _solve(cb, cfg=cfg)
        w, killed = _kill_then_resume(tmp_path / "owlqn",
                                      lambda: _solve(cb, cfg=cfg),
                                      "evaluation", 5)
        assert killed
        np.testing.assert_array_equal(w_ref, w)


    @pytest.mark.parametrize("kind", ["lbfgs", "owlqn"])
    def test_snapshot_without_carried_products_resumes(self, cb, tmp_path,
                                                       monkeypatch, kind):
        """A snapshot written before the history carried its inner
        products — bare (m, d) rings, `rho`, the newest pair's sᵀy / yᵀy
        as two floats — still resumes: the products are recomputed from
        the slots (optim.lbfgs.history_from_slots), so the resumed solve
        is the same solve to f32 reduction noise, not to the bit."""
        from photon_tpu.optim import streamed

        cfg = CFG if kind == "lbfgs" else OptimizerConfig(
            max_iters=8, tolerance=0.0, reg=reg.l1(), reg_weight=1e-3,
            history=4)
        w_ref = _solve(cb, cfg=cfg)
        pack = streamed._pack_stream_state

        def pack_as_before(*args, **kwargs):
            st = pack(*args, **kwargs)
            m, d = cfg.history, st["d"]
            for k in ("S", "Y"):
                st[k] = np.asarray(st[k])[:m].reshape(m, -1)[:, :d]
            sy, yy = (np.asarray(st.pop(k)).reshape(m, m)
                      for k in ("h_sy", "h_yy"))
            order = (st["h_idx"] - 1 - np.arange(m)) % m  # recency -> slot
            rho = np.zeros(m, np.float32)
            rho[order] = 1.0 / np.maximum(np.diag(sy), 1e-20)
            st.update(rho=rho, h_sy=float(sy[0, 0]), h_yy=float(yy[0, 0]))
            for k in ("h_sv", "h_yv"):
                del st[k]
            return st

        ckdir = tmp_path / "before"
        try:
            with monkeypatch.context() as mp:
                mp.setattr(streamed, "_pack_stream_state", pack_as_before)
                with checkpoint.session(str(ckdir), every_evals=1,
                                        every_s=None, async_writer=False):
                    with checkpoint.fault_plan(
                            checkpoint.FaultPlan.kill_at("evaluation", 6)):
                        _solve(cb, cfg=cfg)
        except checkpoint.InjectedFault:
            pass
        restores = []
        monkeypatch.setattr(
            streamed, "history_from_slots",
            lambda *a, f=streamed.history_from_slots: (
                restores.append(int(a[3])), f(*a))[1])
        with checkpoint.session(str(ckdir), every_evals=1, every_s=None,
                                async_writer=False):
            w = _solve(cb, cfg=cfg)
        assert restores and restores[0] > 0  # resumed with pairs in it
        np.testing.assert_allclose(w, w_ref, rtol=0, atol=2e-5)


# ------------------------------------------------------------ streamed mesh
class TestStreamedMeshBitParity:
    def test_mesh_kill_every_site_resume_bit_identical(self, cb, tmp_path,
                                                       mesh8):
        """The mesh half of the acceptance matrix: every kill site —
        including mid-snapshot-write and mid-commit — restores onto the
        SAME mesh bit-identically."""
        w_ref = _solve(cb, mesh=mesh8)
        for site, occ in (("evaluation", 8), ("chunk_upload", 7),
                          ("snapshot_write", 3), ("commit", 3)):
            w, killed = _kill_then_resume(
                tmp_path / f"mesh_{site}", lambda: _solve(cb, mesh=mesh8),
                site, occ)
            assert killed, site
            np.testing.assert_array_equal(w_ref, w, err_msg=site)

    def test_mesh8_snapshot_restores_on_mesh4_and_single(self, cb,
                                                         tmp_path, mesh8):
        """Topology-changing restore: the margin caches re-shard through
        the canonical global row layout. Cross-topology f32 reduction
        order differs, so the guarantee is the same OPTIMUM, not the same
        bits (bit-parity is same-topology)."""
        from photon_tpu.parallel.mesh import make_mesh

        w_ref = _solve(cb, mesh=mesh8)
        for target, label in ((make_mesh(n_devices=4), "mesh4"),
                              (None, "single")):
            ckdir = tmp_path / f"reshard_{label}"
            try:
                with checkpoint.session(str(ckdir), every_evals=1,
                                        every_s=None, async_writer=False):
                    with checkpoint.fault_plan(
                            checkpoint.FaultPlan.kill_at("evaluation", 9)):
                        _solve(cb, mesh=mesh8)
            except checkpoint.InjectedFault:
                pass
            with checkpoint.session(str(ckdir), every_evals=1,
                                    every_s=None, async_writer=False):
                w = _solve(cb, mesh=target)
            assert checkpoint.SnapshotStore(str(ckdir)).latest_seq() >= 0
            np.testing.assert_allclose(w_ref, w, atol=5e-3, err_msg=label)


# -------------------------------------------------------------------- GAME
def _game_problem():
    from photon_tpu.game import (GameData, RandomEffectCoordinate,
                                 RandomEffectDataset)
    from photon_tpu.game.dataset import FixedEffectDataset
    from photon_tpu.game.fixed_effect import FixedEffectCoordinate

    rng = np.random.default_rng(3)
    E, d = 13, 4
    rows = rng.integers(3, 28, size=E)
    ent = np.repeat(np.arange(E), rows)
    rng.shuffle(ent)
    n = ent.shape[0]
    Xr = rng.normal(size=(n, d)).astype(np.float32)
    Xf = rng.normal(size=(n, 3)).astype(np.float32)
    w_re = rng.normal(size=(E, d)) * 1.5
    logit = np.einsum("nd,nd->n", Xr, w_re[ent]) + \
        Xf @ np.array([0.5, -0.3, 0.2])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    data = GameData.build(y, {"s": Xr, "fx": Xf},
                          {"e": ent.astype(np.int64)})
    ds = RandomEffectDataset.build(data, "e", "s", max_blocks=2)
    cfg = OptimizerConfig(max_iters=30, reg=reg.l2(), reg_weight=0.5,
                          history=4)

    def build_coords():
        fe_ds = FixedEffectDataset(X=data.shards["fx"], y=data.y,
                                   weights=data.weights, shard_name="fx")
        return {
            "fixed": FixedEffectCoordinate(fe_ds, TASK, cfg),
            # straggler budgeting ON: the fused one-dispatch path gates
            # itself off, so the pipelined train() block loop (the
            # checkpointed path) runs
            "re": RandomEffectCoordinate(ds, TASK, cfg, pipeline_depth=1,
                                         straggler_budget=8),
        }

    def run():
        from photon_tpu.game.coordinate_descent import coordinate_descent

        return coordinate_descent(build_coords(), data.y, data.weights,
                                  np.zeros(n, np.float32), TASK,
                                  n_sweeps=2)

    return run


def _game_w(out):
    return (np.asarray(out.model.coordinates["fixed"]
                       .model.coefficients.means, np.float64),
            np.asarray(out.model.coordinates["re"].coefficients,
                       np.float64))


class TestGameBitParity:
    def test_kill_every_site_resume_bit_identical(self, tmp_path):
        """The GAME acceptance matrix: straggler-budgeted random-effect
        training + a fused fixed coordinate, 2 sweeps; killed at EVERY
        bucket retirement plus mid-snapshot-write and mid-commit, each
        resume finishing bit-identically (coefficients AND objective
        history)."""
        run = _game_problem()
        ref = run()
        wf_ref, wr_ref = _game_w(ref)

        with checkpoint.session(str(tmp_path / "rec"), every_evals=1,
                                every_s=None, async_writer=False):
            with checkpoint.record_sites() as rec:
                armed = run()
        wf_a, wr_a = _game_w(armed)
        np.testing.assert_array_equal(wf_ref, wf_a)
        np.testing.assert_array_equal(wr_ref, wr_a)
        counts = dict(rec.hits)
        assert counts.get("bucket_retire", 0) >= 4  # 2 blocks x 2 sweeps

        matrix = [("bucket_retire", occ)
                  for occ in range(1, counts["bucket_retire"] + 1)]
        matrix += [("snapshot_write", _occurrences(
            counts["snapshot_write"])[1]),
            ("commit", _occurrences(counts["commit"])[1])]
        for site, occ in matrix:
            ckdir = tmp_path / f"{site}_{occ}"
            try:
                with checkpoint.session(str(ckdir), every_evals=1,
                                        every_s=None, async_writer=False):
                    with checkpoint.fault_plan(
                            checkpoint.FaultPlan.kill_at(site, occ)):
                        run()
                killed = False
            except checkpoint.InjectedFault:
                killed = True
            assert killed, (site, occ)
            with checkpoint.session(str(ckdir), every_evals=1,
                                    every_s=None, async_writer=False):
                out2 = run()
            wf2, wr2 = _game_w(out2)
            np.testing.assert_array_equal(
                wf_ref, wf2, err_msg=f"fixed drift at {site}#{occ}")
            np.testing.assert_array_equal(
                wr_ref, wr2, err_msg=f"re drift at {site}#{occ}")
            assert [float(v) for v in ref.objective_history] == \
                [float(v) for v in out2.objective_history], (site, occ)


# ----------------------------------------------------- store / state layer
class TestStoreAndState:
    def test_newer_schema_rejected_with_clear_error(self, cb, tmp_path):
        ckdir = tmp_path / "newer"
        with checkpoint.session(str(ckdir), every_evals=1, every_s=None,
                                async_writer=False):
            _solve(cb)
        mpath = ckdir / "MANIFEST.json"
        manifest = json.loads(mpath.read_text())
        manifest["schema"] = checkpoint.SCHEMA_VERSION + 1
        mpath.write_text(json.dumps(manifest))
        with pytest.raises(checkpoint.SnapshotSchemaError,
                           match="newer"):
            checkpoint.CheckpointSession(str(ckdir), async_writer=False)

    def test_state_shape_mismatch_rejected(self, cb, tmp_path):
        """A snapshot only fits the program that wrote it: re-chunking
        the dataset must be refused with the mismatch spelled out, not
        resumed into silent drift."""
        ckdir = tmp_path / "mismatch"
        try:
            with checkpoint.session(str(ckdir), every_evals=1,
                                    every_s=None, async_writer=False):
                with checkpoint.fault_plan(
                        checkpoint.FaultPlan.kill_at("evaluation", 5)):
                    _solve(cb)
        except checkpoint.InjectedFault:
            pass
        rechunked = _stream_data(chunk_rows=16)
        with checkpoint.session(str(ckdir), every_evals=1, every_s=None,
                                async_writer=False):
            with pytest.raises(checkpoint.SnapshotStateError,
                               match="chunk"):
                _solve(rechunked)

    def test_retention_keeps_newest(self, cb, tmp_path):
        ckdir = tmp_path / "gc"
        with checkpoint.session(str(ckdir), every_evals=1, every_s=None,
                                async_writer=False, keep=2):
            _solve(cb)
        snaps = sorted(d for d in os.listdir(ckdir)
                       if d.startswith("snap_"))
        assert 1 <= len(snaps) <= 2
        store = checkpoint.SnapshotStore(str(ckdir))
        assert f"snap_{store.latest_seq():08d}" == snaps[-1]

    def test_commit_bytes_kill_leaves_old_content(self, tmp_path):
        path = tmp_path / "blob"
        checkpoint.commit_bytes(str(path), b"generation-1")
        with pytest.raises(checkpoint.InjectedFault):
            with checkpoint.fault_plan(
                    checkpoint.FaultPlan.kill_at("commit", 1)):
                checkpoint.commit_bytes(str(path), b"generation-2")
        assert path.read_bytes() == b"generation-1"
        checkpoint.commit_bytes(str(path), b"generation-2")
        assert path.read_bytes() == b"generation-2"

    def test_retry_io_backoff_and_counters(self):
        from photon_tpu import telemetry

        delays = []
        run = telemetry.start_run("retry_test")
        try:
            with checkpoint.fault_plan(
                    checkpoint.FaultPlan(errors={"s": 3})):
                out = checkpoint.retry_io(lambda: 42, site="s",
                                          base_delay=0.01,
                                          sleep=delays.append)
        finally:
            telemetry.finish_run()
        assert out == 42
        assert delays == [0.01, 0.02, 0.04]  # exponential, deterministic
        assert run.counters["faults.io_retries"] == 3
        assert run.counters["faults.injected_errors"] == 3

    def test_retry_io_exhaustion_reraises(self):
        with checkpoint.fault_plan(
                checkpoint.FaultPlan(errors={"s": 99})):
            with pytest.raises(checkpoint.TransientIOError):
                checkpoint.retry_io(lambda: 42, site="s", retries=2,
                                    base_delay=0.0, sleep=lambda _d: None)

    def test_avro_open_rides_retry(self, tmp_path):
        """The ingest choke point: a transiently-failing container open
        backs off and succeeds (satellite: Avro ingest IO retry)."""
        from photon_tpu.data.avro_io import write_avro
        from photon_tpu.data.streaming import _open_reader

        path = tmp_path / "t.avro"
        write_avro(str(path), [{"x": 1}], json.dumps({
            "type": "record", "name": "R",
            "fields": [{"name": "x", "type": "int"}]}))
        with checkpoint.fault_plan(
                checkpoint.FaultPlan(errors={"avro_open": 2})):
            rd = _open_reader(str(path))
        assert sum(c for c, _ in rd.blocks(skip_payload=True)) == 1

    def test_seeded_fault_plan_is_deterministic(self):
        counts = {"evaluation": 12, "chunk_upload": 30}
        a = checkpoint.FaultPlan.seeded(5, counts)
        b = checkpoint.FaultPlan.seeded(5, counts)
        assert a.kills == b.kills and len(a.kills) == 1


# ------------------------------------------------------------ resident tap
class TestResidentTap:
    def test_tap_captures_last_iterate_and_restores(self, tmp_path):
        rng = np.random.default_rng(1)
        n, d = 48, 5
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        batch = make_batch(X, y)
        cfg = OptimizerConfig(max_iters=4, reg=reg.l2(), reg_weight=0.3,
                              history=3)
        ckdir = tmp_path / "resident"
        with checkpoint.session(str(ckdir), every_evals=None,
                                every_s=None, async_writer=False,
                                resident_tap=True) as sess:
            _, res = train_glm(batch, TASK, cfg)
            np.asarray(res.w)  # force the callback stream
            assert "resident/lbfgs_margin" in sess._state
            sess.snapshot(block=True)
        assert not checkpoint.snapshot_tap_enabled()  # disarmed at close
        with checkpoint.session(str(ckdir), async_writer=False):
            cap = checkpoint.resident_restore("lbfgs_margin")
        assert cap is not None
        assert np.asarray(cap["w"]).shape == (d,)
        assert int(np.asarray(cap["it"])) >= 1

    def test_disarmed_tap_stays_out_of_the_jaxpr(self):
        """Dynamic twin of the checkpoint_off_is_free ContractSpec."""
        import jax

        from photon_tpu.models.training import make_objective
        from photon_tpu.optim.lbfgs import minimize_lbfgs_margin

        cfg = OptimizerConfig(max_iters=3, reg=reg.l2(), reg_weight=0.3,
                              history=3)
        obj = make_objective(TASK, cfg, 4)
        batch = make_batch(np.zeros((8, 4), np.float32),
                           np.zeros(8, np.float32))
        jaxpr = str(jax.make_jaxpr(
            lambda b, w: minimize_lbfgs_margin(obj, b, w, max_iters=3))(
                batch, np.zeros(4, np.float32)))
        assert "callback" not in jaxpr


# ------------------------------------------------------------- session API
class TestSessionScoping:
    def test_scope_paths_and_consumed_once_restore(self, tmp_path):
        s = checkpoint.CheckpointSession(str(tmp_path / "s"),
                                         async_writer=False)
        with s.scope("a"):
            with s.scope("b"):
                s.update("leaf", {"v": 1})
        assert "a/b/leaf" in s._state
        s.snapshot()
        s2 = checkpoint.CheckpointSession(str(tmp_path / "s"),
                                          async_writer=False)
        with s2.scope("a"), s2.scope("b"):
            assert s2.restore("leaf") == {"v": 1}
            assert s2.restore("leaf") is None  # consumed once
        s.close()
        s2.close()

    def test_clear_prefix_drops_subtree(self, tmp_path):
        s = checkpoint.CheckpointSession(str(tmp_path / "s"),
                                         async_writer=False)
        with s.scope("u0"):
            s.update("re", {"v": 1})
            s.update("other", {"v": 2})
        s.update("progress", {"v": 3})
        s.clear("u0", prefix=True)
        assert set(s._state) == {"progress"}
        s.close()
