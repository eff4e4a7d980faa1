"""Pod-scale GAME end-to-end: the composed streamed + mesh regime.

THE acceptance matrix of the round-13 composition: a 2-coordinate GAME
fit (fixed effect + per-entity random effect, 2 sweeps) whose
fixed-effect shard lives as a HOST chunk ladder and solves on the
mesh-streamed backend (mesh 8) — random-effect buckets entity-sharded
over the same mesh, inter-coordinate scores exchanged through host
margin caches — against the resident single-chip fit, across
{L-BFGS, OWL-QN} fixed effects x {dense, blocked-ELL} features, compared
in f64. Chunked f32 accumulation reorders sums (the documented
streamed==resident tolerance of tests/test_streamed.py), so cross-REGIME
parity is pinned at that tolerance; bit-level f64 identity is asserted
where the execution regime is identical — the checkpoint kill/restore
case, whose resumed run must match the uninterrupted one EXACTLY.

Also pinned here: the PR-9 `optim.streamed._backend` mesh + blocked-ELL
rejection is LIFTED for mesh chunk ladders (`chunk_blocked_ell(
n_shards=D)`) and raises precise, actionable errors for every
mismatched layout; the fused-update straggler gate logs + counts; and
the streamed coordinate's scores stay host-resident with the
`game_e2e.*` telemetry spine.
"""
import logging

import numpy as np
import pytest

from photon_tpu import telemetry
from photon_tpu.data.dataset import (chunk_blocked_ell, chunk_matrix,
                                     make_batch)
from photon_tpu.data.matrix import SparseRows
from photon_tpu.game.dataset import GameData
from photon_tpu.game.estimator import (FixedEffectConfig, GameEstimator,
                                       RandomEffectConfig)
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim import regularization as reg
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.parallel.mesh import make_mesh

pytestmark = pytest.mark.release_programs

TASK = TaskType.LOGISTIC_REGRESSION
N, E, D_FIXED, D_RE = 384, 24, 8, 5
D_SPARSE, K, D_DENSE = 40, 4, 16
CHUNK_ROWS = 96  # 4 chunks; 96 % 8 == 0 -> 12 rows per device slot

CFG_RE = OptimizerConfig(max_iters=6, tolerance=1e-6, reg=reg.l2(),
                         reg_weight=1.0, history=4)
CFG_F = {
    "lbfgs": OptimizerConfig(max_iters=8, tolerance=1e-6, reg=reg.l2(),
                             reg_weight=0.5, history=4),
    "owlqn": OptimizerConfig(max_iters=8, tolerance=1e-6, reg=reg.l1(),
                             reg_weight=1e-3, history=4),
}


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(11)
    ent = rng.integers(0, E, size=N)
    Xf = rng.normal(size=(N, D_FIXED)).astype(np.float32)
    Xr = rng.normal(size=(N, D_RE)).astype(np.float32)
    ind = rng.integers(0, D_SPARSE, size=(N, K)).astype(np.int32)
    val = rng.normal(size=(N, K)).astype(np.float32)
    w_true = rng.normal(size=D_FIXED).astype(np.float32) * 0.5
    u_true = rng.normal(size=(E, D_RE)).astype(np.float32)
    margin = Xf @ w_true + np.einsum("nd,nd->n", Xr, u_true[ent])
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return {"y": y, "ent": ent, "dense": Xf, "re": Xr,
            "sparse": SparseRows(ind, val, D_SPARSE)}


def _fixed_shard(problem, layout: str, streamed: bool, n_shards: int = 8):
    if layout == "dense":
        return (chunk_matrix(problem["dense"], CHUNK_ROWS) if streamed
                else problem["dense"])
    sp = problem["sparse"]
    if not streamed:
        return sp
    return chunk_blocked_ell(make_batch(sp, problem["y"]), CHUNK_ROWS,
                             d_dense=D_DENSE, n_shards=n_shards).X


def _fit(problem, shard, opt: str, mesh=None, cfg_re=CFG_RE):
    data = GameData.build(problem["y"], {"fx": shard, "rs": problem["re"]},
                          {"e": problem["ent"]})
    est = GameEstimator(
        task=TASK,
        coordinate_configs={
            "fixed": FixedEffectConfig("fx", CFG_F[opt]),
            "re": RandomEffectConfig("e", "rs", cfg_re)},
        n_sweeps=2, mesh=mesh)
    return est.fit(data)[0]


def _coeffs(result):
    return (np.asarray(result.model.coordinates["fixed"]
                       .model.coefficients.means, np.float64),
            np.asarray(result.model.coordinates["re"].coefficients,
                       np.float64))


# --------------------------------------------------------- parity matrix
class TestStreamedMeshGameParity:
    """streamed(mesh 8) GAME == resident single-chip GAME, f64-compared
    at the streamed==resident tolerance, for every (optimizer, layout)
    face — 2 coordinates, 2 sweeps, warm starts, host score exchange."""

    @pytest.mark.parametrize("opt,layout", [
        ("lbfgs", "dense"), ("lbfgs", "ell"),
        ("owlqn", "dense"), ("owlqn", "ell")])
    def test_streamed_mesh_equals_resident(self, problem, mesh8, opt,
                                           layout):
        r_res = _fit(problem, _fixed_shard(problem, layout, False), opt)
        r_str = _fit(problem, _fixed_shard(problem, layout, True), opt,
                     mesh=mesh8)
        wf_r, wr_r = _coeffs(r_res)
        wf_s, wr_s = _coeffs(r_str)
        np.testing.assert_allclose(wf_s, wf_r, rtol=5e-3, atol=1e-3)
        np.testing.assert_allclose(wr_s, wr_r, rtol=5e-3, atol=1e-3)
        # the objective trajectories track each other update for update
        o_r = r_res.descent.objective_history
        o_s = r_str.descent.objective_history
        assert len(o_r) == len(o_s) == 4  # 2 sweeps x 2 coordinates
        np.testing.assert_allclose(o_s, o_r, rtol=1e-4)

    def test_streamed_scores_stay_host(self, problem, mesh8):
        """The margin exchange is host-resident: the streamed coordinate
        scores into numpy caches, offsets sum on host, and the
        game_e2e.* telemetry spine records the exchange."""
        run = telemetry.start_run("game_e2e_test")
        try:
            r = _fit(problem, _fixed_shard(problem, "dense", True),
                     "lbfgs", mesh=mesh8)
        finally:
            telemetry.finish_run()
        assert r.descent.objective_history
        c = run.counters
        assert c["game_e2e.streamed_fixed_updates"] == 2  # 2 sweeps
        assert c["game_e2e.host_offset_sums"] == 4  # every update
        assert c["game_e2e.score_stream_chunks"] >= 8
        assert c["game_e2e.objective_chunks"] >= 8
        assert c["game_e2e.chunked_fit_points"] == 1

    def test_streamed_fixed_score_is_host_numpy(self, problem, mesh8):
        from photon_tpu.game.dataset import FixedEffectDataset
        from photon_tpu.game.fixed_effect import FixedEffectCoordinate
        from photon_tpu.game.model import FixedEffectModel
        from photon_tpu.models.glm import logistic_regression

        data = GameData.build(problem["y"],
                              {"fx": chunk_matrix(problem["dense"],
                                                  CHUNK_ROWS)},
                              {})
        ds = FixedEffectDataset.build(data, "fx")
        coord = FixedEffectCoordinate(ds, TASK, CFG_F["lbfgs"], mesh=mesh8)
        w = np.linspace(-1, 1, D_FIXED).astype(np.float32)
        score = coord.score(FixedEffectModel(logistic_regression(w), "fx"))
        assert isinstance(score, np.ndarray)
        np.testing.assert_allclose(score, problem["dense"] @ w,
                                   rtol=1e-5, atol=1e-5)


# -------------------------------------------- backend layout pins (PR 9)
class TestBlockedEllMeshBackend:
    """The PR-9 limitation, resolved: mesh + blocked-ELL streams on the
    MESH chunk ladder; every mismatched layout raises an actionable
    error naming the rebuild recipe."""

    def _glm(self, cb, mesh=None):
        from photon_tpu.models.training import train_glm

        cfg = OptimizerConfig(max_iters=6, tolerance=1e-6, reg=reg.l2(),
                              reg_weight=0.3, history=4)
        return train_glm(cb, TASK, cfg, mesh=mesh)

    def test_single_device_ladder_under_mesh_raises_actionable(
            self, problem, mesh8):
        cb = chunk_blocked_ell(make_batch(problem["sparse"],
                                          problem["y"]),
                               CHUNK_ROWS, d_dense=D_DENSE)
        with pytest.raises(ValueError,
                           match=r"n_shards=8.*|chunk_blocked_ell"):
            self._glm(cb, mesh=mesh8)

    def test_mesh_ladder_without_mesh_raises_actionable(self, problem):
        cb = chunk_blocked_ell(make_batch(problem["sparse"],
                                          problem["y"]),
                               CHUNK_ROWS, d_dense=D_DENSE, n_shards=8)
        with pytest.raises(ValueError, match="8-device mesh"):
            self._glm(cb)

    def test_shard_count_mismatch_raises(self, problem, mesh8):
        cb = chunk_blocked_ell(make_batch(problem["sparse"],
                                          problem["y"]),
                               CHUNK_ROWS, d_dense=D_DENSE, n_shards=4)
        with pytest.raises(ValueError, match="4 device shard"):
            self._glm(cb, mesh=mesh8)

    def test_chunk_rows_must_divide_shards(self, problem):
        with pytest.raises(ValueError, match="multiple of"):
            chunk_blocked_ell(make_batch(problem["sparse"], problem["y"]),
                              100, d_dense=D_DENSE, n_shards=8)

    def test_mesh_ladder_glm_parity(self, problem, mesh8):
        """The lifted path at the train_glm level: the mesh chunk ladder
        solves to the resident optimum."""
        m_r, _ = self._glm(make_batch(problem["sparse"], problem["y"]))
        cb = chunk_blocked_ell(make_batch(problem["sparse"],
                                          problem["y"]),
                               CHUNK_ROWS, d_dense=D_DENSE, n_shards=8)
        m_m, _ = self._glm(cb, mesh=mesh8)
        np.testing.assert_allclose(np.asarray(m_m.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   rtol=5e-3, atol=5e-4)

    def test_sharded_ladder_matvec_parity(self, problem, mesh8):
        """Layout-level correctness of the mesh ladder: every chunk's
        sharded matvec reproduces the flat SparseRows margins."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from photon_tpu.data.dataset import mesh_chunk_matrix
        from photon_tpu.data.matrix import matvec
        from photon_tpu.models.training import _hybrid_specs
        from photon_tpu.parallel.mesh import shard_map

        sp = problem["sparse"]
        cb = chunk_blocked_ell(make_batch(sp, problem["y"]), CHUNK_ROWS,
                               d_dense=D_DENSE, n_shards=8)
        rng = np.random.default_rng(0)
        w = rng.normal(size=D_SPARSE).astype(np.float32)
        wp = w[np.asarray(cb.X.perm_cols)]
        ref = np.einsum("nk,nk->n", np.asarray(sp.values),
                        w[np.asarray(sp.indices)])
        axes = tuple(mesh8.axis_names)
        cache: dict = {}
        outs = []
        for i in range(cb.n_chunks):
            Xs = mesh_chunk_matrix(cb.X.chunks[i], mesh8, cache)
            fn = shard_map(lambda Xl, wv: matvec(Xl.local(), wv),
                           mesh=mesh8,
                           in_specs=(_hybrid_specs(Xs, axes).X, P()),
                           out_specs=P(axes))
            outs.append(np.asarray(jax.jit(fn)(Xs, jnp.asarray(wp))))
        np.testing.assert_allclose(np.concatenate(outs)[:N], ref,
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------------- fused gate (satellite)
class TestFusedGateTelemetry:
    def test_straggler_gate_logs_once_and_counts(self, problem, caplog):
        """straggler_budget disabling the fused one-dispatch update is no
        longer a silent call-site comment: INFO log once per coordinate,
        game_re.fused_gate_offs counted per gated call."""
        from photon_tpu.game.dataset import RandomEffectDataset
        from photon_tpu.game.random_effect import RandomEffectCoordinate

        data = GameData.build(problem["y"], {"rs": problem["re"]},
                              {"e": problem["ent"]})
        ds = RandomEffectDataset.build(data, "e", "rs")
        coord = RandomEffectCoordinate(ds, TASK, CFG_RE,
                                       straggler_budget=2)
        run = telemetry.start_run("fused_gate")
        try:
            with caplog.at_level(logging.INFO, logger="photon_tpu.game"):
                assert coord.fused_update_program() is None
                assert coord.fused_update_program() is None
        finally:
            telemetry.finish_run()
        assert run.counters["game_re.fused_gate_offs"] == 2
        gate_lines = [r for r in caplog.records
                      if "straggler_budget" in r.getMessage()]
        assert len(gate_lines) == 1  # once per coordinate, not per call
        assert "pipelined block loop" in gate_lines[0].getMessage()

    def test_fused_fit_counts_its_blocks(self, problem):
        """A resident fit takes the fused one-dispatch update, which solves
        every block of the coordinate: `game_re.blocks` counts them there
        too (the benchmark's `fit_dispatches` reads it)."""
        with telemetry.run("fused_blocks") as run:
            _fit(problem, problem["dense"], "lbfgs")
            counters = run.report_compact()["counters"]
        n_re, n_sweeps = 1, 2
        assert "game_re.fused_gate_offs" not in counters  # the fused path
        assert counters["game_re.blocks"] >= n_re * n_sweeps
        assert counters["game.coordinate_updates"] == 2 * n_sweeps

    def test_unbudgeted_coordinate_still_fuses(self, problem):
        from photon_tpu.game.dataset import RandomEffectDataset
        from photon_tpu.game.random_effect import RandomEffectCoordinate

        data = GameData.build(problem["y"], {"rs": problem["re"]},
                              {"e": problem["ent"]})
        ds = RandomEffectDataset.build(data, "e", "rs")
        coord = RandomEffectCoordinate(ds, TASK, CFG_RE)
        assert coord.fused_update_program() is not None


# --------------------------------------------- checkpoint (coordinate cut)
class TestStreamedGameCheckpoint:
    def test_kill_restore_at_coordinate_boundary_bit_identical(
            self, problem, tmp_path):
        """Kill the streamed GAME descent mid-sweep-2 (inside the SECOND
        coordinate pass — past a coordinate-boundary progress cut of the
        new streamed path), restore, and finish with coefficients AND
        objective history EXACTLY equal (f64) to the uninterrupted
        run's: the host-score progress payload round-trips bit-clean."""
        from photon_tpu import checkpoint

        cfg_re = OptimizerConfig(max_iters=5, tolerance=1e-6,
                                 reg=reg.l2(), reg_weight=1.0, history=4)

        def run():
            return _fit(problem, _fixed_shard(problem, "dense", True),
                        "lbfgs", cfg_re=cfg_re)

        ref = run()
        wf_ref, wr_ref = _coeffs(ref)

        with checkpoint.session(str(tmp_path / "rec"), every_evals=1,
                                every_s=None, async_writer=False):
            with checkpoint.record_sites() as rec:
                armed = run()
        wf_a, wr_a = _coeffs(armed)
        np.testing.assert_array_equal(wf_ref, wf_a)  # observe, not perturb
        np.testing.assert_array_equal(wr_ref, wr_a)
        n_evals = dict(rec.hits)["evaluation"]
        assert n_evals >= 8

        # kill inside the LAST fixed-effect solve: updates 0..2 restore
        # from the descent progress payload (host scores included), the
        # in-flight streamed solve resumes from its own iteration cut
        killed = False
        ckdir = tmp_path / "kill"
        try:
            with checkpoint.session(str(ckdir), every_evals=1,
                                    every_s=None, async_writer=False):
                with checkpoint.fault_plan(
                        checkpoint.FaultPlan.kill_at("evaluation",
                                                     n_evals - 2)):
                    run()
        except checkpoint.InjectedFault:
            killed = True
        assert killed
        with checkpoint.session(str(ckdir), every_evals=1, every_s=None,
                                async_writer=False):
            out2 = run()
        wf2, wr2 = _coeffs(out2)
        np.testing.assert_array_equal(wf_ref, wf2)
        np.testing.assert_array_equal(wr_ref, wr2)
        assert [float(v) for v in ref.descent.objective_history] == \
            [float(v) for v in out2.descent.objective_history]


# -------------------------------------------------------------- contracts
def test_game_e2e_contract_specs_registered():
    """The pod-scale GAME collective budget as registered law: ONE psum
    per streamed fixed-effect evaluation, collective-free RE bucket
    solves on the mesh, scatter-free f32-accumulating streamed chunk and
    score programs."""
    from photon_tpu.analysis.registry import load_registry
    from photon_tpu.analysis.walker import SCATTER_PRIMITIVES

    registry = load_registry()
    assert dict(registry["game_streamed_fixed_evaluation"].collectives) \
        == {"psum": 1}
    assert dict(registry["game_re_mesh_bucket_solve"].collectives or {}) \
        == {}
    for name in ("streamed_mesh_blocked_ell_chunk_partials",
                 "game_score_stream_chunk"):
        spec = registry[name]
        assert dict(spec.collectives or {}) == {}
        assert SCATTER_PRIMITIVES <= spec.forbid, name
        assert spec.require_f32_accum, name
