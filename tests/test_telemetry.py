"""Telemetry spine tests: span nesting/exception safety, cross-thread
counter aggregation, the JSONL sink round-trip, the live streamed-solver
iteration stream (events == OptResult.loss_history, single-chip and
mesh), the resident debug-callback tap's on/off result parity, the GAME
descent event stream, photon_logger level semantics, and the
telemetry-off-is-free contract.

Marked `release_programs`: the tap tests arm/disarm `resident_tap`
(which clears jit caches by design) and the mesh test compiles 8-device
shard_map programs — both put this module in the executable-accumulation
regime tests/conftest.py's marker exists for.
"""
import json
import logging
import os
import threading
import time

import numpy as np
import pytest

import jax

from photon_tpu import telemetry
from photon_tpu.telemetry import trace
from photon_tpu.telemetry.aggregate import aggregate_cluster, rank_files
from photon_tpu.telemetry.health import (CRITICAL, DEGRADED, OK,
                                         HealthMonitor, QuantileDigest,
                                         WatchRule, report_from_jsonl,
                                         snapshot)
from photon_tpu.data.dataset import chunk_batch, make_batch
from photon_tpu.models.training import train_glm
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim import regularization as reg

pytestmark = pytest.mark.release_programs


@pytest.fixture(autouse=True)
def _detached():
    """No test may leak an attached run (or an armed tap) into the rest
    of the suite."""
    yield
    telemetry.finish_run()


def _problem(rng, n=240, d=6):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    return X, y


_CFG = OptimizerConfig(max_iters=8, tolerance=1e-7, reg=reg.l2(),
                       reg_weight=0.1, history=4)


# ------------------------------------------------------------------- spans
class TestSpans:
    def test_nesting_paths_and_depths(self):
        r = telemetry.start_run("t")
        with telemetry.span("outer", phase="x"):
            with telemetry.span("inner"):
                pass
        with telemetry.span("sibling"):
            pass
        by_path = {s.path: s for s in r.spans}
        assert set(by_path) == {"outer/inner", "outer", "sibling"}
        assert by_path["outer/inner"].depth == 1
        assert by_path["sibling"].depth == 0
        assert by_path["outer"].attrs == {"phase": "x"}
        # children complete (and record) before their parents
        assert r.spans[0].name == "inner"
        assert all(s.seconds >= 0.0 for s in r.spans)

    def test_exception_safety(self):
        r = telemetry.start_run("t")
        with pytest.raises(ValueError):
            with telemetry.span("outer"):
                with telemetry.span("boom"):
                    raise ValueError("x")
        by_path = {s.path: s for s in r.spans}
        assert by_path["outer/boom"].error == "ValueError"
        assert by_path["outer"].error == "ValueError"
        # the stack unwound: a new span is top-level again
        with telemetry.span("after"):
            pass
        assert {s.path for s in r.spans} >= {"after"}
        assert [s for s in r.spans if s.path == "after"][0].depth == 0

    def test_noop_without_run(self):
        assert telemetry.current_run() is None
        with telemetry.span("ignored") as rec:
            assert rec is None
        telemetry.count("ignored")
        telemetry.iteration("ignored", 0, 1.0)  # must not raise

    def test_phase_timers_feed_spans(self):
        from photon_tpu.utils.timing import PhaseTimers

        r = telemetry.start_run("t")
        timers = PhaseTimers(span_prefix="train.")
        with timers("read"):
            pass
        with timers("read"):
            pass
        assert sum(1 for s in r.spans if s.path == "train.read") == 2
        assert timers.summary()["read"] >= 0.0
        telemetry.finish_run()
        with timers("read"):  # detached: pure stopwatch, no crash
            pass


# ---------------------------------------------------------------- counters
class TestCounters:
    def test_thread_aggregation(self):
        r = telemetry.start_run("t")

        def bump():
            for _ in range(2000):
                telemetry.count("bumps")
                telemetry.count("weighted", 0.5)

        threads = [threading.Thread(target=bump) for _ in range(8)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert r.counters["bumps"] == 16000.0
        assert r.counters["weighted"] == pytest.approx(8000.0)

    def test_gauges_keep_last(self):
        r = telemetry.start_run("t")
        telemetry.gauge("depth", 2)
        telemetry.gauge("depth", 4)
        assert r.gauges["depth"] == 4

    def test_record_signature_counts_new_traces(self):
        import jax.numpy as jnp

        r = telemetry.start_run("t")
        telemetry.record_signature("prog", (jnp.ones(3),))
        telemetry.record_signature("prog", (jnp.ones(3),))  # same sig
        telemetry.record_signature("prog", (jnp.ones(4),))  # new shape
        assert r.counters["retrace.new_signatures"] == 2.0
        # weak-type drift surfaces in the report
        telemetry.record_signature("drift", (jnp.float32(1.0),))
        telemetry.record_signature("drift", (1.0,))
        assert "drift" in r.report()["retrace"]["weak_type_hazards"]


# ------------------------------------------------------------------- JSONL
class TestJsonlSink:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        r = telemetry.start_run("rt", jsonl_path=path)
        with telemetry.span("a"):
            with telemetry.span("b"):
                pass
        telemetry.count("c1", 3)
        telemetry.iteration("solver", 0, 1.5, grad_norm=0.1, trials=2)
        telemetry.event("custom_event", detail="x")
        report = telemetry.finish_run()

        disk = telemetry.load_report(path)
        assert disk["complete"]
        assert disk["name"] == "rt"
        assert disk["counters"] == report["counters"]
        assert {s["path"] for s in disk["spans"]} == {"a", "a/b"}
        assert disk["iterations"] == report["iterations"]
        assert disk["iterations"][0]["loss"] == 1.5
        assert [e["type"] for e in disk["events"]] == ["custom_event"]
        assert disk["duration_s"] == pytest.approx(report["duration_s"])

    def test_truncated_tail_tolerated(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        telemetry.start_run("rt", jsonl_path=path)
        telemetry.iteration("s", 0, 1.0)
        telemetry.finish_run()
        with open(path, "a") as fh:
            fh.write('{"type": "iteration", "solver": "s", "it')  # cut off
        disk = telemetry.load_report(path)
        assert len(disk["iterations"]) == 1  # prefix still served

    def test_reopen_after_kill_appends_past_torn_tail(self, tmp_path):
        """Elastic-runs satellite: a run killed mid-write leaves a torn
        FINAL record; a resumed run reopening the SAME file with
        append=True must first truncate that tail (otherwise its first
        record fuses onto the torn line and every later event vanishes
        from read_jsonl), then append — all complete records from both
        generations are served."""
        path = str(tmp_path / "run.jsonl")
        telemetry.start_run("gen1", jsonl_path=path)
        telemetry.iteration("s", 0, 1.0)
        telemetry.iteration("s", 1, 0.5)
        telemetry.finish_run()
        with open(path, "a") as fh:  # the kill: a torn final record
            fh.write('{"type": "iteration", "solver": "s", "it')

        telemetry.start_run("gen2", jsonl_path=path, append=True)
        telemetry.iteration("s", 2, 0.25)
        telemetry.finish_run()

        events = list(telemetry.read_jsonl(path))
        assert [e["name"] for e in events
                if e["type"] == "run_start"] == ["gen1", "gen2"]
        iters = [e for e in events if e["type"] == "iteration"]
        assert [e["it"] for e in iters] == [0, 1, 2]  # torn tail dropped
        assert sum(1 for e in events if e["type"] == "run_end") == 2

    def test_repair_tail_noop_on_clean_file(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        telemetry.start_run("rt", jsonl_path=path)
        telemetry.iteration("s", 0, 1.0)
        telemetry.finish_run()
        size = os.path.getsize(path)
        assert telemetry.repair_jsonl_tail(path) == 0
        assert os.path.getsize(path) == size

    def test_every_line_is_json_with_type(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        telemetry.start_run("rt", jsonl_path=path)
        with telemetry.span("a"):
            pass
        telemetry.finish_run()
        with open(path) as fh:
            kinds = [json.loads(line)["type"] for line in fh]
        assert kinds[0] == "run_start" and kinds[-1] == "run_end"
        assert "span" in kinds


# ------------------------------------------- streamed iteration stream
class TestStreamedIterationStream:
    def _events(self, r, solver):
        evs = sorted((e for e in r.iterations if e["solver"] == solver),
                     key=lambda e: e["it"])
        assert [e["it"] for e in evs] == list(range(len(evs)))
        return evs

    def test_lbfgs_events_match_loss_history(self, rng, tmp_path):
        X, y = _problem(rng)
        cb = chunk_batch(make_batch(X, y), 64)
        path = str(tmp_path / "run.jsonl")
        r = telemetry.start_run("t", jsonl_path=path)
        _, res = train_glm(cb, TaskType.LOGISTIC_REGRESSION, _CFG)
        telemetry.finish_run()
        evs = self._events(r, "lbfgs_streamed")
        hist = res.history()
        assert len(evs) == hist.shape[0] == int(res.iterations) + 1
        np.testing.assert_allclose([e["loss"] for e in evs], hist,
                                   rtol=1e-6)
        ghist = res.grad_history()
        np.testing.assert_allclose([e["grad_norm"] for e in evs], ghist,
                                   rtol=1e-5)
        # per-iteration events carry the accepted step + trial count
        assert all("step" in e and e["trials"] >= 1 for e in evs[1:])
        # the same stream round-trips through the JSONL sink
        disk = [e for e in telemetry.read_jsonl(path, kind="iteration")
                if e["solver"] == "lbfgs_streamed"]
        assert [e["loss"] for e in disk] == [e["loss"] for e in evs]

    def test_owlqn_events_match_loss_history(self, rng):
        X, y = _problem(rng)
        cb = chunk_batch(make_batch(X, y), 64)
        cfg = OptimizerConfig(max_iters=8, tolerance=1e-7, reg=reg.l1(),
                              reg_weight=0.05, history=4)
        r = telemetry.start_run("t")
        _, res = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
        telemetry.finish_run()
        evs = self._events(r, "owlqn_streamed")
        hist = res.history()
        assert len(evs) == hist.shape[0]
        np.testing.assert_allclose([e["loss"] for e in evs], hist,
                                   rtol=1e-6)

    def test_streamed_mesh_full_report(self, rng, mesh8, tmp_path):
        """The acceptance shape: a streamed-MESH solve with telemetry on
        produces a JSONL report with spans, >=5 distinct counters, and one
        iteration event per solver iteration whose losses equal
        OptResult.loss_history."""
        X, y = _problem(rng, n=400)
        cb = chunk_batch(make_batch(X, y), 100)
        path = str(tmp_path / "mesh_run.jsonl")
        r = telemetry.start_run("mesh", jsonl_path=path)
        _, res = train_glm(cb, TaskType.LOGISTIC_REGRESSION, _CFG,
                           mesh=mesh8)
        telemetry.finish_run()

        evs = self._events(r, "lbfgs_streamed")
        hist = res.history()
        assert len(evs) == hist.shape[0]
        np.testing.assert_allclose([e["loss"] for e in evs], hist,
                                   rtol=1e-6)

        disk = telemetry.load_report(path)
        assert disk["complete"]
        assert len(disk["spans"]) >= 1
        assert any(s["path"].startswith("solve.lbfgs_streamed")
                   for s in disk["spans"])
        assert len(disk["counters"]) >= 5
        for key in ("stream.chunk_uploads", "stream.stall_seconds",
                    "solver.evaluations", "solver.linesearch_trials",
                    "solver.iterations", "solver.feature_streams"):
            assert key in disk["counters"], key
        # per-pass upload accounting: every feature stream re-uploads all
        # chunks (plus margin-only trial streams never touch features)
        assert disk["counters"]["stream.chunk_uploads"] >= \
            disk["counters"]["solver.feature_streams"] * cb.n_chunks

    def test_counters_off_by_default(self, rng):
        X, y = _problem(rng)
        cb = chunk_batch(make_batch(X, y), 64)
        assert telemetry.current_run() is None
        _, res = train_glm(cb, TaskType.LOGISTIC_REGRESSION, _CFG)
        assert int(res.iterations) > 0  # solve unaffected, nothing raised

    def test_chunk_timeline_sites_get_the_shared_null_span(self, rng,
                                                           monkeypatch):
        """With no run attached every span site of the streamed solve —
        the ring's upload, hand-out and release, the backend's dispatch
        and readback, the host loop's steps and passes — is handed the ONE
        shared no-op: no record, no clock reading, no annotation."""
        X, y = _problem(rng)
        cb = chunk_batch(make_batch(X, y), 64)
        real, handed = telemetry.span, []

        def spy(name, **attrs):
            cm = real(name, **attrs)
            handed.append((name, cm))
            return cm

        monkeypatch.setattr(telemetry, "span", spy)
        assert telemetry.current_run() is None
        train_glm(cb, TaskType.LOGISTIC_REGRESSION, _CFG)
        assert {name for name, _ in handed} >= {
            "solve.lbfgs_streamed", "stream.pass", "stream.upload",
            "stream.handout", "stream.release", "stream.dispatch",
            "stream.readback", "solve.host_step"}
        assert all(cm is telemetry._NULL_SPAN for _, cm in handed)
        assert telemetry._NULL_SPAN.__enter__() is None


# --------------------------------------------------- resident solver tap
class TestResidentTap:
    def test_tap_off_then_on_parity_and_events(self, rng):
        X, y = _problem(rng)
        batch = make_batch(X, y)
        # OFF (default): no run, no events — and the solve works
        res_off = train_glm(batch, TaskType.LOGISTIC_REGRESSION, _CFG)[1]

        r = telemetry.start_run("tap", resident_tap=True)
        res_on = train_glm(batch, TaskType.LOGISTIC_REGRESSION, _CFG)[1]
        jax.effects_barrier()  # debug callbacks drain before asserting
        telemetry.finish_run()

        # parity: the tap must not change results
        np.testing.assert_allclose(np.asarray(res_on.w),
                                   np.asarray(res_off.w), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(res_on.loss_history),
                                   np.asarray(res_off.loss_history),
                                   rtol=1e-6)
        assert int(res_on.iterations) == int(res_off.iterations)

        evs = sorted((e for e in r.iterations
                      if e["solver"] == "lbfgs_margin"),
                     key=lambda e: e["it"])
        hist = res_on.history()
        assert len(evs) == hist.shape[0]
        np.testing.assert_allclose([e["loss"] for e in evs], hist,
                                   rtol=1e-6)
        assert all(e.get("tapped") for e in evs)

        # OFF again: a fresh run without the tap sees no resident events
        r2 = telemetry.start_run("tap-off")
        res_off2 = train_glm(batch, TaskType.LOGISTIC_REGRESSION, _CFG)[1]
        jax.effects_barrier()
        telemetry.finish_run()
        assert not [e for e in r2.iterations
                    if e["solver"] == "lbfgs_margin"]
        np.testing.assert_allclose(np.asarray(res_off2.w),
                                   np.asarray(res_off.w), rtol=1e-6)

    def test_tap_events_owlqn(self, rng):
        X, y = _problem(rng)
        batch = make_batch(X, y)
        cfg = OptimizerConfig(max_iters=6, tolerance=1e-7, reg=reg.l1(),
                              reg_weight=0.05, history=4)
        r = telemetry.start_run("tap", resident_tap=True)
        res = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)[1]
        jax.effects_barrier()
        telemetry.finish_run()
        evs = sorted((e for e in r.iterations if e["solver"] == "owlqn"),
                     key=lambda e: e["it"])
        hist = res.history()
        assert len(evs) == hist.shape[0]
        np.testing.assert_allclose([e["loss"] for e in evs], hist,
                                   rtol=1e-6)


# ----------------------------------------------------------- GAME events
class TestGameStream:
    def test_descent_emits_one_event_per_update(self, rng):
        from photon_tpu.game import (FixedEffectConfig, GameData,
                                     GameEstimator, RandomEffectConfig)

        n, d = 400, 4
        ent = rng.integers(0, 12, size=n)
        Xf = rng.normal(size=(n, d)).astype(np.float32)
        Xr = np.ones((n, 1), np.float32)
        yv = (rng.uniform(size=n) < 0.5).astype(np.float32)
        data = GameData.build(yv, shards={"fixed": Xf, "bias": Xr},
                              entity_ids={"e": ent})
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={
                "fixed": FixedEffectConfig("fixed", _CFG),
                "per_e": RandomEffectConfig("e", "bias", _CFG),
            },
            n_sweeps=2)
        r = telemetry.start_run("game")
        results = est.fit(data)
        telemetry.finish_run()
        descent = results[0].descent
        evs = [e for e in r.iterations if e["solver"] == "game_descent"]
        assert len(evs) == len(descent.objective_history) == 4
        np.testing.assert_allclose([e["loss"] for e in evs],
                                   descent.objective_history, rtol=1e-6)
        assert [(e["sweep"], e["coordinate"]) for e in evs] == \
            [(0, "fixed"), (0, "per_e"), (1, "fixed"), (1, "per_e")]
        assert r.counters["game.coordinate_updates"] == 4.0
        assert r.counters["game.sweeps"] == 2.0
        assert r.counters["game.grid_points"] == 1.0

    def test_re_pipeline_counters_and_spans(self, rng):
        """The round-8 game_re.* spine: per-block upload/solve/readback
        spans + the pipeline/straggler counters, surfaced by
        report_compact() (the piece BENCH_*.json embeds)."""
        from photon_tpu.game import GameData, RandomEffectCoordinate, \
            RandomEffectDataset

        n_entities, d = 10, 3
        ent = np.repeat(np.arange(n_entities), 20)
        n = ent.shape[0]
        X = rng.normal(size=(n, d)).astype(np.float32)
        yv = (rng.uniform(size=n) < 0.5).astype(np.float32)
        data = GameData.build(yv, {"s": X}, {"e": ent})
        ds = RandomEffectDataset.build(data, "e", "s")
        cfg = OptimizerConfig(max_iters=30, tolerance=1e-7, reg=reg.l2(),
                              reg_weight=1e-2, history=4)
        coord = RandomEffectCoordinate(
            ds, TaskType.LOGISTIC_REGRESSION, cfg,
            pipeline_depth=1, straggler_budget=1)
        r = telemetry.start_run("game_re")
        coord.train(np.zeros(n, np.float32))
        telemetry.finish_run()
        assert r.counters["game_re.blocks"] >= 1.0
        assert "game_re.readback_wait_ns" in r.counters
        assert r.gauges["game_re.blocks_in_flight"] >= 1
        # budget=1 guarantees a straggler tail on this problem
        assert r.counters["game_re.straggler_entities"] >= 1.0
        assert r.counters["game_re.tail_resolves"] >= 1.0
        assert "game_re.iters_saved" in r.counters
        totals = r.span_totals()
        for name in ("game_re.upload", "game_re.solve",
                     "game_re.readback", "game_re.tail_solve"):
            assert name in totals, name
        compact = r.report_compact()
        assert "game_re.blocks" in compact["counters"]
        assert "game_re.readback_wait_ns" in compact["counters"]


# ------------------------------------------------------- photon_logger fix
class TestPhotonLoggerLevels:
    def test_explicit_level_survives_reconfiguration(self):
        from photon_tpu.utils.logging import photon_logger

        log = photon_logger("t_lvl_a", level=logging.DEBUG)
        assert log.level == logging.DEBUG
        # a later default-level call (e.g. a second driver phase adding a
        # file handler) must NOT silently reset the effective level
        log = photon_logger("t_lvl_a")
        assert log.level == logging.DEBUG
        # an explicit new level still wins
        log = photon_logger("t_lvl_a", level=logging.WARNING)
        assert log.level == logging.WARNING

    def test_first_call_defaults_to_info(self):
        from photon_tpu.utils.logging import photon_logger

        assert photon_logger("t_lvl_b").level == logging.INFO

    def test_env_override_wins(self, monkeypatch):
        from photon_tpu.utils.logging import photon_logger

        monkeypatch.setenv("PHOTON_TPU_LOG_LEVEL", "warning")
        assert photon_logger("t_lvl_c",
                             level=logging.DEBUG).level == logging.WARNING
        monkeypatch.setenv("PHOTON_TPU_LOG_LEVEL", "15")
        assert photon_logger("t_lvl_d").level == 15
        monkeypatch.setenv("PHOTON_TPU_LOG_LEVEL", "not-a-level")
        assert photon_logger("t_lvl_e").level == logging.INFO

    def test_handlers_stay_notset(self, tmp_path):
        from photon_tpu.utils.logging import photon_logger

        log = photon_logger("t_lvl_f", output_dir=str(tmp_path),
                            level=logging.DEBUG)
        assert log.handlers and all(h.level == logging.NOTSET
                                    for h in log.handlers)

    def test_stall_log_still_fires_with_stable_text(self, caplog):
        from photon_tpu.data.dataset import _log_stream_stall

        r = telemetry.start_run("t")
        with caplog.at_level(logging.INFO, logger="photon_tpu.streamed"):
            _log_stream_stall(stall=1.0, compute=0.2, n_chunks=4,
                              prefetch=2)
        telemetry.finish_run()
        assert any("deeper prefetch or bigger chunks" in rec.message
                   for rec in caplog.records)
        assert r.counters["stream.stalled_passes"] == 1.0


# ----------------------------------------------------- off-is-free contract
class TestOffIsFreeContract:
    def test_registered_and_clean(self):
        from photon_tpu.analysis.contracts import check_contract
        from photon_tpu.analysis.registry import load_registry

        specs = load_registry()
        assert "telemetry_off_is_free" in specs
        spec = specs["telemetry_off_is_free"]
        assert "telemetry" in spec.tags
        violations = check_contract(spec)
        assert violations == [], "\n".join(str(v) for v in violations)

    def test_tap_on_trace_contains_callback_off_does_not(self, rng):
        """The mechanism itself: armed -> debug_callback in the jaxpr;
        disarmed -> absent (what the contract pins at registry level)."""
        import jax.numpy as jnp

        from photon_tpu.analysis import count_primitives
        from photon_tpu.optim.lbfgs import minimize_lbfgs_margin
        from photon_tpu.models.training import make_objective

        X, y = _problem(rng, n=64, d=5)
        batch = make_batch(X, y)
        obj = make_objective(TaskType.LOGISTIC_REGRESSION, _CFG, 5)
        w0 = jnp.zeros((5,), jnp.float32)

        def fn(b, w):
            return minimize_lbfgs_margin(obj, b, w, max_iters=3)

        closed_off = jax.make_jaxpr(fn)(batch, w0)
        assert count_primitives(closed_off,
                                {"debug_callback"}) == {}
        telemetry.set_resident_tap(True)
        try:
            closed_on = jax.make_jaxpr(fn)(batch, w0)
            n_cb = count_primitives(closed_on, {"debug_callback"})
            assert n_cb.get("debug_callback", 0) >= 2  # init + loop body
        finally:
            telemetry.set_resident_tap(False)


# ------------------------------------------------------- serving stream
class TestServingStream:
    def test_dispatcher_emits_serving_events_and_counters(self, rng,
                                                          tmp_path):
        """The round-9 serving.* spine: per-flush spans, request/batch/
        cold-miss counters, the serving_batch JSONL event stream, and
        the close-time latency gauges."""
        from photon_tpu import serving
        from photon_tpu.serving.__main__ import build_demo_model

        model, _ = build_demo_model(seed=3)
        store = serving.CoefficientStore.from_game_model(model)
        ladder = serving.ProgramLadder(store, ladder=(4,),
                                       sparse_k={"member": 3})
        d_f = int(model["fixed"].model.coefficients.dim)
        jsonl = str(tmp_path / "serve.jsonl")
        r = telemetry.start_run("serve", jsonl_path=jsonl)
        disp = serving.MicroBatchDispatcher(ladder, max_batch=4,
                                            max_delay_us=1000)
        try:
            futs = [disp.submit(serving.ScoreRequest(
                features={"global": rng.normal(size=d_f).astype(np.float32),
                          "member": (np.asarray([0, 1], np.int32),
                                     np.asarray([1.0, -1.0], np.float32))},
                entities={"memberId": "e000" if i % 2 else "cold"}))
                for i in range(6)]
            [f.result(timeout=30) for f in futs]
        finally:
            disp.close()
            telemetry.finish_run()
        assert r.counters["serving.requests"] == 6.0
        assert r.counters["serving.batches"] >= 2.0
        assert r.counters["serving.cold_misses"] == 3.0
        assert "serving.batch_fill" in r.gauges
        assert r.gauges["serving.latency_p50_ms"] <= \
            r.gauges["serving.latency_p99_ms"]
        assert any(s.name == "serving.flush" for s in r.spans)
        batches = list(telemetry.read_jsonl(jsonl, kind="serving_batch"))
        assert sum(e["rows"] for e in batches) == 6
        assert all(e["bucket"] == 4 for e in batches)

    def test_docstring_is_single_source_of_truth_for_names(self, rng):
        """Every serving.* counter/gauge a live dispatcher emits must be
        listed in photon_tpu/telemetry/__init__'s docstring — the
        documented registry of counter names."""
        import photon_tpu.telemetry as t
        from photon_tpu import serving
        from photon_tpu.serving.__main__ import build_demo_model

        model, _ = build_demo_model(seed=4)
        store = serving.CoefficientStore.from_game_model(model)
        ladder = serving.ProgramLadder(store, ladder=(4,),
                                       sparse_k={"member": 3})
        d_f = int(model["fixed"].model.coefficients.dim)
        r = telemetry.start_run("doc")
        disp = serving.MicroBatchDispatcher(ladder, max_batch=4,
                                            max_delay_us=500)
        try:
            disp.score(serving.ScoreRequest(
                features={"global": rng.normal(size=d_f).astype(np.float32),
                          "member": (np.asarray([0], np.int32),
                                     np.asarray([1.0], np.float32))},
                entities={"memberId": "nope"}), timeout=30)
        finally:
            disp.close()
            telemetry.finish_run()
        doc = t.__doc__
        emitted = [k for k in list(r.counters) + list(r.gauges)
                   if k.startswith("serving.")]
        assert emitted, "dispatcher emitted no serving.* telemetry"
        for name in emitted:
            short = name.split(".", 1)[1]
            assert short in doc, (
                f"{name} is not listed in telemetry/__init__'s docstring "
                "— the single source of truth for counter names")


# --------------------------------------- round 19: request tracing
class TestRequestTracing:
    def test_disarmed_is_free(self):
        """The off state: begin returns None, every other entry point is
        None-safe, no reservoir exists."""
        assert not trace.armed()
        assert trace.begin("queue_wait") is None
        trace.hop(None, "device_flush")
        trace.finish(None)
        with trace.attach(None):
            assert trace.current() is None
        assert trace.reservoir() is None

    def test_slow_hop_is_named_and_breakdown_sums(self):
        """The acceptance pin's trace-level half: a deterministically
        slow hop must be NAMED by the slowest exemplar, and the hop
        breakdown must sum to the trace total (switch closes the previous
        hop — no gap, no double count)."""
        with trace.tracing(k=4) as res:
            tc = trace.begin("queue_wait")
            trace.hop(tc, "device_flush")
            time.sleep(0.03)  # the injected slow hop
            trace.hop(tc, "retire_wait")
            trace.finish(tc)
        ex = res.slowest()
        assert ex["slowest_hop"] == "device_flush"
        assert [h["name"] for h in ex["hops"]] == \
            ["queue_wait", "device_flush", "retire_wait"]
        assert sum(ex["breakdown_ms"].values()) == \
            pytest.approx(ex["total_ms"], abs=5.0)
        assert ex["breakdown_ms"]["device_flush"] >= 25.0

    def test_reservoir_keeps_k_slowest(self):
        res = trace.ExemplarReservoir(k=3)
        for i in range(10):
            tc = trace.TraceContext()
            tc.switch("h")
            tc.finish()
            tc.start_ns = 0  # pin a deterministic total
            tc.end_ns = (i + 1) * 1_000_000
            res.offer(tc)
        assert res.n_offered == 10
        assert [e["total_ms"] for e in res.snapshot()] == [10.0, 9.0, 8.0]

    def test_finish_is_one_shot(self):
        """A timed-out failover attempt's late retire must not deposit a
        second exemplar or reopen the hop list."""
        with trace.tracing(k=8) as res:
            tc = trace.begin("queue_wait")
            trace.finish(tc)
            trace.finish(tc)  # the straggler thread's late finish
            n_hops = len(tc.hops)
            tc.switch("late_hop")  # mutation after finish: no-op
            assert len(tc.hops) == n_hops
        assert res.n_offered == 1

    def test_contextvar_propagation(self):
        """attach() binds the fleet's trace as the thread's current one;
        begin() inside the block CONTINUES it (how one trace crosses
        fleet → dispatcher.submit), and a fresh one starts outside."""
        with trace.tracing(k=2):
            tc = trace.begin("fleet_route")
            with trace.attach(tc):
                assert trace.current() is tc
                assert trace.begin("queue_wait") is tc
            assert trace.current() is None
            assert trace.begin("queue_wait") is not tc

    def test_tracing_restores_surrounding_state(self):
        outer = trace.arm_tracing()
        try:
            with trace.tracing(k=2) as inner:
                assert trace.reservoir() is inner
            assert trace.reservoir() is outer and trace.armed()
        finally:
            trace.disarm_tracing()

    def test_trace_disabled_scopes_an_armed_session(self):
        with trace.tracing(k=2):
            with trace.trace_disabled():
                assert trace.begin("queue_wait") is None
            assert trace.begin("queue_wait") is not None


# --------------------------------------- round 19: quantile digest
class TestQuantileDigest:
    def test_quantiles_within_1pct_of_exact_on_1e5(self):
        """The dispatcher-regression satellite pin: digest p50/p95/p99 vs
        exact on a 1e5-sample synthetic latency distribution, relative
        error <= 1% (the default 0.5% bucketing leaves headroom)."""
        rng = np.random.default_rng(7)
        lat_ns = rng.lognormal(mean=15.0, sigma=1.0, size=100_000)
        d = QuantileDigest()
        d.add_many(lat_ns)
        for q in (0.50, 0.95, 0.99):
            exact = float(np.quantile(lat_ns, q))
            got = d.quantile(q)
            assert abs(got - exact) / exact <= 0.01, q

    def test_merge_is_exact(self):
        """Same bucketing -> merged counts are bit-identical to a single
        digest over the concatenation (how ReplicaFleet pools replicas)."""
        rng = np.random.default_rng(11)
        a = rng.lognormal(14.0, 1.0, 5_000)
        b = rng.lognormal(16.0, 0.5, 5_000)
        d1, d2, dall = QuantileDigest(), QuantileDigest(), QuantileDigest()
        d1.add_many(a)
        d2.add_many(b)
        d1.merge(d2)
        dall.add_many(np.concatenate([a, b]))
        assert np.array_equal(d1.counts, dall.counts)
        assert d1.n == dall.n
        assert d1.quantile(0.99) == dall.quantile(0.99)

    def test_merge_refuses_different_bucketing(self):
        with pytest.raises(ValueError, match="bucketing"):
            QuantileDigest().merge(QuantileDigest(rel_error=0.01))

    def test_memory_is_fixed(self):
        """O(1) memory forever — the reason the dispatcher's append-only
        latency list is gone."""
        d = QuantileDigest()
        n_buckets = d.counts.size
        assert n_buckets < 3_000  # ~16 KB of int64
        d.add_many(np.random.default_rng(0).lognormal(15, 1, 50_000))
        assert d.counts.size == n_buckets

    def test_stats_ms_shape(self):
        d = QuantileDigest()
        assert d.stats_ms() == {"n": 0, "p50_ms": None, "p95_ms": None,
                                "p99_ms": None, "mean_ms": None}
        d.add(2_000_000.0)  # 2 ms in ns
        s = d.stats_ms()
        assert s["n"] == 1
        assert s["p50_ms"] == pytest.approx(2.0, rel=0.02)
        assert s["mean_ms"] == pytest.approx(2.0, rel=1e-6)


# --------------------------------------- round 19: health plane
class TestHealthPlane:
    def test_watch_rule_thresholds_are_inclusive(self):
        r = WatchRule("shed", "s", 0.05, 0.25, kind="ratio",
                      denominator="a")
        assert r.evaluate({"s": 0, "a": 100})["verdict"] == OK
        assert r.evaluate({"s": 5, "a": 100})["verdict"] == DEGRADED
        assert r.evaluate({"s": 25, "a": 100})["verdict"] == CRITICAL
        d = WatchRule("deaths", "d", 1, 4, kind="delta")
        assert d.evaluate({})["verdict"] == OK
        assert d.evaluate({"d": 1})["verdict"] == DEGRADED
        assert d.evaluate({"d": 4})["verdict"] == CRITICAL

    def test_monitor_windows_diff_counters(self):
        """Each snapshot's rules see ONLY the inter-snapshot delta: a
        healthy first window then a shed storm flips OK -> CRITICAL."""
        run = telemetry.start_run("health_mon")
        try:
            mon = HealthMonitor()
            telemetry.count("serving.admitted", 100)
            rep1 = mon.snapshot(run)
            assert rep1.verdict == OK
            telemetry.count("serving.admitted", 100)
            telemetry.count("serving.shed", 60)
            rep2 = mon.snapshot(run)
            shed = next(r for r in rep2.rules if r["rule"] == "shed_rate")
            assert shed["value"] == pytest.approx(0.6)
            assert rep2.verdict == CRITICAL
        finally:
            telemetry.finish_run()

    def test_staleness_rides_the_gauge(self):
        run = telemetry.start_run("health_stale")
        try:
            telemetry.gauge("continual.staleness_s", 12.5)
            rep = snapshot(run)
            assert rep.staleness_s == 12.5
            assert "photon_tpu_serving_staleness_seconds 12.5" in \
                rep.prometheus()
        finally:
            telemetry.finish_run()

    def test_no_run_snapshot_is_ok_and_empty(self):
        rep = HealthMonitor().snapshot(run=None)
        assert rep.verdict == OK
        assert rep.name == "(no run)"
        assert rep.rates == {} and rep.staleness_s is None

    def test_report_from_jsonl_and_torn_file(self, tmp_path):
        path = str(tmp_path / "r.jsonl")
        telemetry.start_run("offline", jsonl_path=path)
        telemetry.count("serving.admitted", 10)
        telemetry.gauge("continual.staleness_s", 3.0)
        telemetry.finish_run()
        rep = report_from_jsonl(path)
        assert rep.name == "offline"
        assert rep.staleness_s == 3.0
        assert rep.counters["serving.admitted"] == 10
        prom = rep.prometheus()
        assert "photon_tpu_serving_admitted_total 10" in prom
        assert "photon_tpu_health_verdict 0" in prom

        # torn: run_end never landed + a mid-record tear — still a
        # report, never a crash
        with open(path) as fh:
            lines = [ln for ln in fh.read().splitlines()
                     if '"run_end"' not in ln]
        torn = str(tmp_path / "torn.jsonl")
        with open(torn, "w") as fh:
            fh.write("\n".join(lines) + "\n" + '{"type": "co')
        rep2 = report_from_jsonl(torn)
        assert rep2.verdict == OK
        assert rep2.counters == {} and rep2.window_s == 0.0


# --------------------------------------- round 19: cross-rank aggregation
class TestCrossRankAggregation:
    def _write_rank(self, path, name, started_unix, spans, counters,
                    complete=True):
        """Hand-crafted rank JSONL (same record shapes run.Run emits) —
        full control over wall clocks and tears."""
        with open(path, "w") as fh:
            fh.write(json.dumps({"type": "run_start", "name": name,
                                 "started_unix": started_unix}) + "\n")
            for p, secs, t_s in spans:
                fh.write(json.dumps({"type": "span", "name": p, "path": p,
                                     "seconds": secs, "depth": 0,
                                     "t_s": t_s}) + "\n")
            if complete:
                fh.write(json.dumps({"type": "run_end", "duration_s": 5.0,
                                     "counters": counters,
                                     "gauges": {}}) + "\n")

    def test_merge_names_straggler_by_min_barrier_wait(self, tmp_path):
        """Under a barrier the straggler arrives last and waits LEAST —
        rank 1 here, corroborated by its larger decode load."""
        self._write_rank(tmp_path / "p0.jsonl", "r0", 100.0,
                         [("parallel.barrier_wait", 2.0, 3.0)],
                         {"ingest.chunks": 4})
        self._write_rank(tmp_path / "p1.jsonl", "r1", 100.0,
                         [("parallel.barrier_wait", 0.1, 4.9)],
                         {"ingest.chunks": 8})
        rep = aggregate_cluster(str(tmp_path))
        assert rep["complete"]
        assert rep["n_ranks"] == 2 == rep["n_expected"]
        assert rep["skew"]["straggler_rank"] == 1
        assert "rank 1 is the straggler" in rep["skew"]["attribution"]
        assert rep["counters_total"]["ingest.chunks"] == 12
        assert rep["skew"]["barrier_wait_s"]["spread"] == \
            pytest.approx(1.9)

    def test_straggler_falls_back_to_decode_work(self, tmp_path):
        self._write_rank(tmp_path / "p0.jsonl", "r0", 100.0, [],
                         {"ingest.chunks": 2})
        self._write_rank(tmp_path / "p1.jsonl", "r1", 100.0, [],
                         {"ingest.chunks": 9})
        rep = aggregate_cluster(str(tmp_path))
        assert rep["skew"]["straggler_rank"] == 1
        assert rep["skew"]["decode_chunks"]["spread"] == 7

    def test_torn_mid_record_rank_keeps_prefix(self, tmp_path):
        """A rank killed mid-write: its torn tail drops, its prefix still
        contributes, the cluster report is marked incomplete."""
        self._write_rank(tmp_path / "p0.jsonl", "r0", 100.0,
                         [("solve", 1.0, 0.5)], {"ingest.chunks": 3})
        with open(tmp_path / "p1.jsonl", "w") as fh:
            fh.write(json.dumps({"type": "run_start", "name": "r1",
                                 "started_unix": 100.2}) + "\n")
            fh.write(json.dumps({"type": "span", "name": "solve",
                                 "path": "solve", "seconds": 0.7,
                                 "depth": 0, "t_s": 0.1}) + "\n")
            fh.write('{"type": "span", "path": "x", "secon')  # the kill
        rep = aggregate_cluster(str(tmp_path), expect_ranks=2)
        assert rep["n_ranks"] == 2
        assert not rep["complete"]  # rank 1 never wrote run_end
        assert rep["missing_ranks"] == []
        assert rep["ranks"]["1"]["complete"] is False
        assert rep["ranks"]["1"]["span_totals"] == {"solve": 0.7}
        assert rep["counters_total"] == {"ingest.chunks": 3.0}

    def test_missing_rank_is_named_not_crashed(self, tmp_path):
        self._write_rank(tmp_path / "p0.jsonl", "r0", 100.0, [], {})
        self._write_rank(tmp_path / "p2.jsonl", "r2", 100.0, [], {})
        rep = aggregate_cluster(str(tmp_path))  # n_expected inferred: 3
        assert rep["n_expected"] == 3
        assert rep["missing_ranks"] == [1]
        assert not rep["complete"]
        rep2 = aggregate_cluster(str(tmp_path), expect_ranks=4)
        assert rep2["missing_ranks"] == [1, 3]

    def test_clock_skewed_timelines_align_on_wall_clock(self, tmp_path):
        """Rank 1 started 50 s later: its EARLY span must land after
        rank 0's late span on the merged wall clock, and the start
        spread is reported as clock skew."""
        self._write_rank(tmp_path / "p0.jsonl", "r0", 1000.0,
                         [("solve", 1.0, 10.0)], {})
        self._write_rank(tmp_path / "p1.jsonl", "r1", 1050.0,
                         [("solve", 1.0, 2.0)], {})
        rep = aggregate_cluster(str(tmp_path))
        assert rep["clock_skew_s"] == pytest.approx(50.0)
        tl = rep["timeline"]
        assert [e["rank"] for e in tl] == [0, 1]
        assert tl[0]["start_unix"] == pytest.approx(1010.0)
        assert tl[1]["start_unix"] == pytest.approx(1052.0)

    def test_rank_files_and_dict_source(self, tmp_path):
        self._write_rank(tmp_path / "p0.jsonl", "r0", 1.0, [], {})
        (tmp_path / "not_a_rank.jsonl").write_text("{}\n")
        files = rank_files(str(tmp_path))
        assert list(files) == [0]
        rep = aggregate_cluster({0: files[0]})
        assert rep["n_ranks"] == 1 and rep["complete"]
