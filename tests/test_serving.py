"""Online serving tier (photon_tpu/serving): coefficient-store lookups +
mmap persistence, the pow2 AOT program ladder's never-retrace guarantee,
micro-batching dispatcher semantics, and THE acceptance parity —
dispatcher-batched scores bit-identical to the offline drivers/score.py
path for the same model and rows, including the cold-miss
fixed-effect-only fallback.

Marked `release_programs`: the ladder compiles one program per rung per
configuration; teardown drops them (tests/conftest.py).
"""
import copy
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

from photon_tpu import serving, telemetry
from photon_tpu.telemetry import trace
from photon_tpu.data.matrix import SparseRows
from photon_tpu.game.dataset import GameData
from photon_tpu.game.model import (FixedEffectModel, GameModel,
                                   RandomEffectModel)
from photon_tpu.game.scoring import score_game
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.ops.losses import TaskType
from photon_tpu.serving.__main__ import build_demo_model

pytestmark = pytest.mark.release_programs

SPARSE_K = 3


@pytest.fixture(autouse=True)
def _detached():
    yield
    telemetry.finish_run()


@pytest.fixture(scope="module")
def demo():
    """(model, store, ladder): one ladder for the whole module — shared
    shapes keep the compile count at one program per rung."""
    model, _ = build_demo_model(seed=7)
    store = serving.CoefficientStore.from_game_model(model)
    ladder = serving.ProgramLadder(store, ladder=(4, 8),
                                   sparse_k={"member": SPARSE_K},
                                   output_mean=True)
    return model, store, ladder


def _requests(rng, model, n, unseen_every=5):
    """n ragged requests over the demo model's shards; every
    ``unseen_every``-th entity key is unknown to the store."""
    d_f = int(model["fixed"].model.coefficients.dim)
    d_r = model["perEntity"].dim
    E = model["perEntity"].n_entities
    xg = rng.normal(size=(n, d_f)).astype(np.float32)
    ind = rng.integers(0, d_r, size=(n, SPARSE_K)).astype(np.int32)
    val = rng.normal(size=(n, SPARSE_K)).astype(np.float32)
    offs = rng.normal(size=n).astype(np.float32)
    ents = [f"zz{i}" if i % unseen_every == 0 else f"e{i % E:03d}"
            for i in range(n)]
    reqs = [serving.ScoreRequest(
        features={"global": xg[i], "member": (ind[i], val[i])},
        entities={"memberId": ents[i]}, offset=float(offs[i]))
        for i in range(n)]
    data = GameData.build(np.zeros(n, np.float32),
                          {"global": xg, "member": SparseRows(ind, val, d_r)},
                          {"memberId": np.asarray(ents)}, offsets=offs)
    return reqs, data, ents


# ----------------------------------------------------------------- the store
class TestCoefficientStore:
    def test_lookup_seen_unseen_and_zero_row(self, demo):
        model, store, _ = demo
        re = model["perEntity"]
        ids, miss = store.lookup("perEntity", ["e003", "nope", "e000"])
        assert miss == 1
        assert ids.tolist() == [3, re.n_entities, 0]
        # the cold-miss row is all-zero: the graceful-degradation row
        assert (store.random["perEntity"].coefficients[-1] == 0).all()
        # matches the offline model's own unseen-entity convention
        np.testing.assert_array_equal(
            ids, re.dense_ids(np.asarray(["e003", "nope", "e000"])))

    def test_save_open_roundtrip_mmap(self, demo, tmp_path):
        _, store, _ = demo
        store.save(tmp_path / "s")
        back = serving.CoefficientStore.open(tmp_path / "s", mmap=True)
        assert back.order == store.order and back.task == store.task
        np.testing.assert_array_equal(back.fixed["fixed"].weights,
                                      store.fixed["fixed"].weights)
        np.testing.assert_array_equal(
            back.random["perEntity"].coefficients,
            store.random["perEntity"].coefficients)
        # mmap=True really maps (no heap copy of a multi-GB store)
        assert isinstance(back.random["perEntity"].coefficients, np.memmap)
        ids_a, _ = store.lookup("perEntity", ["e001", "x"])
        ids_b, _ = back.lookup("perEntity", ["e001", "x"])
        np.testing.assert_array_equal(ids_a, ids_b)

    def test_save_kill_mid_write_is_crash_consistent(self, demo, tmp_path):
        """Kill-mid-write regression (elastic-runs round): `save` commits
        payload files temp+fsync+rename-first and the manifest LAST, so a
        preemption during the write leaves (a) a fresh directory with NO
        manifest — `open` fails cleanly instead of reading a torn .npy —
        and (b) a re-save over the old store either the complete old or
        complete new manifest, with every referenced block loadable."""
        from photon_tpu import checkpoint

        _, store, _ = demo
        out = tmp_path / "s"
        # (a) fresh save killed in the write phase (before any rename)
        with pytest.raises(checkpoint.InjectedFault):
            with checkpoint.fault_plan(
                    checkpoint.FaultPlan.kill_at("commit", 1)):
                store.save(out)
        assert not (out / "serving_store.json").exists()
        with pytest.raises(FileNotFoundError):
            serving.CoefficientStore.open(out)
        # (b) retry completes; then a killed RE-save (mid manifest
        # commit — the LAST commit point of a save) leaves the previous
        # committed store fully loadable
        with checkpoint.record_sites() as rec:
            store.save(out)
        with pytest.raises(checkpoint.InjectedFault):
            with checkpoint.fault_plan(
                    checkpoint.FaultPlan.kill_at("commit",
                                                 rec.hits["commit"])):
                store.save(out)
        back = serving.CoefficientStore.open(out, mmap=False)
        np.testing.assert_array_equal(
            back.random["perEntity"].coefficients,
            store.random["perEntity"].coefficients)

    def test_open_rejects_foreign_dir(self, tmp_path):
        (tmp_path / "serving_store.json").write_text('{"format": "nope"}')
        with pytest.raises(ValueError, match="not a"):
            serving.CoefficientStore.open(tmp_path)

    def test_reload_requires_identical_shapes(self, demo):
        model, store, _ = demo
        other = serving.CoefficientStore.from_game_model(model)
        store.reload_coefficients(other)  # identical shapes: fine
        small, _ = build_demo_model(seed=1, n_entities=4)
        with pytest.raises(ValueError, match="identically-shaped"):
            store.reload_coefficients(
                serving.CoefficientStore.from_game_model(small))

    def test_paldb_directory_equivalence(self, demo, tmp_path):
        from photon_tpu import native

        if not native.available():
            pytest.skip("native toolchain unavailable")
        model, store, _ = demo
        pstore = serving.CoefficientStore.from_game_model(model, paldb=True)
        keys = ["e000", "e007", "absent", "e015"]
        np.testing.assert_array_equal(store.lookup("perEntity", keys)[0],
                                      pstore.lookup("perEntity", keys)[0])
        pstore.save(tmp_path / "p")
        back = serving.CoefficientStore.open(tmp_path / "p")
        np.testing.assert_array_equal(back.lookup("perEntity", keys)[0],
                                      store.lookup("perEntity", keys)[0])


# -------------------------------------------------------------- the programs
class TestProgramLadder:
    def test_bucket_selection(self, demo):
        _, _, ladder = demo
        assert [ladder.bucket_for(n) for n in (1, 4, 5, 8)] == [4, 4, 8, 8]
        with pytest.raises(ValueError, match="exceeds ladder top"):
            ladder.bucket_for(9)

    def test_non_pow2_ladder_rejected(self, demo):
        _, store, _ = demo
        with pytest.raises(ValueError, match="pow2"):
            serving.ProgramLadder(store, ladder=(4, 6))

    def test_mixed_sizes_never_retrace(self, demo):
        """THE steady-state law: any mix of request sizes compiles at
        most one program per rung (TraceSignatureLog-asserted)."""
        _, _, ladder = demo
        before = len(ladder.signature_log.signatures("serving.score"))
        for B in (4, 8, 4, 8, 4):
            args = ladder.example_args(B)
            ladder.score_padded(args[0], args[1], args[2])
        n_sigs = ladder.assert_no_retrace()
        assert n_sigs <= len(ladder.ladder)
        assert n_sigs >= max(before, 2)  # both rungs actually dispatched

    def test_aot_export_replay_bitwise(self, demo, tmp_path):
        """The AOT plane: warmup exports one program per rung; a FRESH
        ladder over the same store replays (no export) bit-identically."""
        model, store, _ = demo
        aot = str(tmp_path / "aot")
        ladder = serving.ProgramLadder(store, ladder=(4,),
                                       sparse_k={"member": SPARSE_K},
                                       aot_dir=aot, model_tag="demo")
        assert ladder.warmup() == 1
        files = [f for f in os.listdir(aot) if f.endswith(".jaxexp")]
        assert len(files) == 1  # one export per (model, rung)
        rng = np.random.default_rng(3)
        reqs, data, _ = _requests(rng, model, 4)
        replay = serving.ProgramLadder(store, ladder=(4,),
                                       sparse_k={"member": SPARSE_K},
                                       aot_dir=aot, model_tag="demo")
        d = serving.MicroBatchDispatcher(replay, max_batch=4,
                                         max_delay_us=100)
        try:
            got = np.asarray([f.result(timeout=30)
                              for f in [d.submit(q) for q in reqs]],
                             np.float32)
        finally:
            d.close()
        want = np.asarray(model.mean(score_game(model, data)), np.float32)
        assert got.tobytes() == want.tobytes()
        # the replay ladder REPLAYED — it exported nothing new
        assert sorted(os.listdir(aot)) == sorted(files)

    def test_schema_tag_isolates_exports(self, demo, tmp_path):
        """A ladder-schema redesign (different AotStore schema tag) must
        MISS the old files, never replay them."""
        from photon_tpu.utils.aot import AotStore

        store_a = AotStore(str(tmp_path), schema="serving-ladder-v1")
        store_b = AotStore(str(tmp_path), schema="serving-ladder-v2")
        fp = "00" * 8
        assert store_a._path("k", fp) != store_b._path("k", fp)


# ---------------------------------------------------- dispatcher + acceptance
class TestDispatcherParity:
    def test_bitwise_parity_with_offline_driver(self, demo, tmp_path):
        """ACCEPTANCE: the full offline path — save_game_model → Avro
        scoring data → drivers/score.py run_scoring — against the same
        rows dispatched through the micro-batcher: bit-identical scores,
        including the cold-miss fixed-effect-only rows."""
        from photon_tpu.data.avro_io import write_avro
        from photon_tpu.data.index_map import INTERCEPT_KEY, IndexMap
        from photon_tpu.data.ingest import training_example_schema
        from photon_tpu.data.model_io import load_game_model, save_game_model
        from photon_tpu.drivers.score import ScoringParams, run_scoring

        rng = np.random.default_rng(11)
        n, E = 53, 7
        task = TaskType.LOGISTIC_REGRESSION
        # feature shards: "fs" = bag g features a, c + intercept (d=3);
        # "us" = bag pu feature b, no intercept (d=1)
        imap_f = IndexMap().build(["a", "c", INTERCEPT_KEY]).freeze()
        imap_u = IndexMap().build(["b"]).freeze()
        keys = np.asarray(sorted(f"u{i}" for i in range(E)))
        model = GameModel({
            "fixed": FixedEffectModel(GeneralizedLinearModel(
                Coefficients(jnp.asarray(
                    rng.normal(size=3).astype(np.float32))), task), "fs"),
            "perUser": RandomEffectModel(
                entity_name="userId", feature_shard="us", task=task,
                coefficients=jnp.asarray(
                    rng.normal(size=(E, 1)).astype(np.float32)),
                entity_keys=keys,
                key_to_index={k: i for i, k in enumerate(keys.tolist())}),
        }, task)
        model_dir = tmp_path / "model"
        save_game_model(str(model_dir), model,
                        {"fixed": imap_f, "perUser": imap_u})

        a = rng.normal(size=n).astype(np.float32)
        c = rng.normal(size=n).astype(np.float32)
        b = rng.normal(size=n).astype(np.float32)
        offs = rng.normal(size=n).astype(np.float32)
        # u7/u8 never trained: the driver maps them to the zero row, the
        # dispatcher counts them as cold misses — SAME score either way
        users = [f"u{i % (E + 2)}" for i in range(n)]
        schema = training_example_schema(feature_bags=("g", "pu"),
                                         entity_fields=("userId",))
        recs = [{"response": float(i % 2), "offset": float(offs[i]),
                 "weight": None, "uid": f"r{i}", "userId": users[i],
                 "g": [{"name": "a", "term": "", "value": float(a[i])},
                       {"name": "c", "term": "", "value": float(c[i])}],
                 "pu": [{"name": "b", "term": "", "value": float(b[i])}]}
                for i in range(n)]
        data_dir = tmp_path / "data"
        data_dir.mkdir()
        write_avro(data_dir / "part-0.avro", recs, schema, block_records=16)

        out = run_scoring(ScoringParams(
            model_dir=str(model_dir), data_path=str(data_dir),
            output_dir=str(tmp_path / "out"),
            feature_shards={"fs": {"bags": ["g"], "has_intercept": True},
                            "us": {"bags": ["pu"], "has_intercept": False}},
            entity_fields=["userId"]))
        assert out.scores.shape == (n,)

        # the serving side, built from the SAME saved artifacts
        loaded, _ = load_game_model(str(model_dir))
        store = serving.CoefficientStore.from_game_model(loaded)
        # rungs ≥ 8: bit-parity-safe vs the driver's 4096-row chunks
        # (sub-8 CPU matvec kernels drift ULPs — ProgramLadder docstring)
        ladder = serving.ProgramLadder(store, ladder=(8, 16),
                                       output_mean=True)
        d = serving.MicroBatchDispatcher(ladder, max_batch=16,
                                         max_delay_us=500)
        r = telemetry.start_run("parity")
        try:
            futs = [d.submit(serving.ScoreRequest(
                features={"fs": np.asarray([a[i], c[i], 1.0], np.float32),
                          "us": np.asarray([b[i]], np.float32)},
                entities={"userId": users[i]}, offset=float(offs[i])))
                for i in range(n)]
            got = np.asarray([f.result(timeout=30) for f in futs])
        finally:
            d.close()
            telemetry.finish_run()
        # driver scores are the f32 device result widened to f64 — exact,
        # so bitwise f64 comparison is the honest equality
        np.testing.assert_array_equal(got.astype(np.float64), out.scores)
        ladder.assert_no_retrace()
        n_cold = sum(1 for u in users if u in ("u7", "u8"))
        assert r.counters["serving.cold_misses"] == float(n_cold) > 0

    def test_margin_head_matches_score_game(self, demo):
        """output_mean=False serves the raw margin — score_game verbatim."""
        model, store, _ = demo
        ladder = serving.ProgramLadder(store, ladder=(8,),
                                       sparse_k={"member": SPARSE_K},
                                       output_mean=False)
        rng = np.random.default_rng(5)
        reqs, data, _ = _requests(rng, model, 8)
        d = serving.MicroBatchDispatcher(ladder, max_batch=8,
                                         max_delay_us=200)
        try:
            got = np.asarray([f.result(timeout=30)
                              for f in [d.submit(q) for q in reqs]],
                             np.float32)
        finally:
            d.close()
        want = np.asarray(score_game(model, data), np.float32)
        assert got.tobytes() == want.tobytes()


class TestDispatcherBehavior:
    def test_single_request_flushes_on_deadline(self, demo):
        _, _, ladder = demo
        d = serving.MicroBatchDispatcher(ladder, max_delay_us=1000)
        rng = np.random.default_rng(0)
        model = demo[0]
        reqs, _, _ = _requests(rng, model, 1)
        try:
            assert isinstance(d.score(reqs[0], timeout=30), float)
        finally:
            d.close()

    def test_counters_events_and_latency(self, demo, tmp_path):
        model, _, ladder = demo
        rng = np.random.default_rng(2)
        n = 11
        reqs, _, ents = _requests(rng, model, n)
        jsonl = str(tmp_path / "serving.jsonl")
        r = telemetry.start_run("disp", jsonl_path=jsonl)
        d = serving.MicroBatchDispatcher(ladder, max_batch=8,
                                         max_delay_us=2000)
        try:
            futs = [d.submit(q) for q in reqs]
            [f.result(timeout=30) for f in futs]
        finally:
            d.close()
            telemetry.finish_run()
        assert r.counters["serving.requests"] == float(n)
        assert r.counters["serving.batches"] >= 2  # 11 > max_batch=8
        n_unseen = sum(1 for e in ents if e.startswith("zz"))
        assert r.counters["serving.cold_misses"] == float(n_unseen)
        assert "serving.pad_waste" in r.counters
        assert "serving.batch_fill" in r.gauges
        batches = list(telemetry.read_jsonl(jsonl, kind="serving_batch"))
        assert sum(e["rows"] for e in batches) == n
        assert all(e["bucket"] in ladder.ladder for e in batches)
        # close() gauged the percentile summary into the run
        assert r.gauges["serving.latency_p50_ms"] <= \
            r.gauges["serving.latency_p99_ms"]
        st = d.latency_stats()
        assert st["n"] == n and st["p50_ms"] <= st["p95_ms"] <= st["p99_ms"]

    def test_close_flushes_queue_and_rejects_after(self, demo):
        model, _, ladder = demo
        rng = np.random.default_rng(4)
        reqs, _, _ = _requests(rng, model, 6)
        d = serving.MicroBatchDispatcher(ladder, max_batch=8,
                                         max_delay_us=10_000_000)
        futs = [d.submit(q) for q in reqs[:3]]
        d.close()  # must flush the queued 3, not abort them
        assert all(isinstance(f.result(timeout=5), float) for f in futs)
        with pytest.raises(RuntimeError, match="closed"):
            d.submit(reqs[3])

    def test_bad_request_fails_its_future_only(self, demo):
        model, _, ladder = demo
        rng = np.random.default_rng(6)
        reqs, _, _ = _requests(rng, model, 2)
        d = serving.MicroBatchDispatcher(ladder, max_delay_us=500)
        try:
            bad = serving.ScoreRequest(features={}, entities={})
            fb = d.submit(bad)
            with pytest.raises(Exception):
                fb.result(timeout=30)
            # the dispatcher survives and serves the next request
            assert isinstance(d.score(reqs[0], timeout=30), float)
        finally:
            d.close()


# ---------------------------------------------------------- request tracing
class TestDispatcherTracing:
    """telemetry/trace.py riding the real dispatcher: a deterministically
    slow hop must be NAMED by the slowest exemplar, arming tracing must
    not mint new rung signatures, and the disarmed path stays free."""

    def test_slow_device_flush_names_the_hop(self, demo):
        """THE acceptance: inject a deterministic slow hop (a sleeping
        executor) and the slowest-trace exemplar names it."""
        model, _, ladder = demo
        rng = np.random.default_rng(11)
        reqs, _, _ = _requests(rng, model, 4)
        d = serving.MicroBatchDispatcher(ladder, max_batch=8,
                                         max_delay_us=500)
        real_execute = d._executor.execute

        def slow_execute(batch):
            time.sleep(0.05)
            return real_execute(batch)

        d._executor.execute = slow_execute
        try:
            with trace.tracing(k=2) as res:
                futs = [d.submit(q) for q in reqs]
                [f.result(timeout=30) for f in futs]
                slow = res.slowest()
        finally:
            d.close()
        assert slow is not None and slow["slowest_hop"] == "device_flush"
        assert slow["breakdown_ms"]["device_flush"] >= 40.0
        assert res.n_offered == len(reqs)
        # the full hop chain survives the three thread crossings
        names = [h["name"] for h in slow["hops"]]
        assert names == ["queue_wait", "device_flush", "retire_wait"]

    def test_slow_queue_wait_names_the_hop(self, demo):
        """Same acceptance from the other side: a long batching delay on
        a lone request makes queue_wait the dominant hop."""
        model, _, ladder = demo
        rng = np.random.default_rng(12)
        reqs, _, _ = _requests(rng, model, 1)
        d = serving.MicroBatchDispatcher(ladder, max_batch=8,
                                         max_delay_us=80_000)
        try:
            with trace.tracing(k=1) as res:
                assert isinstance(d.score(reqs[0], timeout=30), float)
                slow = res.slowest()
        finally:
            d.close()
        assert slow is not None and slow["slowest_hop"] == "queue_wait"
        assert slow["breakdown_ms"]["queue_wait"] >= 60.0

    def test_armed_tracing_never_retraces(self, demo):
        model, _, ladder = demo
        rng = np.random.default_rng(13)
        reqs, _, _ = _requests(rng, model, 18)
        d = serving.MicroBatchDispatcher(ladder, max_batch=8,
                                         max_delay_us=2000)
        try:
            # untraced warm drive populates both rungs' signatures...
            futs = [d.submit(q) for q in reqs[:9]]
            [f.result(timeout=30) for f in futs]
            before = ladder.assert_no_retrace()
            # ...then the armed drive must not mint a single new one
            with trace.tracing(k=4):
                futs = [d.submit(q) for q in reqs[9:]]
                [f.result(timeout=30) for f in futs]
        finally:
            d.close()
        assert ladder.assert_no_retrace() == before

    def test_disarmed_requests_carry_no_trace(self, demo):
        from photon_tpu.serving.dispatcher import _Pending
        model, _, ladder = demo
        rng = np.random.default_rng(14)
        reqs, _, _ = _requests(rng, model, 2)
        # the request object is where the trace rides; disarmed it is None
        assert _Pending(reqs[0]).trace is None
        with trace.tracing(k=2):
            assert _Pending(reqs[0]).trace is not None
        assert trace.reservoir() is None


# ------------------------------------------------------------ overload policy
class TestAdmission:
    """serving/admission.py: deadlines, watermark shedding, bounded
    submit. The invariants: every dropped request resolves to a typed
    `Shed` (futures never leak, callers never block forever), the
    counters add up, and the policy layer never changes the device
    programs (the live half of the registered
    `serving_admission_program_invariance` contract)."""

    def test_default_policy_is_off(self):
        p = serving.AdmissionPolicy()
        assert not p.active
        ctrl = serving.AdmissionController(p)
        assert ctrl.submit_shed_reason(10**9) is None
        assert ctrl.deadline_ns(serving.ScoreRequest(features={}), 0) is None
        assert ctrl.submit_timeout_s(None) is None  # legacy: block forever

    def test_shed_is_typed_and_falsy(self):
        s = serving.Shed("watermark", queue_depth=3)
        assert not s and s.reason == "watermark"

    def test_deadline_expired_resolves_shed(self, demo):
        """deadline_ms=0.0 expires every request at its first batch-slot
        check: the future resolves to Shed("deadline_expired"), counted,
        and the batch dispatches WITHOUT them."""
        model, _, ladder = demo
        rng = np.random.default_rng(8)
        reqs, _, _ = _requests(rng, model, 5)
        r = telemetry.start_run("admission_deadline")
        d = serving.MicroBatchDispatcher(
            ladder, max_batch=8, max_delay_us=500,
            policy=serving.AdmissionPolicy(deadline_ms=0.0))
        try:
            res = [d.submit(q).result(timeout=30) for q in reqs]
        finally:
            d.close()
            telemetry.finish_run()
        assert all(isinstance(v, serving.Shed)
                   and v.reason == "deadline_expired" for v in res)
        assert r.counters["serving.deadline_expired"] == 5.0
        assert r.counters["serving.admitted"] == 5.0
        assert "serving.requests" not in r.counters  # nothing dispatched

    def test_request_deadline_overrides_policy(self, demo):
        """A per-request deadline_ms wins over the policy default: the
        doomed request sheds, its batch-mates score."""
        model, _, ladder = demo
        rng = np.random.default_rng(9)
        reqs, data, _ = _requests(rng, model, 8)
        reqs[2] = serving.ScoreRequest(
            features=reqs[2].features, entities=reqs[2].entities,
            offset=reqs[2].offset, deadline_ms=0.0)
        d = serving.MicroBatchDispatcher(
            ladder, max_batch=8, max_delay_us=50_000,
            policy=serving.AdmissionPolicy(deadline_ms=10_000.0))
        try:
            res = [d.submit(q).result(timeout=30) for q in reqs]
        finally:
            d.close()
        assert isinstance(res[2], serving.Shed)
        assert res[2].reason == "deadline_expired"
        alive = [i for i in range(8) if i != 2]
        assert all(isinstance(res[i], float) for i in alive)
        want = np.asarray(model.mean(score_game(model, data)), np.float32)
        for i in alive:  # survivors land on rung 8: bit-parity territory
            assert np.float32(res[i]) == want[i]

    def test_watermark_sheds_at_submit(self, demo):
        model, _, ladder = demo
        rng = np.random.default_rng(10)
        reqs, _, _ = _requests(rng, model, 6)
        r = telemetry.start_run("admission_watermark")
        d = serving.MicroBatchDispatcher(
            ladder, max_batch=8, max_delay_us=500,
            policy=serving.AdmissionPolicy(shed_watermark=0))
        try:
            res = [d.submit(q).result(timeout=30) for q in reqs]
        finally:
            d.close()
            telemetry.finish_run()
        assert all(isinstance(v, serving.Shed) and v.reason == "watermark"
                   for v in res)
        assert r.counters["serving.shed"] == 6.0
        assert "serving.admitted" not in r.counters  # never enqueued

    def test_bounded_submit_never_blocks_forever(self, demo):
        """queue_depth=1 + submit(timeout=0): a full queue sheds
        ("queue_full") instead of blocking; every future resolves to a
        float or a typed Shed and the accounting closes."""
        model, _, ladder = demo
        rng = np.random.default_rng(12)
        reqs, _, _ = _requests(rng, model, 200)
        r = telemetry.start_run("admission_bounded")
        d = serving.MicroBatchDispatcher(ladder, max_batch=8,
                                         max_delay_us=100, queue_depth=1)
        try:
            futs = [d.submit(q, timeout=0.0) for q in reqs]
            res = [f.result(timeout=60) for f in futs]
        finally:
            d.close()
            telemetry.finish_run()
        sheds = [v for v in res if isinstance(v, serving.Shed)]
        scored = [v for v in res if isinstance(v, float)]
        assert len(sheds) + len(scored) == 200
        assert sheds and all(s.reason == "queue_full" for s in sheds)
        assert r.counters["serving.shed"] == float(len(sheds))
        assert r.counters["serving.admitted"] == float(len(scored))

    def test_close_resolves_expired_inflight_futures(self, demo):
        """THE close() guarantee with overload policy armed: requests
        whose deadline expired while batched-but-undispatched resolve at
        close (shed, never leaked) — the dispatcher holds them in its
        assembly loop (max_delay 10 s, batch unfilled) until close
        flushes, and the flush-time deadline check sheds them all."""
        model, _, ladder = demo
        rng = np.random.default_rng(13)
        reqs, _, _ = _requests(rng, model, 6)
        r = telemetry.start_run("admission_close")
        d = serving.MicroBatchDispatcher(
            ladder, max_batch=8, max_delay_us=10_000_000,
            policy=serving.AdmissionPolicy(deadline_ms=100.0))
        futs = [d.submit(q) for q in reqs]
        import time as _time

        _time.sleep(0.15)  # all six expire while awaiting batch-mates
        d.close()
        telemetry.finish_run()
        assert all(f.done() for f in futs)  # nothing leaked
        res = [f.result(timeout=1) for f in futs]
        assert all(isinstance(v, serving.Shed)
                   and v.reason == "deadline_expired" for v in res)
        assert r.counters["serving.deadline_expired"] == 6.0

    def test_admission_on_off_never_retraces(self, demo):
        """The same ladder serves admission-off and admission-on traffic
        with zero new signatures — the live face of the registered
        program-invariance contract."""
        model, _, ladder = demo
        rng = np.random.default_rng(14)
        reqs, _, _ = _requests(rng, model, 8)
        before = len(ladder.signature_log.signatures("serving.score"))
        d_off = serving.MicroBatchDispatcher(ladder, max_batch=8,
                                             max_delay_us=50_000)
        try:
            off = [d_off.submit(q).result(timeout=30) for q in reqs]
        finally:
            d_off.close()
        d_on = serving.MicroBatchDispatcher(
            ladder, max_batch=8, max_delay_us=50_000,
            policy=serving.AdmissionPolicy(deadline_ms=10_000.0,
                                           shed_watermark=1 << 20,
                                           submit_timeout_s=5.0))
        try:
            on = [d_on.submit(q).result(timeout=30) for q in reqs]
        finally:
            d_on.close()
        assert off == on  # same model, same rows, same programs
        assert ladder.assert_no_retrace() >= before


# ------------------------------------------------------- hot-swap concurrency
class TestHotSwapConcurrency:
    """`CoefficientStore.reload_coefficients` under an in-flight
    dispatcher flush: every request scores bit-identically under EITHER
    the old or the new model — one coefficient generation per dispatch,
    never a torn fixed-from-A/random-from-B mix — and each swap counts
    on `serving.hot_swaps`."""

    def _scores(self, store, reqs) -> np.ndarray:
        ladder = serving.ProgramLadder(store, ladder=(8, 16),
                                       sparse_k={"member": SPARSE_K},
                                       output_mean=True)
        d = serving.MicroBatchDispatcher(ladder, max_delay_us=200)
        try:
            futs = [d.submit(r) for r in reqs]
            return np.asarray([f.result(timeout=60) for f in futs])
        finally:
            d.close()

    def test_requests_see_old_or_new_never_torn(self):
        import threading

        model_a, _ = build_demo_model(seed=7)
        model_b, _ = build_demo_model(seed=21)  # same structure, new values
        store_a = serving.CoefficientStore.from_game_model(model_a)
        store_b = serving.CoefficientStore.from_game_model(model_b)
        rng = np.random.default_rng(11)
        reqs, _, _ = _requests(rng, model_a, 48)
        # reference scores under each pure generation (rungs ≥ 8 are
        # row-stable across batch compositions — docs/SERVING.md)
        ref_a = self._scores(serving.CoefficientStore.from_game_model(
            model_a), reqs)
        ref_b = self._scores(serving.CoefficientStore.from_game_model(
            model_b), reqs)
        assert (ref_a != ref_b).any()

        run = telemetry.start_run("hot_swap_test")
        live = serving.CoefficientStore.from_game_model(model_a)
        ladder = serving.ProgramLadder(live, ladder=(8, 16),
                                       sparse_k={"member": SPARSE_K},
                                       output_mean=True)
        d = serving.MicroBatchDispatcher(ladder, max_delay_us=100)
        results: dict = {}
        stop = threading.Event()
        n_swaps = 0

        def swapper():
            nonlocal n_swaps
            import time as _time

            flip = [store_b, store_a]
            while not stop.is_set():
                live.reload_coefficients(flip[n_swaps % 2])
                n_swaps += 1
                _time.sleep(0.002)  # don't starve the 1-core CI box

        t = threading.Thread(target=swapper)
        t.start()
        try:
            for rep in range(6):
                futs = [(i, d.submit(r)) for i, r in enumerate(reqs)]
                for i, f in futs:
                    results.setdefault(i, []).append(f.result(timeout=60))
        finally:
            stop.set()
            t.join()
            d.close()
        assert n_swaps >= 2
        for i, got in results.items():
            for v in got:
                assert v == ref_a[i] or v == ref_b[i], (
                    f"request {i} scored {v!r}: neither the old model's "
                    f"{ref_a[i]!r} nor the new model's {ref_b[i]!r} — "
                    "a torn coefficient generation")
        assert run.counters.get("serving.hot_swaps") == n_swaps
        ladder.assert_no_retrace()  # swaps never retrace the rungs

    def test_mid_swap_kill_under_load_keeps_old_model(self, tmp_path):
        """The continual flywheel's crash story under LIVE dispatcher
        load: a kill at the ``swap_publish`` fault site (after the new
        version directory is written, before the CURRENT pointer commits)
        aborts the hot swap with every in-flight request still resolving
        — all on the OLD model, bit-identically — and nothing published.
        The next clean swap then cuts the same traffic over to the new
        model."""
        from photon_tpu import checkpoint, continual

        model_a, _ = build_demo_model(seed=7)
        model_b, _ = build_demo_model(seed=21)
        store_b = serving.CoefficientStore.from_game_model(model_b)
        rng = np.random.default_rng(17)
        reqs, _, _ = _requests(rng, model_a, 32)
        ref_a = self._scores(serving.CoefficientStore.from_game_model(
            model_a), reqs)
        ref_b = self._scores(store_b, reqs)
        assert (ref_a != ref_b).any()

        root = str(tmp_path / "pub")
        live = serving.CoefficientStore.from_game_model(model_a)
        ladder = serving.ProgramLadder(live, ladder=(8, 16),
                                       sparse_k={"member": SPARSE_K},
                                       output_mean=True)
        d = serving.MicroBatchDispatcher(ladder, max_delay_us=100)
        try:
            futs = [d.submit(r) for r in reqs]  # sustained in-flight load
            with pytest.raises(checkpoint.InjectedFault):
                with checkpoint.fault_plan(
                        checkpoint.FaultPlan.kill_at("swap_publish", 1)):
                    continual.hot_swap(live, store_b, root=root,
                                       probe=continual.ParityProbe(
                                           bound=1e9))
            got = np.asarray([f.result(timeout=60) for f in futs])
            # the killed swap never reloaded: everything served OLD
            np.testing.assert_array_equal(got, ref_a)
            from photon_tpu.continual.swap import current_version

            assert current_version(root) is None  # nothing published
            # the half-written version directory from the kill is swept
            # by the next successful publish, which also cuts over
            continual.hot_swap(live, store_b, root=root,
                               probe=continual.ParityProbe(bound=1e9))
            assert current_version(root) is not None
            futs2 = [d.submit(r) for r in reqs]
            got2 = np.asarray([f.result(timeout=60) for f in futs2])
            np.testing.assert_array_equal(got2, ref_b)
        finally:
            d.close()
        ladder.assert_no_retrace()  # neither kill nor swap retraced

    def test_reload_still_rejects_mismatched_shapes(self):
        model, _ = build_demo_model(seed=7)
        small, _ = build_demo_model(seed=7, n_entities=8)
        store = serving.CoefficientStore.from_game_model(model)
        with pytest.raises(ValueError, match="identically-shaped"):
            store.reload_coefficients(
                serving.CoefficientStore.from_game_model(small))


# ----------------------------------------------------------- quantized rungs
class TestQuantizedRungs:
    """The quantized rungs (int8 + row-wise scales, bf16): the warmup
    accuracy gate REFUSES a breach (`QuantizationRefused`, counted), the
    cold-miss row dequantizes to exact zeros, mixed-size dispatch never
    retraces, a hot-swap re-quantizes — and every rung of the default
    ladder equals a float64 numpy margin over the dequantized blocks."""

    def _ladder(self, quantize=None, eps=0.5, E=32, df=12, dr=6, k=3,
                **ladder_kw):
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
        ladder_kw = {"floor": 8, "max_batch": 16, **ladder_kw}
        return serving.ProgramLadder(
            store, sparse_k={"member": k}, quantize=quantize,
            quant_epsilon=eps, **ladder_kw), (df, dr, k, E)

    def test_epsilon_refusal_and_counter(self):
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

    def test_hot_swap_invalidates_qdev(self):
        """A `continual.hot_swap` swings `device_blocks()` to a new
        generation, which invalidates the ladder's `_qdev` quantized-block
        cache — the next dispatch re-quantizes and scores the new model
        (negated coefficients mirror the logistic mean around 0.5),
        through the same executables (no retrace)."""
        from photon_tpu.continual import hot_swap

        ladder, (df, dr, k, _E) = self._ladder(quantize="int8")
        ladder.warmup()
        rng = np.random.default_rng(31)
        off = np.zeros(8, np.float32)
        shards = {"global": rng.normal(size=(8, df)).astype(np.float32),
                  "member": SparseRows(
                      rng.integers(0, dr, size=(8, k)).astype(np.int32),
                      rng.normal(size=(8, k)).astype(np.float32), dr)}
        ids = {"perMember": np.zeros(8, np.int32)}
        before = np.asarray(ladder.score_padded(off, shards, ids))
        token_before = ladder._qdev[0]
        other = copy.copy(ladder.store)
        other.fixed = {n: dataclasses.replace(
            b, weights=-np.asarray(b.weights))
            for n, b in ladder.store.fixed.items()}
        other.random = {n: dataclasses.replace(
            b, coefficients=-np.asarray(b.coefficients))
            for n, b in ladder.store.random.items()}
        other._device = None
        hot_swap(ladder.store, other, probe=None, root=None)
        after = np.asarray(ladder.score_padded(off, shards, ids))
        assert ladder._qdev[0] is not token_before  # cache turned over
        np.testing.assert_allclose(before + after, 1.0, atol=1e-6)
        assert ladder.assert_no_retrace() <= len(ladder.ladder)

    @pytest.mark.parametrize("B", [8, 16, 32, 64, 128, 256])
    @pytest.mark.parametrize("mode", ["int8", "bf16"])
    def test_rung_matches_float64_reference(self, mode, B):
        """Rung by rung over the default ladder 8…256: the margin a
        quantized rung returns is the float64 numpy margin over the
        DEQUANTIZED blocks (q·scale, or bf16 → f64), half of the batch
        scoring unseen entities (the zero row E).

        Tolerance: the reference reads the very blocks the rung reads, so
        what separates them is f32 arithmetic alone — one rounding for
        the dequantizing product, one per multiply, one per add of a
        ≤ (df + k)-term sum: per row (df + k + 2)·2^-23·Σ|x||w|. And the
        blocks themselves sit where quantization says: within half a
        step (scale / 2) of the f32 store, within 2^-8 relative for
        bf16."""
        ladder, (df, dr, k, E) = self._ladder(
            quantize=mode, max_batch=256, output_mean=False)
        assert ladder.ladder == (8, 16, 32, 64, 128, 256)
        rng = np.random.default_rng(100 + B)
        off = rng.normal(size=B).astype(np.float32)
        xg = rng.normal(size=(B, df)).astype(np.float32)
        ind = rng.integers(0, dr, size=(B, k)).astype(np.int32)
        val = rng.normal(size=(B, k)).astype(np.float32)
        eid = rng.integers(0, E, size=B).astype(np.int32)
        eid[::2] = E  # unseen: the cold-miss row
        got = np.asarray(ladder.score_padded(
            off, {"global": xg, "member": SparseRows(ind, val, dr)},
            {"perMember": eid}), np.float64)

        fixed_q, re_q = ladder._quant_blocks()
        w32 = np.asarray(ladder.store.fixed["fixed"].weights, np.float64)
        c32 = np.asarray(ladder.store.random["perMember"].coefficients,
                         np.float64)
        if mode == "int8":
            q, s = fixed_q["fixed"]
            w = np.asarray(q, np.float64) * float(s)
            q, s = re_q["perMember"]
            s = np.asarray(s, np.float64)
            C = np.asarray(q, np.float64) * s[:, None]
            assert np.all(np.abs(w - w32) <= 0.5 * float(fixed_q["fixed"][1])
                          * (1 + 1e-6))
            assert np.all(np.abs(C - c32) <= 0.5 * s[:, None] * (1 + 1e-6))
        else:
            w = np.asarray(fixed_q["fixed"]).astype(np.float64)
            C = np.asarray(re_q["perMember"]).astype(np.float64)
            assert np.all(np.abs(w - w32) <= 2.0 ** -8 * np.abs(w32))
            assert np.all(np.abs(C - c32) <= 2.0 ** -8 * np.abs(c32))
        assert not C[E].any()  # the zero row survives quantization
        rows = C[eid]                                        # (B, dr)
        picked = np.take_along_axis(rows, ind.astype(np.int64), axis=1)
        want = (off.astype(np.float64) + xg.astype(np.float64) @ w
                + np.sum(val.astype(np.float64) * picked, axis=1))
        mass = (np.abs(off) + np.abs(xg).astype(np.float64) @ np.abs(w)
                + np.sum(np.abs(val) * np.abs(picked), axis=1))
        tol = (df + k + 2) * 2.0 ** -23 * mass
        assert np.all(np.abs(got - want) <= tol), \
            float(np.max(np.abs(got - want) / tol))

    @pytest.mark.parametrize("quantize,keys", [
        (None, ["serving/m@B8", "serving/m@B256"]),
        ("int8", ["serving/m:int8@B8", "serving/m:int8@B256"]),
        ("bf16", ["serving/m:bf16@B8", "serving/m:bf16@B256"])],
        ids=["f32", "int8", "bf16"])
    def test_aot_key_is_stable(self, quantize, keys):
        """The AOT store's file identity, as LITERAL strings: an export
        stored by an earlier checkout is found by this one (the key never
        carried anything but the model tag, the quantization and the
        rung)."""
        ladder, _ = self._ladder(quantize=quantize, max_batch=256,
                                 model_tag="m")
        assert [ladder._key(b) for b in (8, 256)] == keys


def test_selftest_cli_end_to_end():
    """`python -m photon_tpu.serving --selftest --json` — the CI smoke
    face of this whole module — exits 0 with every check ok."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the CLI must self-provision its platform
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "photon_tpu.serving", "--selftest", "--json"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"] is True
    assert all(v == "ok" for v in report["checks"].values())
