"""Driver end-to-end tests: Avro files → trained model dir → scored output
(SURVEY.md §4 'driver end-to-end from Avro files to scored output')."""
import json

import numpy as np
import pytest

from photon_tpu.data.avro_io import read_avro, write_avro
from photon_tpu.data.ingest import training_example_schema
from photon_tpu.drivers import (
    CoordinateSpec,
    ScoringParams,
    TrainingParams,
    run_scoring,
    run_training,
)
from photon_tpu.utils.timing import PhaseTimers, Timer


def _write_game_avro(path, n, seed=0, n_users=8):
    rng = np.random.default_rng(seed)
    user = rng.integers(0, n_users, n)
    age = rng.normal(0, 1, n)
    ctr = rng.normal(0, 1, n)
    u_eff = np.linspace(-1.5, 1.5, n_users)[np.argsort(rng.uniform(size=n_users))]
    margin = 1.2 * age - 0.8 * ctr + u_eff[user]
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    schema = training_example_schema(
        feature_bags=("global", "puser"), entity_fields=("userId",))
    records = [{
        "response": float(y[i]),
        "offset": None, "weight": None, "uid": f"row{i}",
        "userId": f"u{user[i]}",
        "global": [
            {"name": "age", "term": "", "value": float(age[i])},
            {"name": "ctr", "term": "", "value": float(ctr[i])},
        ],
        "puser": [{"name": "bias", "term": "", "value": 1.0}],
    } for i in range(n)]
    write_avro(path, records, schema)
    return y


FEATURE_SHARDS = {
    "fixedShard": {"bags": ["global"], "has_intercept": True},
    "userShard": {"bags": ["puser"], "has_intercept": False},
}
from photon_tpu.data.feature_bags import FeatureShardConfig

FEATURE_SHARDS_TYPED = {
    k: FeatureShardConfig(bags=tuple(v["bags"]),
                          has_intercept=v["has_intercept"])
    for k, v in FEATURE_SHARDS.items()
}
COORDINATES = {
    "fixed": {"feature_shard": "fixedShard", "reg_type": "l2",
              "reg_weight": 0.5, "max_iters": 40},
    "perUser": {"feature_shard": "userShard", "entity_name": "userId",
                "reg_type": "l2", "reg_weight": 2.0, "max_iters": 20},
}


@pytest.fixture(scope="module")
def job_dirs(tmp_path_factory):
    root = tmp_path_factory.mktemp("game_job")
    y_train = _write_game_avro(root / "train.avro", 600, seed=1)
    y_val = _write_game_avro(root / "validation.avro", 300, seed=2)
    return root, y_train, y_val


class TestTrainingDriver:
    def test_end_to_end_with_grid(self, job_dirs):
        root, *_ = job_dirs
        params = TrainingParams(
            train_path=str(root / "train.avro"),
            validation_path=str(root / "validation.avro"),
            output_dir=str(root / "out"),
            feature_shards=FEATURE_SHARDS,
            coordinates={
                **COORDINATES,
                "fixed": {**COORDINATES["fixed"], "reg_weights": [0.1, 10.0]},
            },
            entity_fields=["userId"],
            n_sweeps=2,
        )
        out = run_training(params)
        assert len(out.results) == 2  # one model per grid point
        assert out.best.validation_score is not None
        assert out.best.validation_score > 0.7  # AUC on planted signal
        # model dir is loadable and complete
        from photon_tpu.data.model_io import load_game_model

        model, imaps = load_game_model(out.model_dir)
        assert set(model.names()) == {"fixed", "perUser"}
        assert "read" in out.timings and "train" in out.timings

    def test_compilation_cache_knob(self, job_dirs, tmp_path, monkeypatch):
        """The one placement rule (utils/compile_cache.py): "" turns the
        cache off; JAX_COMPILATION_CACHE_DIR beats any configured path;
        unset, an explicit path is honoured (relative → under
        output_dir) and the default is the fixed in-checkout dir."""
        import jax

        from photon_tpu.utils import compile_cache as cc

        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        assert cc.resolve_cache_dir(None, "/o") == cc.DEFAULT_CACHE_DIR
        assert cc.DEFAULT_CACHE_DIR.endswith(".jax_cache")
        assert cc.resolve_cache_dir("", "/o") is None
        assert cc.resolve_cache_dir("cc", "/o") == "/o/cc"
        assert cc.resolve_cache_dir("/abs/cc", "/o") == "/abs/cc"
        monkeypatch.setenv(cc.ENV_VAR, "/env/cc")
        assert cc.resolve_cache_dir(None, "/o") == "/env/cc"
        assert cc.resolve_cache_dir("/abs/cc", "/o") == "/env/cc"
        assert cc.resolve_cache_dir("", "/o") is None

        root, *_ = job_dirs
        out_dir = tmp_path / "cache_job"
        params = TrainingParams(
            train_path=str(root / "train.avro"),
            output_dir=str(out_dir),
            feature_shards=FEATURE_SHARDS,
            coordinates={"fixed": COORDINATES["fixed"]},
            entity_fields=["userId"],
            n_sweeps=1,
            compilation_cache_dir="ignored_under_env",
        )
        env_dir = tmp_path / "env_cache"
        monkeypatch.setenv(cc.ENV_VAR, str(env_dir))
        prev = jax.config.jax_compilation_cache_dir
        prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            run_training(params)
            assert jax.config.jax_compilation_cache_dir == str(env_dir)
            assert env_dir.is_dir()
            assert not (out_dir / "ignored_under_env").exists()
        finally:  # both knobs: the rest of the session must not keep
            # persisting every compile into a deleted tmpdir
            jax.config.update("jax_compilation_cache_dir", prev)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              prev_min)

    def test_scoring_driver_round_trip(self, job_dirs):
        root, _, y_val = job_dirs
        params = TrainingParams(
            train_path=str(root / "train.avro"),
            validation_path=str(root / "validation.avro"),
            output_dir=str(root / "out2"),
            feature_shards=FEATURE_SHARDS,
            coordinates=COORDINATES,
            entity_fields=["userId"],
            n_sweeps=1,
        )
        tr = run_training(params)
        sc = run_scoring(ScoringParams(
            model_dir=tr.model_dir,
            data_path=str(root / "validation.avro"),
            output_dir=str(root / "scored"),
            feature_shards=FEATURE_SHARDS,
            entity_fields=["userId"],
        ))
        assert sc.metric == pytest.approx(tr.best.validation_score, abs=1e-6)
        written = read_avro(sc.output_path)
        assert len(written) == 300
        assert written[0]["uid"] == "row0"
        probs = np.asarray([r["predictionScore"] for r in written])
        assert ((probs > 0) & (probs < 1)).all()  # sigmoid applied
        np.testing.assert_allclose(
            [r["label"] for r in written], y_val, atol=1e-6)

    def test_normalization_and_downsampling_modes(self, job_dirs, tmp_path):
        root, *_ = job_dirs
        params = TrainingParams(
            train_path=str(root / "train.avro"),
            validation_path=str(root / "validation.avro"),
            output_dir=str(tmp_path / "out_norm"),
            feature_shards=FEATURE_SHARDS,
            coordinates=COORDINATES,
            entity_fields=["userId"],
            n_sweeps=1,
            normalization="scale_with_standard_deviation",
            down_sampling_rate=0.5,
        )
        out = run_training(params)
        assert out.best.validation_score > 0.65

    def test_cli_json_config(self, job_dirs, tmp_path, capsys):
        root, *_ = job_dirs
        cfg = {
            "train_path": str(root / "train.avro"),
            "validation_path": str(root / "validation.avro"),
            "output_dir": str(tmp_path / "cli_out"),
            "feature_shards": FEATURE_SHARDS,
            "coordinates": COORDINATES,
            "entity_fields": ["userId"],
            "n_sweeps": 1,
        }
        cfg_path = tmp_path / "job.json"
        cfg_path.write_text(json.dumps(cfg))
        from photon_tpu.drivers.train import main

        main(["--config", str(cfg_path)])
        printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert printed["n_models"] == 1
        assert printed["validation_score"] > 0.65

    def test_gp_tuning_mode(self, job_dirs, tmp_path):
        root, *_ = job_dirs
        params = TrainingParams(
            train_path=str(root / "train.avro"),
            validation_path=str(root / "validation.avro"),
            output_dir=str(tmp_path / "out_tune"),
            feature_shards=FEATURE_SHARDS,
            coordinates=COORDINATES,
            entity_fields=["userId"],
            n_sweeps=1,
            tuning_iters=4,
            tuning_range=(1e-3, 1e3),
        )
        out = run_training(params)
        assert len(out.results) == 4  # one fit per tuner evaluation
        assert out.best.validation_score == pytest.approx(
            max(r.validation_score for r in out.results))


class TestTimers:
    def test_timer_accumulates(self):
        t = Timer()
        with t:
            pass
        first = t.seconds
        with t:
            pass
        assert t.seconds >= first
        with pytest.raises(RuntimeError):
            t.stop()

    def test_phase_timers(self):
        timers = PhaseTimers()
        with timers("a"):
            pass
        with timers("a"):
            pass
        with timers("b"):
            pass
        s = timers.summary()
        assert set(s) == {"a", "b"} and s["a"] >= 0


class TestSummarization:
    def test_driver_writes_feature_summaries(self, job_dirs):
        from photon_tpu.data.statistics import FeatureSummary

        root, *_ = job_dirs
        params = TrainingParams(
            train_path=str(root / "train.avro"),
            output_dir=str(root / "out_summ"),
            feature_shards=FEATURE_SHARDS,
            coordinates=COORDINATES,
            entity_fields=["userId"],
            n_sweeps=1,
            normalization="scale_with_standard_deviation",
            summarization_output_dir="summaries",
        )
        out = run_training(params)
        assert out.best is not None
        for shard in FEATURE_SHARDS:
            s = FeatureSummary.load(
                str(root / "out_summ" / "summaries" / f"{shard}.json"))
            assert s.count == 600
        s_fixed = FeatureSummary.load(
            str(root / "out_summ" / "summaries" / "fixedShard.json"))
        # age/ctr are standard normal draws; intercept column is constant 1
        assert abs(float(s_fixed.mean[-1]) - 1.0) < 1e-6
        assert float(s_fixed.variance[-1]) < 1e-8
        assert 0.7 < float(s_fixed.std[0]) < 1.3


class TestOutputModeAll:
    def test_all_models_saved_with_manifest(self, job_dirs):
        import json as _json

        from photon_tpu.data.model_io import load_game_model

        root, *_ = job_dirs
        params = TrainingParams(
            train_path=str(root / "train.avro"),
            validation_path=str(root / "validation.avro"),
            output_dir=str(root / "out_all"),
            feature_shards=FEATURE_SHARDS,
            coordinates={
                **COORDINATES,
                "fixed": {**COORDINATES["fixed"],
                          "reg_weights": [0.1, 10.0]},
            },
            entity_fields=["userId"],
            n_sweeps=1,
            output_mode="ALL",
        )
        out = run_training(params)
        with open(root / "out_all" / "models" / "models.json") as fh:
            manifest = _json.load(fh)
        assert len(manifest) == 2
        assert sum(1 for m in manifest if m["best"]) == 1
        regs = [m["reg_weights"]["fixed"] for m in manifest]
        assert sorted(regs) == [0.1, 10.0]
        for m in manifest:
            gm, _ = load_game_model(m["dir"])
            assert set(gm.names()) == {"fixed", "perUser"}
            assert m["validation_score"] is not None

    def test_bad_output_mode_rejected(self, job_dirs):
        # fails fast at construction, before any training runs
        root, *_ = job_dirs
        with pytest.raises(ValueError, match="BEST or ALL"):
            TrainingParams(
                train_path=str(root / "train.avro"),
                output_dir=str(root / "out_bad"),
                feature_shards=FEATURE_SHARDS,
                coordinates=COORDINATES,
                entity_fields=["userId"],
                n_sweeps=1,
                output_mode="SOME",
            )


class TestMultipleEvaluators:
    def test_selection_and_reporting(self, job_dirs):
        root, *_ = job_dirs
        params = TrainingParams(
            train_path=str(root / "train.avro"),
            validation_path=str(root / "validation.avro"),
            output_dir=str(root / "out_ev"),
            feature_shards=FEATURE_SHARDS,
            coordinates={
                **COORDINATES,
                "fixed": {**COORDINATES["fixed"], "reg_weights": [0.1, 100.0]},
            },
            entity_fields=["userId"],
            n_sweeps=1,
            evaluators=["logistic_loss", "AUC", "precision@5",
                        "sharded_auc"],
            evaluator_entity="userId",
        )
        out = run_training(params)
        # selection ran on LOGISTIC_LOSS (lower is better)
        losses = [r.validation_score for r in out.results]
        assert out.best.validation_score == min(losses)
        m = out.validation_metrics
        assert set(m) == {"LOGISTIC_LOSS", "AUC", "PRECISION_AT_K@5",
                          "SHARDED_AUC"}
        assert m["LOGISTIC_LOSS"] == pytest.approx(out.best.validation_score)
        assert 0.5 < m["AUC"] <= 1.0
        assert 0.0 <= m["PRECISION_AT_K@5"] <= 1.0

    def test_parse_evaluator_specs(self):
        from photon_tpu.evaluation.evaluator import (
            EvaluatorType, evaluator_name, parse_evaluator)

        ev = parse_evaluator("precision@3")
        assert ev.kind is EvaluatorType.PRECISION_AT_K and ev.k == 3
        assert evaluator_name(ev) == "PRECISION_AT_K@3"
        assert parse_evaluator("rmse").kind is EvaluatorType.RMSE
        with pytest.raises(ValueError, match="unknown evaluator"):
            parse_evaluator("nope")

    def test_scoring_driver_multiple_evaluators(self, job_dirs):
        root, *_ = job_dirs
        tr = run_training(TrainingParams(
            train_path=str(root / "train.avro"),
            output_dir=str(root / "out_sc_ev"),
            feature_shards=FEATURE_SHARDS,
            coordinates=COORDINATES,
            entity_fields=["userId"],
            n_sweeps=1,
        ))
        sc = run_scoring(ScoringParams(
            model_dir=tr.model_dir,
            data_path=str(root / "validation.avro"),
            output_dir=str(root / "scored_ev"),
            feature_shards=FEATURE_SHARDS,
            entity_fields=["userId"],
            evaluators=["AUC", "logistic_loss", "sharded_auc"],
        ))
        assert set(sc.metrics) == {"AUC", "LOGISTIC_LOSS", "SHARDED_AUC"}
        assert sc.metric == pytest.approx(sc.metrics["AUC"])
        assert 0.5 < sc.metrics["AUC"] <= 1.0

    def test_metric_none_when_first_evaluator_skipped(self, job_dirs,
                                                      tmp_path):
        """ScoringOutput.metric must honor the FIRST evaluator, not fall
        back to a different metric's value (regression)."""
        root, *_ = job_dirs
        tr = run_training(TrainingParams(
            train_path=str(root / "train.avro"),
            output_dir=str(tmp_path / "o"),
            feature_shards=FEATURE_SHARDS,
            coordinates=COORDINATES,
            entity_fields=["userId"],
            n_sweeps=1,
        ))
        sc = run_scoring(ScoringParams(
            model_dir=tr.model_dir,
            data_path=str(root / "validation.avro"),
            output_dir=str(tmp_path / "s"),
            feature_shards=FEATURE_SHARDS,
            entity_fields=["userId"],
            evaluators=["sharded_auc", "AUC"],
            evaluator_entity="missingEntity",
        ))
        assert sc.metric is None  # first evaluator was skipped
        assert set(sc.metrics) == {"AUC"}

    def test_bad_evaluator_k_suffix_rejected(self):
        from photon_tpu.evaluation.evaluator import parse_evaluator

        with pytest.raises(ValueError, match="only applies to the precision"):
            parse_evaluator("AUC@5")

    def test_sharded_extra_metric_never_destroys_run(self, job_dirs,
                                                     tmp_path):
        """A sharded EXTRA evaluator with no usable entity must be skipped
        with a warning after training, not crash before the save
        (regression)."""
        import os

        root, *_ = job_dirs
        out = run_training(TrainingParams(
            train_path=str(root / "train.avro"),
            validation_path=str(root / "validation.avro"),
            output_dir=str(tmp_path / "o"),
            feature_shards={"fixedShard": FEATURE_SHARDS["fixedShard"]},
            coordinates={"fixed": COORDINATES["fixed"]},  # no random effect
            entity_fields=[],
            n_sweeps=1,
            evaluators=["AUC", "sharded_auc"],
        ))
        assert os.path.isdir(out.model_dir)  # model was saved
        assert set(out.validation_metrics) == {"AUC"}  # sharded skipped


class TestIndexingDriver:
    def test_build_save_and_reuse(self, job_dirs, tmp_path):
        from photon_tpu.data.ingest import GameDataConfig, read_game_data
        from photon_tpu.drivers import (IndexingParams, load_index_maps,
                                        run_indexing)

        root, *_ = job_dirs
        out = run_indexing(IndexingParams(
            data_path=str(root / "train.avro"),
            output_dir=str(tmp_path / "maps"),
            feature_shards=FEATURE_SHARDS,
        ))
        assert out.n_records == 600
        # fixedShard: age + ctr + intercept
        assert out.sizes["fixedShard"] == 3
        maps = load_index_maps(out.map_paths)
        assert maps["fixedShard"].frozen
        assert maps["fixedShard"].intercept_id == 2  # intercept LAST
        # ingestion with the prebuilt maps matches implicit ingestion
        cfg = GameDataConfig(shards=FEATURE_SHARDS_TYPED,
                             entity_fields=("userId",))
        d1, implicit = read_game_data(str(root / "train.avro"), cfg)
        d2, _ = read_game_data(str(root / "train.avro"), cfg,
                               index_maps=maps)
        np.testing.assert_array_equal(
            np.asarray(d1.shards["fixedShard"]),
            np.asarray(d2.shards["fixedShard"]))

    def test_min_count_prunes_rare_features(self, tmp_path):
        from photon_tpu.data.ingest import training_example_schema
        from photon_tpu.drivers import IndexingParams, run_indexing

        schema = training_example_schema(feature_bags=("g",),
                                         entity_fields=())
        recs = []
        for i in range(20):
            feats = [{"name": "common", "term": "", "value": 1.0}]
            if i == 0:
                feats.append({"name": "rare", "term": "", "value": 1.0})
            recs.append({"response": 1.0, "offset": None, "weight": None,
                         "uid": str(i), "g": feats})
        write_avro(str(tmp_path / "d.avro"), recs, schema)
        out = run_indexing(IndexingParams(
            data_path=str(tmp_path / "d.avro"),
            output_dir=str(tmp_path / "maps"),
            feature_shards={"s": {"bags": ["g"], "has_intercept": False}},
            min_count=2,
        ))
        assert out.sizes["s"] == 1  # only "common" survives

    def test_cli(self, job_dirs, tmp_path, capsys):
        cfg = {
            "data_path": str(job_dirs[0] / "train.avro"),
            "output_dir": str(tmp_path / "m"),
            "feature_shards": FEATURE_SHARDS,
        }
        p = tmp_path / "job.json"
        p.write_text(json.dumps(cfg))
        from photon_tpu.drivers.index import main

        main(["--config", str(p)])
        printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert printed["sizes"]["fixedShard"] == 3

    def test_training_driver_consumes_prebuilt_maps(self, tmp_path):
        """index_map_dir: min_count pruning must carry through to the
        trained model's feature space (the offline job's purpose)."""
        from photon_tpu.data.ingest import training_example_schema
        from photon_tpu.drivers import IndexingParams, run_indexing

        schema = training_example_schema(feature_bags=("g",),
                                         entity_fields=())
        rng = np.random.default_rng(0)
        recs = []
        for i in range(120):
            feats = [{"name": "a", "term": "", "value": float(rng.normal())},
                     {"name": "b", "term": "", "value": float(rng.normal())}]
            if i == 0:
                feats.append({"name": "rare", "term": "", "value": 1.0})
            recs.append({"response": float(rng.integers(0, 2)),
                         "offset": None, "weight": None, "uid": str(i),
                         "g": feats})
        write_avro(str(tmp_path / "d.avro"), recs, schema)
        shards = {"s": {"bags": ["g"], "has_intercept": True}}
        idx = run_indexing(IndexingParams(
            data_path=str(tmp_path / "d.avro"),
            output_dir=str(tmp_path / "maps"),
            feature_shards=shards, min_count=2))
        assert idx.sizes["s"] == 3  # a, b, intercept — rare pruned
        out = run_training(TrainingParams(
            train_path=str(tmp_path / "d.avro"),
            output_dir=str(tmp_path / "out"),
            feature_shards=shards,
            coordinates={"fixed": {"feature_shard": "s", "reg_type": "l2",
                                   "reg_weight": 1.0, "max_iters": 15}},
            n_sweeps=1,
            index_map_dir=str(tmp_path / "maps")))
        w = np.asarray(out.best.model.coordinates["fixed"]
                       .model.coefficients.means)
        assert w.shape == (3,)  # pruned width, not 4
        with pytest.raises(FileNotFoundError, match="no map for shard"):
            run_training(TrainingParams(
                train_path=str(tmp_path / "d.avro"),
                output_dir=str(tmp_path / "out2"),
                feature_shards={"other": {"bags": ["g"]}},
                coordinates={"fixed": {"feature_shard": "other",
                                       "max_iters": 2}},
                n_sweeps=1,
                index_map_dir=str(tmp_path / "maps")))


class TestProfiling:
    def test_trace_writes_profile(self, tmp_path):
        import os

        import jax.numpy as jnp

        from photon_tpu import telemetry

        with telemetry.run("profiled"), \
                telemetry.device_trace(str(tmp_path)):
            with telemetry.span("solve.tiny-matmul"):  # a TraceAnnotation
                x = jnp.ones((64, 64))
                (x @ x).block_until_ready()
        found = []
        for base, _, files in os.walk(tmp_path):
            found += [f for f in files if f.endswith((".pb", ".json.gz",
                                                      ".xplane.pb"))]
        assert found, "profiler trace produced no files"


class TestResume:
    def test_resume_skips_completed_points(self, job_dirs, tmp_path):
        root, *_ = job_dirs

        def make(resume):
            return TrainingParams(
                train_path=str(root / "train.avro"),
                validation_path=str(root / "validation.avro"),
                output_dir=str(tmp_path / "out"),
                feature_shards=FEATURE_SHARDS,
                coordinates={
                    **COORDINATES,
                    "fixed": {**COORDINATES["fixed"],
                              "reg_weights": [0.1, 10.0]},
                },
                entity_fields=["userId"],
                n_sweeps=1,
                output_mode="ALL",
                resume=resume,
            )

        first = run_training(make(resume=False))
        assert first.n_resumed == 0
        second = run_training(make(resume=True))
        assert second.n_resumed == 2  # both points loaded, nothing retrained
        for a, b in zip(first.results, second.results):
            assert b.validation_score == pytest.approx(a.validation_score)
            wa = np.asarray(
                a.model.coordinates["fixed"].model.coefficients.means)
            wb = np.asarray(
                b.model.coordinates["fixed"].model.coefficients.means)
            np.testing.assert_allclose(wb, wa, atol=1e-6)
        assert (second.best.configs["fixed"].optimizer.reg_weight
                == first.best.configs["fixed"].optimizer.reg_weight)

    def test_resume_trains_only_missing_points(self, job_dirs, tmp_path):
        import shutil

        root, *_ = job_dirs

        def make(weights, resume):
            return TrainingParams(
                train_path=str(root / "train.avro"),
                validation_path=str(root / "validation.avro"),
                output_dir=str(tmp_path / "out"),
                feature_shards=FEATURE_SHARDS,
                coordinates={
                    **COORDINATES,
                    "fixed": {**COORDINATES["fixed"],
                              "reg_weights": weights},
                },
                entity_fields=["userId"],
                n_sweeps=1,
                output_mode="ALL",
                resume=resume,
            )

        run_training(make([0.1], resume=False))
        # widen the grid; the 0.1 point must load, 10.0 must train fresh
        out = run_training(make([0.1, 10.0], resume=True))
        assert out.n_resumed == 1
        assert len(out.results) == 2
        regs = [r.configs["fixed"].optimizer.reg_weight for r in out.results]
        assert regs == [0.1, 10.0]

    def test_resume_requires_all_mode(self, job_dirs):
        root, *_ = job_dirs
        with pytest.raises(ValueError, match="output_mode=ALL"):
            TrainingParams(
                train_path=str(root / "train.avro"),
                output_dir="x",
                feature_shards=FEATURE_SHARDS,
                coordinates=COORDINATES,
                resume=True,
            )

    def test_died_job_resumes_from_checkpoints(self, job_dirs, tmp_path,
                                               monkeypatch):
        """Crash mid-grid: completed points were checkpointed as they
        finished, so the rerun retrains only the rest (regression: nothing
        was persisted until the whole grid succeeded)."""
        from photon_tpu.game.estimator import GameEstimator

        root, *_ = job_dirs

        def make():
            return TrainingParams(
                train_path=str(root / "train.avro"),
                validation_path=str(root / "validation.avro"),
                output_dir=str(tmp_path / "out"),
                feature_shards=FEATURE_SHARDS,
                coordinates={
                    **COORDINATES,
                    "fixed": {**COORDINATES["fixed"],
                              "reg_weights": [0.1, 1.0, 10.0]},
                },
                entity_fields=["userId"],
                n_sweeps=1, output_mode="ALL", resume=True,
            )

        real_fit = GameEstimator.fit
        calls = {"n": 0}

        def dying_fit(self, *a, **kw):
            calls["n"] += 1
            if calls["n"] == 3:  # die while training the third point
                raise RuntimeError("simulated preemption")
            return real_fit(self, *a, **kw)

        monkeypatch.setattr(GameEstimator, "fit", dying_fit)
        with pytest.raises(RuntimeError, match="preemption"):
            run_training(make())
        monkeypatch.setattr(GameEstimator, "fit", real_fit)
        out = run_training(make())
        assert out.n_resumed == 2  # the two checkpointed points loaded
        assert len(out.results) == 3

    def test_changed_config_is_not_resumed(self, job_dirs, tmp_path):
        """Any hyperparameter change invalidates the checkpoint (regression:
        matching on reg weights alone reloaded stale models)."""
        root, *_ = job_dirs

        def make(max_iters):
            return TrainingParams(
                train_path=str(root / "train.avro"),
                validation_path=str(root / "validation.avro"),
                output_dir=str(tmp_path / "out"),
                feature_shards=FEATURE_SHARDS,
                coordinates={
                    **COORDINATES,
                    "fixed": {**COORDINATES["fixed"],
                              "max_iters": max_iters,
                              "reg_weights": [0.1, 10.0]},
                },
                entity_fields=["userId"],
                n_sweeps=1, output_mode="ALL", resume=True,
            )

        run_training(make(max_iters=40))
        out = run_training(make(max_iters=41))
        assert out.n_resumed == 0  # different config signature → retrain

    def test_resume_objective_selection_without_validation(self, job_dirs,
                                                           tmp_path):
        """Loaded points carry their recorded training objective, so
        best-by-objective selection survives a resume (regression: empty
        history compared as +inf)."""
        root, *_ = job_dirs

        def make():
            return TrainingParams(
                train_path=str(root / "train.avro"),
                output_dir=str(tmp_path / "out"),
                feature_shards=FEATURE_SHARDS,
                coordinates={
                    **COORDINATES,
                    "fixed": {**COORDINATES["fixed"],
                              "reg_weights": [0.1, 1000.0]},
                },
                entity_fields=["userId"],
                n_sweeps=1, output_mode="ALL", resume=True,
            )

        first = run_training(make())
        second = run_training(make())
        assert second.n_resumed == 2
        assert (second.best.configs["fixed"].optimizer.reg_weight
                == first.best.configs["fixed"].optimizer.reg_weight)

    def test_resume_rejects_incremental(self, job_dirs):
        root, *_ = job_dirs
        with pytest.raises(ValueError, match="incremental"):
            TrainingParams(
                train_path=str(root / "train.avro"),
                output_dir="x", feature_shards=FEATURE_SHARDS,
                coordinates=COORDINATES, output_mode="ALL", resume=True,
                incremental_coordinates=["fixed"],
                initial_model_dir="y")

    def test_global_config_change_is_not_resumed(self, job_dirs, tmp_path):
        """Changing a training-wide knob (n_sweeps here) must invalidate
        every checkpoint (regression: signature covered only per-coordinate
        settings, so stale models were silently reloaded)."""
        root, *_ = job_dirs

        def make(n_sweeps):
            return TrainingParams(
                train_path=str(root / "train.avro"),
                validation_path=str(root / "validation.avro"),
                output_dir=str(tmp_path / "out"),
                feature_shards=FEATURE_SHARDS,
                coordinates={
                    **COORDINATES,
                    "fixed": {**COORDINATES["fixed"],
                              "reg_weights": [0.1, 10.0]},
                },
                entity_fields=["userId"],
                n_sweeps=n_sweeps, output_mode="ALL", resume=True,
            )

        run_training(make(n_sweeps=1))
        out = run_training(make(n_sweeps=2))
        assert out.n_resumed == 0
        # and same-config rerun still resumes fully
        out2 = run_training(make(n_sweeps=2))
        assert out2.n_resumed == 2

    def test_changed_validation_is_not_resumed(self, job_dirs, tmp_path):
        """Resume must not reuse stored validation_scores when the
        validation data or selection metric changed — the scores would be
        incomparable to freshly trained points' scores and silently
        corrupt best-model selection (regression: signature omitted
        validation_path/evaluators)."""
        root, *_ = job_dirs
        other_val = tmp_path / "validation2.avro"
        _write_game_avro(other_val, 300, seed=7)

        def make(validation_path, evaluators=()):
            return TrainingParams(
                train_path=str(root / "train.avro"),
                validation_path=str(validation_path),
                output_dir=str(tmp_path / "out"),
                feature_shards=FEATURE_SHARDS,
                coordinates={
                    **COORDINATES,
                    "fixed": {**COORDINATES["fixed"],
                              "reg_weights": [0.1, 10.0]},
                },
                entity_fields=["userId"],
                n_sweeps=1, output_mode="ALL", resume=True,
                evaluators=evaluators,
            )

        run_training(make(root / "validation.avro"))
        out = run_training(make(other_val))
        assert out.n_resumed == 0  # different validation data → retrain
        # changing the selection metric also invalidates the checkpoints
        out2 = run_training(make(other_val, evaluators=("RMSE",)))
        assert out2.n_resumed == 0
        # unchanged rerun still resumes fully
        out3 = run_training(make(other_val, evaluators=("RMSE",)))
        assert out3.n_resumed == 2

    def test_all_mode_overwrites_stale_point_dirs(self, job_dirs, tmp_path):
        """A non-resume ALL run into a reused output_dir must overwrite
        existing signature-keyed dirs: the signature keys on train_path,
        not file content, so an existing dir may hold a stale model
        (regression: the save phase skipped any dir that existed)."""
        import shutil

        from photon_tpu.data.model_io import load_game_model

        root, *_ = job_dirs

        def make():
            return TrainingParams(
                train_path=str(root / "train.avro"),
                validation_path=str(root / "validation.avro"),
                output_dir=str(tmp_path / "out"),
                feature_shards=FEATURE_SHARDS,
                coordinates={
                    **COORDINATES,
                    "fixed": {**COORDINATES["fixed"],
                              "reg_weights": [0.1, 10.0]},
                },
                entity_fields=["userId"],
                n_sweeps=1, output_mode="ALL",
            )

        run_training(make())
        models_dir = tmp_path / "out" / "models"
        with open(models_dir / "models.json") as fh:
            manifest = json.load(fh)
        # tamper: swap one point's on-disk model for the other's, the
        # observable effect of train_path's content having changed
        a, b = (m["dir"] for m in manifest[:2])
        shutil.rmtree(a)
        shutil.copytree(b, a)
        out = run_training(make())
        for r, m in zip(out.results, manifest):
            on_disk, _ = load_game_model(m["dir"])
            want = np.asarray(
                r.model.coordinates["fixed"].model.coefficients.means)
            got = np.asarray(
                on_disk.coordinates["fixed"].model.coefficients.means)
            np.testing.assert_allclose(got, want, atol=1e-6)

    def test_duplicate_grid_points_get_distinct_dirs(self, job_dirs,
                                                     tmp_path):
        """Two identical grid points train different models under warm
        starts (different warm-start chains); their signatures must not
        collide on one models/m_<hash>/ dir (regression: the second save
        overwrote the first, and resume handed both points one model)."""
        from photon_tpu.data.model_io import load_game_model

        root, *_ = job_dirs

        def make(resume):
            return TrainingParams(
                train_path=str(root / "train.avro"),
                validation_path=str(root / "validation.avro"),
                output_dir=str(tmp_path / "out"),
                feature_shards=FEATURE_SHARDS,
                coordinates={
                    **COORDINATES,
                    "fixed": {**COORDINATES["fixed"],
                              "reg_weights": [0.1, 0.1]},
                },
                entity_fields=["userId"],
                n_sweeps=1, output_mode="ALL", resume=resume,
            )

        first = run_training(make(resume=False))
        models_dir = tmp_path / "out" / "models"
        with open(models_dir / "models.json") as fh:
            manifest = json.load(fh)
        assert len({m["dir"] for m in manifest}) == 2
        for r, m in zip(first.results, manifest):
            on_disk, _ = load_game_model(m["dir"])
            np.testing.assert_allclose(
                np.asarray(
                    on_disk.coordinates["fixed"].model.coefficients.means),
                np.asarray(
                    r.model.coordinates["fixed"].model.coefficients.means),
                atol=1e-6)
        # and a resumed rerun recovers BOTH points
        second = run_training(make(resume=True))
        assert second.n_resumed == 2
