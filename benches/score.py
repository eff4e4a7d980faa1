"""Scoring-driver throughput: chunked native-decode → device score →
vectorized ScoredItemAvro write, vs the native ingest decode rate
(VERDICT r3 item 2's target: scoring within ~2x of native ingest rec/s).

Run: python benches/score.py [--rows 200000]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import tempfile
import time

import numpy as np

def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=200_000)
    p.add_argument("--bag-nnz", type=int, default=12)
    p.add_argument("--codec", default="deflate")
    args = p.parse_args()

    from photon_tpu.data.avro_io import write_avro
    from photon_tpu.data.ingest import (
        GameDataConfig,
        read_game_data,
        training_example_schema,
    )
    from photon_tpu.data.feature_bags import FeatureShardConfig
    from photon_tpu.drivers import (
        ScoringParams, TrainingParams, run_scoring, run_training,
    )

    rng = np.random.default_rng(0)
    n, k = args.rows, args.bag_nnz
    root = tempfile.mkdtemp(prefix="score_bench_")
    schema = training_example_schema(feature_bags=("features",),
                                     entity_fields=("memberId",))

    def gen(path, rows, seed):
        r = np.random.default_rng(seed)
        names = [f"f{j}" for j in range(5000)]
        recs = [{
            "response": float(r.integers(0, 2)),
            "offset": None, "weight": None, "uid": f"uid_{seed}_{i}",
            "memberId": f"m{r.integers(0, 1000)}",
            "features": [
                {"name": names[int(v)], "term": "",
                 "value": float(r.normal())}
                for v in r.integers(0, 5000, size=k)
            ],
        } for i in range(rows)]
        write_avro(path, recs, schema)

    train_path = os.path.join(root, "train.avro")
    gen(train_path, 4000, 1)
    shards = {"all": FeatureShardConfig(bags=("features",))}
    model_out = os.path.join(root, "model")
    run_training(TrainingParams(
        train_path=train_path, output_dir=model_out,
        feature_shards={"all": {"bags": ["features"]}},
        coordinates={"fixed": {"feature_shard": "all", "reg_type": "l2",
                               "reg_weight": 1.0, "max_iters": 10}},
        sparse_k=k + 1, data_validation="disabled"))

    data_path = os.path.join(root, "score_data")
    os.makedirs(data_path)
    per_file = args.rows // 4
    for fi in range(4):
        gen(os.path.join(data_path, f"part-{fi}.avro"), per_file, 10 + fi)
    n = per_file * 4
    sz = sum(os.path.getsize(os.path.join(data_path, f))
             for f in os.listdir(data_path))
    print(f"scoring input: {n} records, {sz / 1e6:.1f} MB, 4 files")

    # reference point: raw native ingest decode of the same data
    cfg = GameDataConfig(shards=shards, entity_fields=("memberId",))
    t0 = time.perf_counter()
    read_game_data(data_path, cfg, use_native=True, sparse_k=k + 1)
    dt_ingest = time.perf_counter() - t0
    print(f"native ingest:   {n / dt_ingest:12.0f} rec/s  ({dt_ingest:.2f} s)")

    # Two passes: the first pays the per-shape XLA compiles (a fixed cost —
    # chunk heights quantize to a handful of shapes), the second is the
    # steady-state throughput a long job sees. Evaluators off in the timed
    # pass: the ingest reference decodes only, so compare like with like.
    for label in ("cold", "warm"):
        out_dir = os.path.join(root, f"scored_{label}")
        t0 = time.perf_counter()
        out = run_scoring(ScoringParams(
            model_dir=os.path.join(model_out, "best_model"),
            data_path=data_path, output_dir=out_dir,
            feature_shards={"all": {"bags": ["features"]}},
            entity_fields=["memberId"], uid_field="uid",
            sparse_k=k + 1, output_codec=args.codec,
            evaluators=["RMSE"]))
        dt_score = time.perf_counter() - t0
        assert out.scores.shape[0] == n
        print(f"scoring driver ({label}): {n / dt_score:10.0f} rec/s  "
              f"({dt_score:.2f} s, codec={args.codec})")
    print(f"scoring / ingest ratio (warm): {dt_ingest / dt_score:.2f}x "
          f"(>= 0.5 meets the 'within ~2x of ingest' bar)")


if __name__ == "__main__":
    main()
