"""Avro ingest throughput: C++ columnar decoder vs pure Python
(SURVEY.md §6's ingest numbers; reference: AvroDataReader on the JVM).

Run: python benches/ingest.py [--rows 20000]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import os
import tempfile
import time

import numpy as np

def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=20_000)
    p.add_argument("--bag-nnz", type=int, default=12)
    args = p.parse_args()

    from photon_tpu.data.avro_io import write_avro
    from photon_tpu.data.ingest import (
        GameDataConfig,
        read_game_data,
        training_example_schema,
    )
    from photon_tpu.data.feature_bags import FeatureShardConfig

    rng = np.random.default_rng(0)
    n, k = args.rows, args.bag_nnz
    schema = training_example_schema(feature_bags=("features",),
                                     entity_fields=("memberId",))
    records = [{
        "response": float(rng.integers(0, 2)),
        "offset": None, "weight": None, "uid": str(i),
        "memberId": f"m{rng.integers(0, 1000)}",
        "features": [
            {"name": f"f{rng.integers(0, 5000)}", "term": "",
             "value": float(rng.normal())}
            for _ in range(k)
        ],
    } for i in range(n)]
    path = os.path.join(tempfile.mkdtemp(), "bench.avro")
    write_avro(path, records, schema)
    print(f"wrote {n} records ({os.path.getsize(path) / 1e6:.1f} MB)")

    cfg = GameDataConfig(
        shards={"all": FeatureShardConfig(bags=("features",))},
        entity_fields=("memberId",),
    )
    for name, use_native in (("python", False), ("native C++", True)):
        t0 = time.perf_counter()
        data, _ = read_game_data(path, cfg, use_native=use_native)
        dt = time.perf_counter() - t0
        assert data.n == n
        print(f"{name:10s}: {dt:6.2f}s  ({n / dt:,.0f} rec/s)")

    # streaming (bounded-memory chunks) must hold the one-shot throughput
    from photon_tpu.data.streaming import (
        build_index_maps_streaming,
        iter_game_chunks,
    )

    maps = build_index_maps_streaming(path, cfg)
    for name, use_native in (("stream py", False), ("stream C++", True)):
        t0 = time.perf_counter()
        stream, chunks = iter_game_chunks(path, cfg, maps, chunk_rows=8192,
                                          sparse_k=args.bag_nnz + 1,  # + intercept
                                          use_native=use_native)
        total = sum(chunk.n for chunk in chunks)
        dt = time.perf_counter() - t0
        assert total == n
        print(f"{name:10s}: {dt:6.2f}s  ({n / dt:,.0f} rec/s; "
              f"peak arena {stream.peak_arena_bytes / 1e6:.1f} MB)")

    # Exotic-schema leg (VERDICT r3 item 3): extra fields the round-3
    # planner rejected — nested record, map, enum, wide union — now skip
    # natively via generic skip programs instead of dropping the whole job
    # to the pure-Python road.
    schema2 = dict(schema)
    schema2["fields"] = schema["fields"] + [
        {"name": "meta", "type": {"type": "record", "name": "Meta",
                                  "fields": [
                                      {"name": "a", "type": "long"},
                                      {"name": "b", "type": ["null",
                                                             "string",
                                                             "double"]}]}},
        {"name": "tags", "type": {"type": "map", "values": "string"}},
        {"name": "kind", "type": {"type": "enum", "name": "Kind",
                                  "symbols": ["A", "B"]}},
    ]
    recs2 = [dict(r, meta={"a": i, "b": None}, tags={"t": "v"},
                  kind="AB"[i % 2]) for i, r in enumerate(records)]
    path2 = os.path.join(os.path.dirname(path), "bench_exotic.avro")
    write_avro(path2, recs2, schema2)
    t0 = time.perf_counter()
    data, _ = read_game_data(path2, cfg, use_native=True)
    dt = time.perf_counter() - t0
    assert data.n == n
    print(f"exotic C++: {dt:6.2f}s  ({n / dt:,.0f} rec/s — schema the "
          "round-3 planner rejected, still native)")

    # Consumed-exotic leg (VERDICT r4 item 5): the CONSUMED columns
    # themselves in exotic shapes — union-wrapped bag, long-valued map
    # bag, 3-branch scalar union, wide entity union — previously one such
    # column dropped the whole job to the Python record decoder (~10x).
    ntv = {"type": "record", "name": "NTV3", "fields": [
        {"name": "name", "type": "string"},
        {"name": "term", "type": "string"},
        {"name": "value", "type": "double"}]}
    schema3 = {"type": "record", "name": "ConsumedExotic", "fields": [
        {"name": "response", "type": "double"},
        {"name": "offset", "type": ["null", "double"], "default": None},
        {"name": "weight", "type": ["null", "long", "string"],
         "default": None},
        {"name": "memberId",
         "type": ["null", "string", {"type": "array", "items": "int"}],
         "default": None},
        {"name": "features", "type": ["null", {"type": "array",
                                               "items": ntv}],
         "default": None},
        {"name": "ctx", "type": [{"type": "map", "values": "long"},
                                 "null"]},
    ]}
    recs3 = [{"response": r["response"], "offset": None,
              "weight": None if i % 3 else 2,
              "memberId": r["memberId"],
              "features": None if i % 13 == 7 else r["features"],
              "ctx": None if i % 5 == 2 else {"c1": i % 9, "c2": 3}}
             for i, r in enumerate(records)]
    cfg3 = GameDataConfig(
        shards={"all": FeatureShardConfig(bags=("features", "ctx"))},
        entity_fields=("memberId",),
        optional_entity_fields=("memberId",),
    )
    path3 = os.path.join(os.path.dirname(path), "bench_consumed.avro")
    write_avro(path3, recs3, schema3)
    for name, use_native in (("consumed py", False), ("consumed C++", True)):
        t0 = time.perf_counter()
        data, _ = read_game_data(path3, cfg3, use_native=use_native)
        dt = time.perf_counter() - t0
        assert data.n == n
        note = " — every consumed column exotic, still native" \
            if use_native else ""
        print(f"{name:12s}: {dt:6.2f}s  ({n / dt:,.0f} rec/s{note})")


if __name__ == "__main__":
    main()
