"""Round-14 ingest data plane: sharded worker-pool decode parity (ordered,
bit-identical, fault-degrading), the decode-once columnar chunk cache
(cold==cached bitwise, torn-commit fallback, CRC, key invalidation), the
blocked-ELL ladder cache, stall-driven prefetch, and plane-on/off solver
bit parity through the streamed GLM and the GAME training driver."""
import json
import os

import numpy as np
import pytest

from photon_tpu.checkpoint.faults import (FaultPlan, InjectedFault,
                                          fault_plan, record_sites)
from photon_tpu.data import chunk_cache as cc
from photon_tpu.data.avro_io import write_avro
from photon_tpu.data.feature_bags import FeatureShardConfig
from photon_tpu.data.ingest import GameDataConfig, training_example_schema
from photon_tpu.data.ingest_plane import (AdaptivePrefetch,
                                          chunk_blocked_ell_from_avro,
                                          iter_game_chunks_parallel,
                                          open_chunk_source,
                                          plan_chunk_tasks)
from photon_tpu.data.matrix import SparseRows
from photon_tpu.data.streaming import (iter_game_chunks, scan_ingest,
                                       scan_row_counts, stream_to_host)


def _write_files(root, n_files=3, rows_per_file=400, seed=0):
    """Multi-file GAME dataset: a dense bag, a wide (sparse) bag, an
    entity column, optional offset/weight — block_records=130 leaves a
    NON-DIVIDING tail block per file (400 = 130+130+130+10)."""
    rng = np.random.default_rng(seed)
    schema = training_example_schema(feature_bags=("f", "g"),
                                     entity_fields=("member",))
    os.makedirs(root, exist_ok=True)
    for fi in range(n_files):
        records = []
        for i in range(rows_per_file):
            f_bag = [{"name": "age", "term": "",
                      "value": float(rng.normal())},
                     {"name": "ctr", "term": "",
                      "value": float(rng.normal())}]
            g_bag = [{"name": f"id{int(v)}", "term": "t",
                      "value": float(rng.normal())}
                     for v in rng.integers(0, 500, size=3)]
            records.append({
                "response": float(rng.integers(0, 2)),
                "offset": float(rng.normal()) if i % 3 == 0 else None,
                "weight": 2.0 if i % 5 == 0 else None,
                "uid": f"r{fi}_{i}",
                "member": f"m{int(rng.integers(0, 37))}",
                "f": f_bag, "g": g_bag,
            })
        write_avro(root / f"part-{fi:03d}.avro", records, schema,
                   block_records=130)
    return root


def _config():
    return GameDataConfig(
        shards={
            "dense": FeatureShardConfig(bags=("f",), has_intercept=True),
            "wide": FeatureShardConfig(bags=("g",), has_intercept=False,
                                       dense_threshold=4),
        },
        entity_fields=("member",),
    )


def _chunks_equal(a, b):
    np.testing.assert_array_equal(a.y, b.y)
    np.testing.assert_array_equal(a.weights, b.weights)
    np.testing.assert_array_equal(a.offsets, b.offsets)
    for s, X in a.shards.items():
        Y = b.shards[s]
        if isinstance(X, SparseRows):
            np.testing.assert_array_equal(np.asarray(X.indices),
                                          np.asarray(Y.indices))
            np.testing.assert_array_equal(np.asarray(X.values),
                                          np.asarray(Y.values))
        else:
            np.testing.assert_array_equal(np.asarray(X), np.asarray(Y))
    for e, col in a.entity_ids.items():
        np.testing.assert_array_equal(col, b.entity_ids[e])


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    root = _write_files(tmp_path_factory.mktemp("ingest_plane"))
    config = _config()
    scan = scan_ingest(str(root), config)
    _, chunks = iter_game_chunks(str(root), config, scan.index_maps,
                                 chunk_rows=300, sparse_k=4)
    return root, config, scan, list(chunks)


class TestScanIngest:
    def test_one_pass_scan_matches_two_pass(self, dataset):
        """scan_ingest's maps == build_index_maps_streaming's, its block
        index answers scan_row_counts without reopening, and its row
        count matches the header scan."""
        root, config, scan, _ = dataset
        from photon_tpu.data.streaming import build_index_maps_streaming

        maps2 = build_index_maps_streaming(str(root), config)
        for s in config.shards:
            assert scan.index_maps[s].keys_in_order() == \
                maps2[s].keys_in_order()
        assert scan.n_rows == 1200
        assert scan_row_counts(str(root)) == scan.row_counts
        assert scan_row_counts(str(root),
                               block_index=scan.block_index) == \
            scan.row_counts

    def test_task_plan_matches_serial_chunk_boundaries(self, dataset):
        """plan_chunk_tasks closes tasks at exactly the block boundaries
        the serial chunker closes chunks on — including the non-dividing
        tail blocks."""
        _, _, scan, ref = dataset
        tasks = plan_chunk_tasks(scan.block_index, 300)
        assert len(tasks) == len(ref)
        assert [t.n_rows for t in tasks] == [c.n for c in ref]
        assert sum(t.n_rows for t in tasks) == 1200


class TestParallelDecode:
    @pytest.mark.parametrize("chunk_rows", [250, 300, 1000])
    def test_thread_pool_parity_matrix(self, dataset, chunk_rows):
        """Worker-pool chunks == in-process chunks bit-for-bit, in order,
        across chunk sizes that do and do not divide the block counts."""
        root, config, scan, _ = dataset
        _, c0 = iter_game_chunks(str(root), config, scan.index_maps,
                                 chunk_rows=chunk_rows, sparse_k=4)
        ref = list(c0)
        _, c1 = iter_game_chunks_parallel(
            str(root), config, scan.index_maps, chunk_rows=chunk_rows,
            sparse_k=4, workers=2, mode="thread",
            block_index=scan.block_index)
        got = list(c1)
        assert len(got) == len(ref) >= 2
        for a, b in zip(ref, got):
            _chunks_equal(a, b)

    def test_process_pool_parity(self, dataset):
        """The real plane: spawn-context worker processes decode the
        blocks; chunks come back bit-identical and in order."""
        root, config, scan, ref = dataset
        _, c = iter_game_chunks_parallel(
            str(root), config, scan.index_maps, chunk_rows=300,
            sparse_k=4, workers=2, mode="process")
        got = list(c)
        assert len(got) == len(ref)
        for a, b in zip(ref, got):
            _chunks_equal(a, b)

    def test_workers_never_initialise_a_jax_backend(self, dataset,
                                                   monkeypatch):
        """One process per chip: a parent that holds the chip starts the
        decode workers, so a worker that initialised a backend would fail
        or hang there. The workers import `jax.numpy` (feature_bags) but
        must never touch a device: spawned with a JAX_PLATFORMS naming no
        backend — any backend initialisation raises — they still decode
        every chunk, bit-identically, with no worker death."""
        from photon_tpu import telemetry

        root, config, scan, ref = dataset
        monkeypatch.setenv("JAX_PLATFORMS", "no_such_platform")
        with telemetry.run("workers") as run:
            _, c = iter_game_chunks_parallel(
                str(root), config, scan.index_maps, chunk_rows=300,
                sparse_k=4, workers=2, mode="process")
            got = list(c)
        assert len(got) == len(ref)
        for a, b in zip(ref, got):
            _chunks_equal(a, b)
        assert run.counters.get("ingest.worker_deaths", 0) == 0
        assert run.counters.get("ingest.worker_chunks", 0) == len(ref)

    def test_worker_kill_matrix(self, dataset):
        """An injected ingest_worker kill at the FIRST / a MIDDLE / the
        LAST retired task degrades that chunk to in-process decode: no
        hung iterator, chunk order and content unchanged, the death
        counted."""
        from photon_tpu import telemetry

        root, config, scan, ref = dataset
        n = len(ref)
        for occ in (1, max(n // 2, 1), n):
            run = telemetry.start_run("kill")
            try:
                with fault_plan(FaultPlan.kill_at("ingest_worker", occ)):
                    _, c = iter_game_chunks_parallel(
                        str(root), config, scan.index_maps, chunk_rows=300,
                        sparse_k=4, workers=2, mode="thread",
                        block_index=scan.block_index)
                    got = list(c)
            finally:
                telemetry.finish_run()
            assert len(got) == n
            for a, b in zip(ref, got):
                _chunks_equal(a, b)
            assert run.counters.get("ingest.worker_deaths", 0) >= 1

    def test_python_decoder_parity(self, dataset):
        """use_native=False in the workers matches the forced-Python
        serial stream (decoder choice is parity-pinned either way)."""
        root, config, scan, _ = dataset
        _, c0 = iter_game_chunks(str(root), config, scan.index_maps,
                                 chunk_rows=300, sparse_k=4,
                                 use_native=False)
        ref = list(c0)
        _, c1 = iter_game_chunks_parallel(
            str(root), config, scan.index_maps, chunk_rows=300,
            sparse_k=4, workers=2, mode="thread", use_native=False,
            block_index=scan.block_index)
        for a, b in zip(ref, list(c1)):
            _chunks_equal(a, b)


class TestChunkCache:
    def test_cached_equals_cold_bitwise(self, dataset, tmp_path):
        """Cold decode == cache-building pass == cached epoch, bitwise,
        across dense + sparse shards and the GAME entity columns; the
        cached epoch is counted as a hit."""
        from photon_tpu import telemetry

        root, config, scan, ref = dataset
        cache = tmp_path / "cache"
        _, c = open_chunk_source(str(root), config, scan.index_maps,
                                 chunk_rows=300, sparse_k=4,
                                 cache_dir=str(cache))
        cold = list(c)
        run = telemetry.start_run("hit")
        try:
            _, c = open_chunk_source(str(root), config, scan.index_maps,
                                     chunk_rows=300, sparse_k=4,
                                     cache_dir=str(cache))
            warm = list(c)
        finally:
            telemetry.finish_run()
        assert run.counters.get("ingest.cache_hits", 0) == 1
        assert len(cold) == len(warm) == len(ref)
        for a, b, w in zip(ref, cold, warm):
            _chunks_equal(a, b)
            _chunks_equal(a, w)

    def test_kill_mid_commit_matrix_falls_back(self, dataset, tmp_path):
        """Kills at the first / a middle / the LAST cache_commit
        occurrence (the manifest commit itself) leave a TORN entry that
        reads as a MISS — the next run falls back to Avro decode, serves
        bit-identical chunks, and rebuilds a good entry. No partial chunk
        is ever served."""
        root, config, scan, ref = dataset
        key = cc.cache_key(str(root), config, scan.index_maps, 300, 4)
        with record_sites() as rec:
            _, c = open_chunk_source(str(root), config, scan.index_maps,
                                     chunk_rows=300, sparse_k=4,
                                     cache_dir=str(tmp_path / "dry"))
            list(c)
        n_hits = rec.hits["cache_commit"]
        for occ in (1, max(n_hits // 2, 1), n_hits):
            cache = tmp_path / f"kill_{occ}"
            with pytest.raises(InjectedFault):
                with fault_plan(FaultPlan.kill_at("cache_commit", occ)):
                    _, c = open_chunk_source(
                        str(root), config, scan.index_maps, chunk_rows=300,
                        sparse_k=4, cache_dir=str(cache))
                    list(c)
            assert cc.open_cache(str(cache), key, "game_chunks") is None
            _, c = open_chunk_source(str(root), config, scan.index_maps,
                                     chunk_rows=300, sparse_k=4,
                                     cache_dir=str(cache))
            rebuilt = list(c)
            for a, b in zip(ref, rebuilt):
                _chunks_equal(a, b)
            assert cc.open_cache(str(cache), key,
                                 "game_chunks") is not None

    def test_schema_hash_invalidation(self, dataset):
        """The key moves with every layout/config/map input: chunk_rows,
        sparse_k, GameDataConfig, index maps, entry kind."""
        root, config, scan, _ = dataset
        maps = scan.index_maps
        base = cc.cache_key(str(root), config, maps, 300, 4)
        assert cc.cache_key(str(root), config, maps, 256, 4) != base
        assert cc.cache_key(str(root), config, maps, 300, 8) != base
        import dataclasses

        cfg2 = dataclasses.replace(config, entity_fields=())
        assert cc.cache_key(str(root), cfg2, maps, 300, 4) != base
        cfg3 = dataclasses.replace(config, shards={
            **config.shards,
            "wide": FeatureShardConfig(bags=("g",), has_intercept=False,
                                       dense_threshold=8)})
        assert cc.cache_key(str(root), cfg3, maps, 300, 4) != base
        from photon_tpu.data.index_map import IndexMap

        maps2 = dict(maps)
        maps2["wide"] = IndexMap({"only": 0}, frozen=True)
        assert cc.cache_key(str(root), config, maps2, 300, 4) != base
        assert cc.cache_key(str(root), config, maps, 300, 4,
                            kind="ladder") != base
        # and the key is STABLE when nothing changed
        assert cc.cache_key(str(root), config, maps, 300, 4) == base

    def test_newer_schema_refused(self, dataset, tmp_path):
        root, config, scan, _ = dataset
        cache = tmp_path / "cache"
        _, c = open_chunk_source(str(root), config, scan.index_maps,
                                 chunk_rows=300, sparse_k=4,
                                 cache_dir=str(cache))
        list(c)
        key = cc.cache_key(str(root), config, scan.index_maps, 300, 4)
        mpath = os.path.join(cc.entry_dir(str(cache), key),
                             "MANIFEST.json")
        doc = json.load(open(mpath))
        doc["schema"] = cc.CACHE_SCHEMA_VERSION + 1
        with open(mpath, "w") as f:
            json.dump(doc, f)
        with pytest.raises(cc.ChunkCacheSchemaError):
            open_chunk_source(str(root), config, scan.index_maps,
                              chunk_rows=300, sparse_k=4,
                              cache_dir=str(cache))

    def test_corrupted_payload_detected(self, dataset, tmp_path):
        root, config, scan, _ = dataset
        cache = tmp_path / "cache"
        _, c = open_chunk_source(str(root), config, scan.index_maps,
                                 chunk_rows=300, sparse_k=4,
                                 cache_dir=str(cache))
        list(c)
        key = cc.cache_key(str(root), config, scan.index_maps, 300, 4)
        bag = cc.open_cache(str(cache), key, "game_chunks")
        victim = os.path.join(bag.dir, bag.manifest["entries"][0]["file"])
        raw = open(victim, "rb").read()
        with open(victim, "wb") as f:
            f.write(raw[:-4] + b"\x00\x01\x02\x03")
        with pytest.raises(cc.ChunkCacheCorrupt):
            _, c = open_chunk_source(str(root), config, scan.index_maps,
                                     chunk_rows=300, sparse_k=4,
                                     cache_dir=str(cache))
            list(c)

    def test_response_mask_and_presence_round_trip(self, tmp_path):
        """allow_missing_response masks and optional-entity presence ride
        the cache: the cached stream restores them onto the handle
        exactly as a live decode."""
        rng = np.random.default_rng(3)
        schema = training_example_schema(feature_bags=("f",),
                                         entity_fields=("member",))
        # nullable response: the allow_missing_response regime
        schema["fields"][0]["type"] = ["null", "double"]
        records = []
        for i in range(60):
            records.append({
                "response": float(i) if i % 4 else None,
                "offset": None, "weight": None, "uid": f"r{i}",
                "member": f"m{i % 5}" if i % 3 else None,
                "f": [{"name": "x", "term": "",
                       "value": float(rng.normal())}]})
        root = tmp_path / "data"
        os.makedirs(root)
        write_avro(root / "a.avro", records, schema, block_records=16)
        config = GameDataConfig(
            shards={"s": FeatureShardConfig(bags=("f",),
                                            has_intercept=True)},
            entity_fields=("member",),
            optional_entity_fields=("member",),
            allow_missing_response=True)
        scan = scan_ingest(str(root), config)
        cache = tmp_path / "cache"

        def collect(cache_dir):
            stream, chunks = open_chunk_source(
                str(root), config, scan.index_maps, chunk_rows=25,
                cache_dir=cache_dir)
            out = []
            for ch in chunks:
                out.append((np.asarray(stream.last_response_mask),
                            np.asarray(
                                stream.last_entity_presence["member"])))
            return stream, out

        s_cold, cold = collect(str(cache))
        s_warm, warm = collect(str(cache))
        assert s_cold.saw_missing_response and s_warm.saw_missing_response
        assert len(cold) == len(warm) >= 2
        for (ma, pa), (mb, pb) in zip(cold, warm):
            np.testing.assert_array_equal(ma, mb)
            np.testing.assert_array_equal(pa, pb)

    def test_distributed_writer_convention(self, tmp_path):
        """The multi-host cache directory convention (docs/INGEST.md):
        p<k>_ payload prefixes, k>0 sidecars instead of manifests,
        process 0 merging entries + metas and committing the ONE shared
        manifest LAST; a missing sidecar fails loudly instead of
        publishing a partial entry."""
        from photon_tpu.data.chunk_cache import (ChunkCacheWriter,
                                                 open_cache,
                                                 shard_chunk_range)

        key = "d" * 64
        w1 = ChunkCacheWriter(tmp_path, key, "game_chunks",
                              meta={"n_chunks": 1, "n_rows": 7},
                              process=1, n_processes=2)
        w1.add_array("c00001.y", np.arange(3.0))
        w1.commit()
        # no manifest yet: the entry is a MISS everywhere until process 0
        assert open_cache(tmp_path, key, "game_chunks") is None
        w0 = ChunkCacheWriter(tmp_path, key, "game_chunks",
                              meta={"n_chunks": 1, "n_rows": 5},
                              process=0, n_processes=2)
        w0.add_array("c00000.y", np.arange(2.0))
        w0.commit(sidecar_timeout_s=5)
        bag = open_cache(tmp_path, key, "game_chunks")
        assert sorted(bag.names()) == ["c00000.y", "c00001.y"]
        assert bag.meta["n_chunks"] == 2 and bag.meta["n_rows"] == 12
        np.testing.assert_array_equal(
            np.asarray(bag.array("c00001.y")), np.arange(3.0))
        files = sorted(os.listdir(w0.dir))
        assert any(f.startswith("p0_") for f in files)
        assert any(f.startswith("p1_") for f in files)
        # process 0 with a never-arriving sidecar refuses to publish
        key2 = "e" * 64
        lone = ChunkCacheWriter(tmp_path, key2, "game_chunks",
                                meta={}, process=0, n_processes=2)
        lone.add_array("c00000.y", np.arange(2.0))
        with pytest.raises(TimeoutError, match="sidecar"):
            lone.commit(sidecar_timeout_s=0.2)
        assert open_cache(tmp_path, key2, "game_chunks") is None
        # the canonical split covers [0, n) contiguously in order
        spans = [shard_chunk_range(10, k, 3) for k in range(3)]
        assert spans == [(0, 4), (4, 7), (7, 10)]


class TestLadderCache:
    @pytest.mark.parametrize("n_shards", [1, 2])
    def test_ladder_cache_round_trips_bitwise(self, dataset, tmp_path,
                                              n_shards):
        """The direct-to-blocked-ELL build == its cached reopen,
        leaf-for-leaf, for both the single-device and the mesh
        (ShardedBlockedEllRows) ladders."""
        import jax

        root, config, scan, _ = dataset
        cache = tmp_path / f"ladder{n_shards}"
        kw = dict(d_dense=64, sparse_k=4, n_shards=n_shards,
                  cache_dir=str(cache))
        cb1 = chunk_blocked_ell_from_avro(str(root), config,
                                          scan.index_maps, "wide", 256,
                                          **kw)
        cb2 = chunk_blocked_ell_from_avro(str(root), config,
                                          scan.index_maps, "wide", 256,
                                          **kw)
        assert cb1.X.n_chunks == cb2.X.n_chunks
        assert cb1.X.chunk_shards == cb2.X.chunk_shards == n_shards
        l1 = jax.tree_util.tree_leaves(cb1.X.chunks)
        l2 = jax.tree_util.tree_leaves(cb2.X.chunks)
        assert len(l1) == len(l2)
        for a, b in zip(l1, l2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in ((cb1.y, cb2.y), (cb1.weights, cb2.weights),
                     (cb1.offsets, cb2.offsets),
                     (cb1.X.perm_cols, cb2.X.perm_cols),
                     (cb1.X.inv_perm, cb2.X.inv_perm)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert cb1.X.last_col_pos == cb2.X.last_col_pos


class TestAdaptivePrefetch:
    def test_widen_narrow_and_budget(self):
        ap = AdaptivePrefetch(depth=2, max_depth=8, byte_budget=1000)
        ap.observe(stall_s=1.0, compute_s=0.1, n_items=4, item_bytes=100)
        assert ap.depth == 4  # stall > compute: +2
        ap.observe(stall_s=0.2, compute_s=1.0, n_items=4, item_bytes=100)
        assert ap.depth == 5  # stalled (>5% of compute): +1
        ap.observe(stall_s=0.0, compute_s=1.0, n_items=4, item_bytes=100)
        assert ap.depth == 4  # stall-free: -1
        ap.observe(stall_s=9.0, compute_s=0.1, n_items=4, item_bytes=200)
        assert ap.depth == 5  # byte budget: 1000 // 200
        ap.observe_wait(0.5, 200)
        assert ap.depth == 5  # still capped
        ap.observe_wait(0.5, 50)
        assert ap.depth == 6  # wider budget at smaller items
        assert [d["why"] for d in ap.decisions] == [
            "stalled", "stalled", "stall-free", "stalled", "upload-wait"]

    def test_iter_device_feeds_controller_and_telemetry(self, tmp_path):
        """A streamed pass under the controller records its decision
        (controller trace + a prefetch_decision JSONL event) and yields
        chunks identical to a fixed window — depth is an overlap knob,
        never a results knob."""
        from photon_tpu import telemetry
        from photon_tpu.data.dataset import chunk_batch, make_batch
        from photon_tpu.telemetry import read_jsonl

        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 4)).astype(np.float32)
        cb = chunk_batch(make_batch(X, np.zeros(64, np.float32)), 16)
        ctl = AdaptivePrefetch()
        jsonl = str(tmp_path / "run.jsonl")
        telemetry.start_run("prefetch", jsonl_path=jsonl)
        try:
            fixed = [np.asarray(b.y) for _, b in cb.iter_device(prefetch=2)]
            ctl_out = [np.asarray(b.y)
                       for _, b in cb.iter_device(prefetch=ctl)]
        finally:
            telemetry.finish_run()
        for a, b in zip(fixed, ctl_out):
            np.testing.assert_array_equal(a, b)
        assert len(ctl.decisions) == 1
        events = [e for e in read_jsonl(jsonl)
                  if e.get("type") == "prefetch_decision"]
        assert len(events) == 1
        assert events[0]["depth"] >= 1


class TestPlaneSolverParity:
    def test_streamed_glm_plane_on_off_bit_identical(self, dataset,
                                                     tmp_path):
        """THE acceptance parity, streamed-GLM face: the host-chunked
        dataset assembled through the plane (worker pool + cache, then
        the cached epoch) is bit-identical to the serial read, the chunk
        program sees ONE dispatch signature across all three sources, and
        the streamed L-BFGS solve lands f64-bit-identical coefficients."""
        from photon_tpu.analysis.rules import TraceSignatureLog
        from photon_tpu.data.dataset import make_chunked_batch
        from photon_tpu.models.training import train_glm
        from photon_tpu.ops.losses import TaskType
        from photon_tpu.optim.config import OptimizerConfig
        from photon_tpu.optim.regularization import l2

        root, config, scan, _ = dataset
        cache = tmp_path / "cache"

        def read(**kw):
            data, n_real = stream_to_host(
                str(root), config, scan.index_maps,
                chunked_shards={"dense"}, chunk_rows=300,
                objective_chunk_rows=256, sparse_k=4, **kw)
            assert n_real == 1200
            return data

        plain = read()
        plane = read(workers=2, cache_dir=str(cache),
                     block_index=scan.block_index)
        cached = read(workers=2, cache_dir=str(cache))
        log = TraceSignatureLog()
        batches = []
        for data in (plain, plane, cached):
            cb = make_chunked_batch(data.shards["dense"], data.y,
                                    data.weights, data.offsets)
            if batches:
                ref = batches[0]
                assert cb.n_chunks == ref.n_chunks
                for i in range(cb.n_chunks):
                    a, b = ref.chunk(i), cb.chunk(i)
                    np.testing.assert_array_equal(np.asarray(a.X),
                                                  np.asarray(b.X))
                    np.testing.assert_array_equal(a.y, b.y)
                    np.testing.assert_array_equal(a.weights, b.weights)
            log.record("ingest.chunk0", tuple(cb.chunk(0)))
            batches.append(cb)
        assert len(log.signatures("ingest.chunk0")) == 1
        assert not log.hazards()
        cfg = OptimizerConfig(max_iters=8, tolerance=0.0, reg=l2(),
                              reg_weight=1e-2, history=4)
        ws = [np.asarray(
            train_glm(b, TaskType.LOGISTIC_REGRESSION,
                      cfg)[0].coefficients.means, dtype=np.float64)
            for b in batches]
        np.testing.assert_array_equal(ws[0], ws[1])
        np.testing.assert_array_equal(ws[0], ws[2])

    def test_game_driver_plane_on_off_bit_identical(self, tmp_path):
        """THE acceptance parity, GAME-e2e face: run_training (fixed +
        per-entity random effect) with the ingest plane on (workers +
        chunk cache, twice — cold build then cached epoch) produces
        models f64-bit-identical to the plane-off driver run."""
        from photon_tpu.drivers import TrainingParams, run_training

        root = _write_files(tmp_path / "train", n_files=2,
                            rows_per_file=220, seed=7)
        shards = {"fixedShard": {"bags": ["f"], "has_intercept": True},
                  "memShard": {"bags": ["g"], "has_intercept": False,
                               "dense_threshold": 4}}
        coords = {"fixed": {"feature_shard": "fixedShard",
                            "reg_type": "l2", "reg_weight": 0.5,
                            "max_iters": 15},
                  "perMember": {"feature_shard": "memShard",
                                "entity_name": "member",
                                "reg_type": "l2", "reg_weight": 2.0,
                                "max_iters": 10}}

        def fit(tag, **kw):
            return run_training(TrainingParams(
                train_path=str(root), output_dir=str(tmp_path / tag),
                feature_shards=shards, coordinates=coords,
                entity_fields=["member"], n_sweeps=1, sparse_k=4,
                streaming=True, streaming_chunk_rows=128, **kw))

        off = fit("off")
        cache = str(tmp_path / "cache")
        on = fit("on", ingest_workers=2, chunk_cache_dir=cache)
        warm = fit("warm", ingest_workers=2, chunk_cache_dir=cache)
        for run_out in (on, warm):
            ca = off.best.model.coordinates
            cb = run_out.best.model.coordinates
            assert set(ca) == set(cb)
            np.testing.assert_array_equal(
                np.asarray(ca["fixed"].model.coefficients.means),
                np.asarray(cb["fixed"].model.coefficients.means))
            np.testing.assert_array_equal(
                np.asarray(ca["perMember"].coefficients),
                np.asarray(cb["perMember"].coefficients))
            np.testing.assert_array_equal(ca["perMember"].entity_keys,
                                          cb["perMember"].entity_keys)


class TestSelftestCLI:
    @pytest.mark.slow
    def test_selftest_cli(self):
        import subprocess
        import sys

        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "photon_tpu.ingest", "--selftest",
             "--json"], capture_output=True, text=True, timeout=600,
            env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        assert report["ok"]
        assert set(report["checks"]) == {
            "scan", "decode_parity", "cache", "ladder", "prefetch",
            "contract"}
