"""The first streamed cell (PR 34, `glm-sparse10m-stream.single`): a host
blocked-ELL chunk ladder built in its stored dtype piece by piece, the
streamed solve's trial count, `stream.pass` spans and `stream.upload_bytes`
counter, the benchmark's chunk-preserving generator, the four `stream_*`
readers, and the cell's comparison with its controls — at tiny sizes on
the CPU.
"""
import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import time
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.gen import reference, sparse_stream
from benchmark.layer_metrics import (stream_chunk_device_ms,
                                     stream_handout_wait_ms,
                                     stream_host_step_ms,
                                     stream_idle_unattributed_share,
                                     stream_link_share, stream_pass_s,
                                     stream_stall_share,
                                     stream_turnaround_ms,
                                     stream_upload_call_ms)
from benchmark.lib.stream_bytes import chunk_upload_bytes, ladder_bytes
from photon_tpu import telemetry
from photon_tpu.data import dataset, matrix
from photon_tpu.data.dataset import (cast_features, chunk_blocked_ell,
                                     make_batch)
from photon_tpu.data.matrix import (SparseRows, shard_blocked_ell,
                                    to_blocked_ell)
from photon_tpu.models.training import train_glm
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig, OptimizerType
from photon_tpu.optim.regularization import elastic_net, l2

LOGISTIC = TaskType.LOGISTIC_REGRESSION
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "glm-sparse10m-stream.single"


def _problem(n=512, d=400, k=7, seed=11):
    """Zipf columns (a row repeats the popular ones), a tenth of the slots
    empty."""
    rng = np.random.default_rng(seed)
    ind = ((rng.zipf(1.4, size=(n, k)) - 1) % (d - 1)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    val[rng.uniform(size=(n, k)) < 0.1] = 0.0
    y = (rng.uniform(size=n) < 0.4).astype(np.float32)
    return SparseRows(ind, val, d), y


def _recast_whole_block_ladder(batch, chunk_rows, d_dense=1024,
                               feature_dtype=None, n_shards=1):
    """`chunk_blocked_ell` as every program before PR 34 had it: the whole
    hot block built as ONE float32 array, every value leaf recast chunk by
    chunk afterwards."""
    cb = chunk_blocked_ell(batch, chunk_rows, d_dense=d_dense,
                           n_shards=n_shards)
    if feature_dtype is None:
        return cb

    def recast(c):
        return dataclasses.replace(
            c, dense=np.asarray(c.dense).astype(feature_dtype),
            ell_vals=tuple(np.asarray(v).astype(feature_dtype)
                           for v in c.ell_vals),
            bucket_vals=tuple(np.asarray(v).astype(feature_dtype)
                              for v in c.bucket_vals))

    return cb._replace(X=dataclasses.replace(
        cb.X, chunks=tuple(recast(c) for c in cb.X.chunks)))


def _same_leaves(a, b):
    la, lb = jax.tree_util.tree_flatten(a), jax.tree_util.tree_flatten(b)
    assert la[1] == lb[1]  # structure and every meta field
    for x, y in zip(la[0], lb[0]):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y)


# ------------------------------------------- (a) the piece-by-piece build
@pytest.mark.parametrize("pieces", ["one_piece", "many_pieces"])
@pytest.mark.parametrize("n_shards", [1, 4], ids=["one_device", "mesh4"])
def test_ladder_built_in_its_dtype_is_the_recast_ladder(monkeypatch,
                                                        n_shards, pieces):
    """Built straight into bf16, the ladder is leaf for leaf the float32
    build recast afterwards — the one-device form and the mesh form, and
    also where a chunk's rows take several scatter pieces that end inside
    a chunk."""
    X, y = _problem()
    if pieces == "many_pieces":
        monkeypatch.setattr(matrix, "_HOST_PIECE_CELLS", 32 * 5)
    built = chunk_blocked_ell(make_batch(X, y), 128, d_dense=32,
                              feature_dtype=jnp.bfloat16,
                              n_shards=n_shards)
    monkeypatch.undo()
    old = _recast_whole_block_ladder(make_batch(X, y), 128, d_dense=32,
                                     feature_dtype=jnp.bfloat16,
                                     n_shards=n_shards)
    assert built.n_chunks == old.n_chunks == 4
    for c, o in zip(built.X.chunks, old.X.chunks):
        assert c.dense.dtype == jnp.bfloat16
        _same_leaves(c, o)
    for name in ("y", "weights", "offsets"):
        assert np.array_equal(getattr(built, name), getattr(old, name))
    assert np.array_equal(built.X.perm_cols, old.X.perm_cols)
    assert np.count_nonzero(np.asarray(built.X.chunks[0].dense,
                                       np.float32)) > 0


def test_host_block_keeps_f32_without_a_dtype():
    X, y = _problem()
    cb = chunk_blocked_ell(make_batch(X, y), 128, d_dense=32)
    assert all(c.dense.dtype == np.float32 for c in cb.X.chunks)
    whole = shard_blocked_ell(X, 4, 32)
    assert whole.dense.dtype == np.float32
    assert np.array_equal(np.concatenate([c.dense for c in cb.X.chunks]),
                          whole.dense)


def _build_peak(build, X, y, chunk_rows, d_dense):
    """(tracemalloc peak − the returned ladder's bytes) in hot blocks of
    one chunk."""
    batch = make_batch(X, y)
    tracemalloc.start()
    try:
        cb = build(batch, chunk_rows, d_dense=d_dense,
                   feature_dtype=jnp.bfloat16)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    return (peak - ladder_bytes(cb)) / chunk_upload_bytes(cb)["hot_block"]


def test_build_peak_stays_under_three_chunks():
    """At 4 chunks of 2048 rows × 512 hot columns the piece-by-piece build
    holds under three chunks' hot blocks beside the ladder it returns; the
    float32 whole-block build holds the ladder twice over: 8 blocks (the
    parent's held its float64 scatter scratch besides, 16 more here)."""
    rng = np.random.default_rng(5)
    n, d, k = 8192, 6000, 16
    ind = ((rng.zipf(1.4, size=(n, k)) - 1) % (d - 1)).astype(np.int32)
    X = SparseRows(ind, rng.normal(size=(n, k)).astype(np.float32), d)
    y = np.zeros(n, np.float32)
    new = _build_peak(chunk_blocked_ell, X, y, 2048, 512)
    old = _build_peak(_recast_whole_block_ladder, X, y, 2048, 512)
    assert new < 3.0 and old > 7.5, (new, old)


# ------------------------------------------------- (b) the streamed solve
def test_streamed_bf16_ladder_solve_is_the_resident_solve():
    """`train_glm` over the bf16 chunk ladder: its final loss is the
    float64 numpy objective of the ``w`` it returns and the resident
    `to_blocked_ell` solve's on the same rows, to one bf16 ulp
    (tests/test_streamed.py's bf16 tolerance is 5e-3; this is 2^-8)."""
    X, y = _problem(n=768, seed=3)
    cfg = OptimizerConfig(max_iters=12, tolerance=0.0, reg=l2(),
                          reg_weight=0.3, history=5)
    cb = chunk_blocked_ell(make_batch(X, y), 192, d_dense=32,
                           feature_dtype=jnp.bfloat16)
    one = cast_features(make_batch(to_blocked_ell(X, 32), y))
    m_c, r_c = train_glm(cb, LOGISTIC, cfg)
    _, r_s = train_glm(one, LOGISTIC, cfg)
    w = np.asarray(m_c.coefficients.means, np.float64)
    va = reference.stored(np.asarray(X.values), jnp.bfloat16)
    f64 = reference.np_logistic_objective(
        np.einsum("nk,nk->n", va, w[X.indices]), y, w, 0.3)
    assert abs(float(r_c.value) - f64) <= reference.LOSS_RTOL * f64
    assert abs(float(r_c.value) - float(r_s.value)) <= (
        reference.LOSS_RTOL * f64)
    assert int(r_c.iterations) == int(r_s.iterations) == 12


@pytest.mark.parametrize("solver", ["lbfgs", "owlqn"])
def test_streamed_result_counts_its_trials(solver):
    """`OptResult.evaluations` is the solve's line-search trials, the same
    number the `solver.linesearch_trials` counter holds, beside
    `solver.iterations`; every pass is one `stream.pass` span and every
    chunk it consumed is in `stream.upload_bytes`."""
    X, y = _problem()
    if solver == "lbfgs":
        cfg = OptimizerConfig(max_iters=8, tolerance=0.0, reg=l2(),
                              reg_weight=0.3, history=5)
    else:
        cfg = OptimizerConfig(max_iters=8, tolerance=0.0,
                              reg=elastic_net(0.5), reg_weight=1e-2,
                              history=5, optimizer=OptimizerType.OWLQN)
    cb = chunk_blocked_ell(make_batch(X, y), 128, d_dense=32,
                           feature_dtype=jnp.bfloat16)
    with telemetry.run("t") as run:
        _, res = train_glm(cb, LOGISTIC, cfg)
        report = run.report()
    c = report["counters"]
    assert res.evaluations is not None
    assert int(res.evaluations) == c["solver.linesearch_trials"] >= 8
    assert int(res.iterations) == c["solver.iterations"] == 8
    assert c["stream.passes"] == c["solver.feature_streams"]
    assert c["stream.chunk_uploads"] == 4 * c["stream.passes"]
    assert c["stream.upload_bytes"] == (c["stream.chunk_uploads"]
                                        * cb.chunk_nbytes())
    assert cb.chunk_nbytes() == chunk_upload_bytes(cb)["total"]
    spans = [s for s in report["spans"] if s["name"] == "stream.pass"]
    assert len(spans) == c["stream.passes"]
    kinds = [s["attrs"]["kind"] for s in spans]
    if solver == "lbfgs":
        assert kinds[0] == "init" and set(kinds[1:]) == {"dz", "gradient"}
        assert kinds.count("dz") == 8
    else:
        assert set(kinds) == {"value_grad", "ladder"}


def test_upload_in_pieces_is_the_whole_upload(monkeypatch):
    """A chunk uploaded in row pieces assembled in place is leaf for leaf
    the chunk `device_put` uploads whole — also where the last piece is
    short and where a leaf is too small to be cut — and the streamed solve
    over pieces is the solve over whole uploads, bit for bit."""
    X, y = _problem()
    cb = chunk_blocked_ell(make_batch(X, y), 128, d_dense=32,
                           feature_dtype=jnp.bfloat16)
    whole = jax.device_put(cb.chunk(1))
    cfg = OptimizerConfig(max_iters=5, tolerance=0.0, reg=l2(),
                          reg_weight=0.3, history=5)
    _, ref = train_glm(cb, LOGISTIC, cfg)
    # 23 rows of the (128, 32) bf16 hot block a piece: 5 pieces and a
    # short sixth; every other leaf is under two pieces and goes whole
    monkeypatch.setattr(dataset, "_UPLOAD_PIECE_BYTES", 23 * 64)
    pieces = dataset.device_put_in_pieces(cb.chunk(1))
    assert isinstance(pieces.X.dense, jax.Array)
    _same_leaves(pieces, whole)
    _, res = train_glm(cb, LOGISTIC, cfg)
    assert np.array_equal(np.asarray(res.w), np.asarray(ref.w))
    assert np.array_equal(np.asarray(res.loss_history),
                          np.asarray(ref.loss_history))


def test_one_shot_stream_counts_its_bytes():
    X, y = _problem()
    cb = chunk_blocked_ell(make_batch(X, y), 128, d_dense=32)
    with telemetry.run("t") as run:
        for _ in cb.iter_device():
            pass
        c = run.report_compact()["counters"]
    assert c["stream.chunk_uploads"] == 4
    assert c["stream.upload_bytes"] == 4 * chunk_upload_bytes(cb)["total"]


def test_ring_holds_its_depth_and_ends_empty():
    """The ring acts when its consumer speaks: `consumed` registers the
    handed chunk's program, frees the chunk spoken for BEFORE it (so the
    device never holds one more than the ring's depth) and only then
    issues the next upload — behind the program just dispatched, not
    ahead of it. A consumer that says nothing gets its uploads when it
    resumes the generator. `close()` drops what the last pass primed; a
    solve leaves its ring empty, having uploaded what its passes consumed
    and the one chunk primed for a pass that never came."""
    X, y = _problem()
    cb = chunk_blocked_ell(make_batch(X, y), 128, d_dense=32)
    log = []

    class Output:  # stands for a chunk program's result, still running
        def __init__(self, i):
            self.i = i

        def is_ready(self):
            return False

        def block_until_ready(self):
            log.append(("ready", self.i))
            return self

    ring = cb.device_ring(prefetch=2)
    put = ring._put
    ring._put = lambda i: (log.append(("put", i)), put(i))[1]
    held = []
    with telemetry.run("t") as run:
        for i, b in ring.stream_pass():
            assert not b.X.dense.is_deleted()
            held.append(b)
            log.append(("spoke", i))
            assert ring.consumed(out := Output(i)) is out
        c = run.report_compact()["counters"]
    # chunk i's program is registered before chunk i + 1 is issued, and
    # chunk i - 1 is waited for and freed before that upload could make a
    # third; the last `consumed` primed the next pass's first chunk
    assert log == [("put", 0), ("put", 1), ("spoke", 0),
                   ("spoke", 1), ("ready", 0), ("put", 2),
                   ("spoke", 2), ("ready", 1), ("put", 3),
                   ("spoke", 3), ("ready", 2), ("put", 0)]
    assert all(leaf.is_deleted() for b in held[:3]
               for leaf in jax.tree_util.tree_leaves(b))
    assert not held[3].X.dense.is_deleted()  # its program is the ring's
    assert len(ring._window) == 1 and ring._spoken[1].i == 3  # to wait for
    # every upload but the two primed before anything was handed out went
    # out behind a running program
    assert c["stream.uploads_behind_compute"] == 3
    assert c["stream.chunk_uploads"] == 4
    del log[:]
    held += [b for _, b in ring.stream_pass()]  # a consumer that says nothing
    assert log == [("ready", 3), ("put", 1), ("put", 2), ("put", 3),
                   ("put", 0), ("put", 1)]
    assert all(leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(held[3]))
    assert not any(leaf.is_deleted() for b in held[4:]
                   for leaf in jax.tree_util.tree_leaves(b))
    assert len(ring._window) == 2 and ring._spoken is None
    ring.close()
    assert not ring._window and ring._next == 0

    from photon_tpu.optim import streamed

    seen = []
    real = streamed._backend

    def spy(*args):
        be = real(*args)
        put = be.ring._put
        # a slow link: the upload calls outweigh everything else in a pass
        be.ring._put = lambda i: (seen.append(i), time.sleep(0.01),
                                  put(i))[2]
        seen.append(be)
        return be

    cfg = OptimizerConfig(max_iters=3, tolerance=0.0, reg=l2(),
                          reg_weight=0.3, history=5)
    streamed._backend = spy
    try:
        with telemetry.run("t") as run:
            train_glm(cb, LOGISTIC, cfg)
            compact = run.report_compact()
    finally:
        streamed._backend = real
    c = compact["counters"]
    be, uploads = seen[0], seen[1:]
    assert not be.ring._window and be.ring._spoken is None
    assert c["stream.chunk_uploads"] == 4 * 7
    assert len(uploads) == 4 * 7 + 1
    assert 0 <= c.get("stream.uploads_behind_compute", 0) <= 4 * 7 - 1
    # a pass's wall = the waits for a chunk + the upload calls + the
    # consumer's own time: `stream.compute_seconds` no longer holds the
    # upload calls (it was the wall less the waits alone, so the three
    # added up to the wall AND the 0.29 s slept in `_put`)
    assert c["stream.issue_seconds"] >= 0.01 * (4 * 7 + 1)
    parts = (c["stream.stall_seconds"] + c["stream.issue_seconds"]
             + c["stream.compute_seconds"])
    walls = compact["span_totals"]["solve.lbfgs_streamed/stream.pass"]
    assert parts <= walls < parts + c["stream.issue_seconds"]


# ------------------------------ a chunk's timeline from inside the solve
NEW_SPANS = ("stream.upload", "stream.handout", "stream.release",
             "stream.dispatch", "stream.readback", "solve.host_step")
SOLVE, PASS = "solve.lbfgs_streamed", "solve.lbfgs_streamed/stream.pass"


@pytest.fixture(scope="module")
def timeline():
    """One small streamed L-BFGS solve (3 iterations: 7 passes of 4
    chunks) under a telemetry run: (the run's report, its compact one)."""
    X, y = _problem()
    cb = chunk_blocked_ell(make_batch(X, y), 128, d_dense=32)
    cfg = OptimizerConfig(max_iters=3, tolerance=0.0, reg=l2(),
                          reg_weight=0.3, history=5)
    with telemetry.run("t") as run:
        train_glm(cb, LOGISTIC, cfg)
        return run.report(), run.report_compact()


def _named(report, name):
    return [s for s in report["spans"] if s["name"] == name]


@pytest.mark.parametrize("name", NEW_SPANS)
def test_timeline_has_every_span(timeline, name):
    report, compact = timeline
    spans = _named(report, name)
    assert spans
    # the reports are a timeline: a start beside every length, a count
    # beside every total
    assert all(s["t_s"] >= 0.0 and s["seconds"] >= 0.0 for s in spans)
    paths = {s["path"] for s in spans}
    assert sum(compact["span_counts"][p] for p in paths) == len(spans)
    assert set(compact["span_counts"]) == set(compact["span_totals"])


def test_timeline_counts_a_span_a_chunk(timeline):
    report, compact = timeline
    c = compact["counters"]
    assert c["stream.chunk_uploads"] == 4 * 7
    # every consumed chunk was handed out once and dispatched once; one
    # more was uploaded: primed for a pass that never came, dropped by
    # `close()` — the last `stream.release`, the solve span's own child
    assert len(_named(report, "stream.handout")) == 4 * 7
    assert len(_named(report, "stream.dispatch")) == 4 * 7
    assert len(_named(report, "stream.upload")) == 4 * 7 + 1
    releases = _named(report, "stream.release")
    assert [s["path"] for s in releases].count(
        SOLVE + "/stream.release") == 2  # the last program's chunk, the
    #                                      primed one
    assert len(releases) == 4 * 7 + 1
    assert [s["attrs"]["chunk"] for s in _named(report, "stream.handout")
            ] == list(range(4)) * 7
    assert [s["attrs"]["chunk"] for s in _named(report, "stream.upload")
            ] == (list(range(4)) * 8)[:4 * 7 + 1]
    assert [s["attrs"]["n"] for s in _named(report, "stream.pass")
            ] == list(range(7))
    assert {s["attrs"]["program"] for s in _named(report, "stream.dispatch")
            } == {"init", "dz_phi", "grad"}
    assert {s["attrs"]["what"] for s in _named(report, "stream.readback")
            } == {"margins", "totals"}
    # every stretch between two passes: after the first pass, then three
    # an iteration
    assert [s["attrs"]["part"] for s in _named(report, "solve.host_step")
            ] == ["update"] + ["direction", "linesearch", "update"] * 3


def test_timeline_nests_the_ring_under_the_pass(timeline):
    """The ring's spans are the pass's own children — siblings of
    `stream.dispatch`, never under it — so a chunk's dispatch, the upload
    issued behind it and the release of the chunk before read as one
    sequence."""
    report, _ = timeline
    for name in ("stream.upload", "stream.handout", "stream.dispatch",
                 "stream.readback"):
        assert {s["path"] for s in _named(report, name)} == {
            PASS + "/" + name}
    assert {s["path"] for s in _named(report, "stream.release")} == {
        PASS + "/stream.release", SOLVE + "/stream.release"}
    assert {s["path"] for s in _named(report, "solve.host_step")} == {
        SOLVE + "/solve.host_step"}


def test_timeline_spans_are_the_counters(timeline):
    """One measurement, two sinks: the seconds counters are the sums of
    the spans' own clock readings."""
    report, compact = timeline
    c = compact["counters"]
    for counter, name in (("stream.issue_seconds", "stream.upload"),
                          ("stream.stall_seconds", "stream.handout")):
        # `report()` rounds a span to the microsecond
        assert c[counter] == pytest.approx(
            sum(s["seconds"] for s in _named(report, name)),
            abs=1e-6 * len(_named(report, name)))
    # the pass's spans leave little of a pass unnamed, and the host steps
    # and the passes little of the solve
    totals = compact["span_totals"]
    assert totals[PASS] + totals[SOLVE + "/solve.host_step"] + totals[
        SOLVE + "/stream.release"] <= totals[SOLVE]


def test_chunk_programs_carry_the_shared_scopes():
    """The X-pass, loss and L-BFGS scopes the resident solves carry are in
    the streamed solve's programs too."""
    from photon_tpu.models.training import make_objective
    from photon_tpu.optim import streamed
    from photon_tpu.optim.lbfgs import empty_history

    X, y = _problem()
    cb = chunk_blocked_ell(make_batch(X, y), 128, d_dense=32,
                           feature_dtype=jnp.bfloat16)
    cfg = OptimizerConfig(reg=l2(), reg_weight=0.3)
    obj = make_objective(LOGISTIC, cfg, 400,
                         intercept_index=cb.X.last_col_pos)
    w = jnp.zeros((400,), jnp.float32)
    z = jnp.zeros((128,), jnp.float32)
    chunk = cb.chunk(0)

    def names(fn, *args):
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        return {s for s in telemetry.DEVICE_SCOPES if s in text}

    assert {"xpass.fwd.hot", "xpass.fwd.tail", "xpass.fwd.reassemble",
            "xpass.t.hot", "xpass.t.tail", "objective.loss"} <= names(
        streamed._chunk_init_fn, obj, w, chunk)
    assert {"xpass.t.tail", "objective.loss"} <= names(
        streamed._chunk_grad_fn, obj, z, chunk)
    assert {"xpass.fwd.tail", "objective.loss"} <= names(
        streamed._chunk_dz_phi_fn, obj, w, z, np.float32(1.0), chunk)
    assert {"lbfgs.linesearch", "objective.loss"} <= names(
        streamed._chunk_phi, obj, z, z, np.float32(1.0), chunk.y,
        chunk.weights)
    assert {"lbfgs.two_loop", "lbfgs.direction"} <= names(
        streamed._lbfgs_direction, w, empty_history(5, 400, jnp.float32))


# ------------------------------------------------- (c) the generator
def test_generator_gives_three_seeds_the_same_ladder_shapes(tmp_path):
    sizes = dict(rows=1024, features=3000, nnz=8, zipf=1.4, hot_signal=300,
                 n_chunks=4, cache_dir=str(tmp_path))
    ladders = []
    for seed in (7, 2147483659, 4000000007):
        ind, va, y = sparse_stream.chunked_coo(seed, **sizes)
        assert np.count_nonzero(va) == va.size
        ladders.append(sparse_stream.chunked_batch(
            ind, va, y, 3000, 64, 256, jnp.bfloat16))
    shapes = [[(np.shape(x), np.asarray(x).dtype)
               for x in jax.tree_util.tree_leaves(cb.X.chunks)]
              for cb in ladders]
    assert shapes[0] == shapes[1] == shapes[2]
    assert len({cb.X.chunks[0].n_prefix for cb in ladders}) == 1
    assert not np.array_equal(ladders[0].X.chunks[0].ell_vals[0],
                              ladders[1].X.chunks[0].ell_vals[0])
    assert not np.array_equal(ladders[0].y, ladders[1].y)


# --------------------------------------------- (d) bytes and the readers
def test_chunk_upload_bytes_are_the_leaves():
    X, y = _problem()
    cb = chunk_blocked_ell(make_batch(X, y), 128, d_dense=32,
                           feature_dtype=jnp.bfloat16)
    parts = chunk_upload_bytes(cb)
    c = cb.X.chunks[0]
    assert parts["hot_block"] == 128 * 32 * 2
    assert parts["permutation"] == 2 * 4 * 400
    assert parts["labels_weights_offsets"] == 3 * 4 * 128
    assert parts["ell_tail"] == sum(6 * int(np.prod(v.shape))
                                    for v in c.ell_vals)
    assert parts["total"] == sum(v for k, v in parts.items()
                                 if k != "total")
    assert ladder_bytes(cb) == 4 * parts["total"]


def _ctx(counters=None, spans=None, unit=None, peaks=True):
    return {"peaks": {"hbm_bytes_per_s": 819e9} if peaks else None,
            "trace": {"sections": {"unit": unit} if unit else {}},
            "telemetry": {"counters": counters or {},
                          "span_totals": spans or {}}}


def test_stream_readers_on_hand_made_input(monkeypatch):
    monkeypatch.setattr(stream_link_share, "_device_kind",
                        lambda: "TPU v5 lite")
    counters = {"stream.passes": 21.0, "stream.chunk_uploads": 84.0,
                "stream.upload_bytes": 84 * 4.4e9,
                "stream.stall_seconds": 2.0, "stream.issue_seconds": 28.0}
    spans = {"solve.lbfgs_streamed": 50.0,
             "solve.lbfgs_streamed/stream.pass": 42.0}
    ctx = _ctx(counters, spans, {"busy_s": 4.2, "wall_s": 50.0})
    assert stream_pass_s.read(ctx) == pytest.approx(2.0)
    # 84 chunks of 4.4 GB in 42 s of passes, of the measured 14.15 GB/s
    assert stream_link_share.read(ctx) == pytest.approx(
        100.0 * 84 * 4.4e9 / 42.0 / 14.15e9)
    assert stream_link_share.read(ctx) < 100.0
    # the host's waits: issuing uploads and the ring's own, of the wall
    assert stream_stall_share.read(ctx) == pytest.approx(60.0)
    del counters["stream.issue_seconds"]  # a ring that does not count it
    assert stream_stall_share.read(ctx) == pytest.approx(4.0)
    counters["stream.issue_seconds"] = 28.0
    assert stream_chunk_device_ms.read(ctx) == pytest.approx(50.0)
    # a program without the span or the counters: nothing, no error
    bare = _ctx({"solver.iterations": 10.0},
                {"solve.lbfgs_streamed": 50.0},
                {"busy_s": 4.2, "wall_s": 50.0})
    for reader in (stream_pass_s, stream_link_share, stream_stall_share,
                   stream_chunk_device_ms):
        assert reader.read(bare) is None
    assert stream_link_share.read(_ctx(counters, spans, peaks=False)) is None
    assert stream_stall_share.read(_ctx(counters, spans)) is None
    monkeypatch.setattr(stream_link_share, "_device_kind", lambda: "cpu")
    with pytest.raises(KeyError, match="host-link peak"):
        stream_link_share.read(ctx)


def test_timeline_readers_on_hand_made_input(monkeypatch):
    """A solve of 10 iterations, 21 passes of 4 chunks, 85 uploads."""
    counters = {"stream.passes": 21.0, "stream.chunk_uploads": 84.0}
    spans = {SOLVE: 28.6, PASS: 28.0,
             PASS + "/stream.upload": 25.2,
             "probe/stream.upload": 0.3,  # one no pass encloses
             PASS + "/stream.handout": 1.68,
             PASS + "/stream.dispatch": 0.5,
             SOLVE + "/solve.host_step": 0.5}
    counts = {SOLVE: 1, PASS: 21, PASS + "/stream.upload": 84,
              "probe/stream.upload": 1, PASS + "/stream.handout": 84,
              PASS + "/stream.dispatch": 84,
              SOLVE + "/solve.host_step": 31}
    ctx = _ctx(counters, spans)
    ctx["telemetry"]["span_counts"] = counts
    ctx["results"] = {"unit": [{"steps": 10}]}
    assert stream_upload_call_ms.read(ctx) == pytest.approx(300.0)
    assert stream_handout_wait_ms.read(ctx) == pytest.approx(20.0)
    # (28.0 - 25.2 - 1.68) s over 84 chunks: the upload no pass encloses
    # is not taken off the passes
    assert stream_turnaround_ms.read(ctx) == pytest.approx(1120.0 / 84)
    assert stream_host_step_ms.read(ctx) == pytest.approx(50.0)
    assert (stream_upload_call_ms.read(ctx) * 85 / 84
            + stream_handout_wait_ms.read(ctx)
            + stream_turnaround_ms.read(ctx)) == pytest.approx(
        (28.0 + 0.3) / 84 * 1e3)
    table = {"spans": {"stream.upload": {}}, "idle_s": 21.0,
             "uncovered_s": 0.42}
    monkeypatch.setattr(stream_idle_unattributed_share, "unit_host_spans",
                        lambda rehearse: table)
    assert stream_idle_unattributed_share.read(ctx) == pytest.approx(2.0)
    # the parent's program: `stream.pass` and the solve span alone, no
    # counts in its report, no ring span in its trace — nothing, no error
    bare = _ctx(counters, {SOLVE: 28.6, PASS: 28.0})
    bare["results"] = ctx["results"]
    table = {"spans": {"stream.pass": {}}, "idle_s": 21.0,
             "uncovered_s": 0.4}
    for reader in (stream_upload_call_ms, stream_handout_wait_ms,
                   stream_turnaround_ms, stream_host_step_ms,
                   stream_idle_unattributed_share):
        assert reader.read(bare) is None
    table = None
    assert stream_idle_unattributed_share.read(ctx) is None


def test_benchmark_json_lists_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "glm-sparse10m-stream"
    assert "glm-sparse10m-stream" in {c["name"] for c in spec["configs"]}
    rate = next(m for m in spec["end_to_end"]
                if m["name"] == "rows_iters_per_s")
    assert CELL in rate["workloads"]
    mine = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert mine == {"layout_build_s", "solve_xpass_ms",
                    "solve_xpass_tail_ms", "linesearch_evals_per_iter",
                    "solve_iter_device_ms", "solve_state_ms",
                    "solve_linesearch_ms",
                    "stream_pass_s", "stream_link_share",
                    "stream_stall_share", "stream_chunk_device_ms",
                    "stream_upload_call_ms", "stream_handout_wait_ms",
                    "stream_turnaround_ms", "stream_host_step_ms",
                    "stream_idle_unattributed_share"}
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           f"{name}.py"))
    with open(os.path.join(BENCH, "configs",
                           "glm-sparse10m-stream.json")) as f:
        config = json.load(f)
    assert config["n_rows"] == 4 * config["chunk_rows"] == 8388608
    assert config["architecture"] is None


# ------------------------------- (e) the cell's comparison and its faults
def _config():
    with open(os.path.join(BENCH, "configs",
                           "glm-sparse10m-stream.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def stream_cell(tmp_path_factory):
    """`glm-sparse10m-stream.single` at its rehearse sizes: (traffic
    module, state, the warm-up solve's evidence with the program's probe
    readings in it), as `benchmark/run.py` builds them."""
    from benchmark.traffic import glm_stream_solve

    config = _config()
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        params = json.load(f)["params"]
    config = {**config, **config["rehearse"]}
    state = glm_stream_solve.setup(
        config, params, 2147483659,
        {"shared": str(tmp_path_factory.mktemp("pattern"))})
    evidence = glm_stream_solve.unit(state, keep=True)["evidence"]
    return glm_stream_solve, state, {
        **evidence, **glm_stream_solve.probe(state, evidence["w"])}


def _fault_none(traffic, state, evidence):
    return state, evidence


def _without_last_chunk(state):
    """The ladder as a ring that drops the last chunk would stream it: that
    chunk's rows carry weight 0."""
    weights = state.batch.weights.copy()
    weights[-state.batch.chunk_rows:] = 0.0
    return dataclasses.replace(
        state, batch=state.batch._replace(weights=weights))


def _fault_dropped_chunk(traffic, state, evidence):
    lost = _without_last_chunk(state)
    solved = traffic.unit(lost, keep=True)["evidence"]
    return state, {**solved, **traffic.probe(lost, solved["w"])}


def _fault_chunk_streamed_twice(traffic, state, evidence):
    """A ring that hands out chunk 0 where chunk 3 was due: the rows of
    chunk 0 twice, those of chunk 3 never."""
    batch = state.batch
    chunks = batch.X.chunks[:-1] + (batch.X.chunks[0],)
    c = batch.chunk_rows
    def twice(v):
        return np.concatenate([v[:-c], v[:c]])
    twice_batch = batch._replace(
        X=dataclasses.replace(batch.X, chunks=chunks), y=twice(batch.y),
        weights=twice(batch.weights), offsets=twice(batch.offsets))
    wrong = dataclasses.replace(state, batch=twice_batch)
    solved = traffic.unit(wrong, keep=True)["evidence"]
    return state, {**solved, **traffic.probe(wrong, solved["w"])}


def _fault_bf16_margins(traffic, state, evidence):
    z = np.asarray(jnp.asarray(evidence["margins"], jnp.float32).astype(
        jnp.bfloat16), np.float64)
    return state, {**evidence, "margins": z}


def _fault_bf16_gradient(traffic, state, evidence):
    g = np.asarray(jnp.asarray(evidence["grad0"], jnp.float32).astype(
        jnp.bfloat16), np.float64)
    return state, {**evidence, "grad0": g}


def _one_step_down(tree):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype), tree)


def _probed_with(traffic, state, evidence, **programs):
    """The sound solve's evidence with the probe's readings taken again
    while `optim.streamed`'s chunk programs of those names are replaced
    (the donated form and the plain one: a backend picks either)."""
    from unittest import mock

    from photon_tpu.optim import streamed

    with contextlib.ExitStack() as stack:
        for name, fn in programs.items():
            stack.enter_context(mock.patch.object(
                streamed, name, jax.jit(fn)))
            stack.enter_context(mock.patch.object(
                streamed, name + "_don", jax.jit(fn)))
        return state, {**evidence, **traffic.probe(state, evidence["w"])}


def _fault_bf16_gradient_program(traffic, state, evidence):
    """The gradient pass at cached margins (40 of a unit's 84 chunk
    programs) one precision step down: its partials come back in bf16. The
    solve only returns another ``w`` for it and every summed loss passes;
    the probe runs THAT program and its first gradient does not."""
    from photon_tpu.optim import streamed

    return _probed_with(
        traffic, state, evidence, _chunk_grad_at_margin=lambda obj, z, b:
        _one_step_down(streamed._chunk_grad_fn(obj, z, b)))


def _fault_bf16_dz_program(traffic, state, evidence):
    """The direction pass (40 of 84) one step down: bf16 dz."""
    from photon_tpu.optim import streamed

    return _probed_with(
        traffic, state, evidence, _chunk_dz_phi=lambda obj, p, z, a, b:
        _one_step_down(streamed._chunk_dz_phi_fn(obj, p, z, a, b)))


def _fault_bf16_init_program(traffic, state, evidence):
    """The first pass's program (4 of 84) one step down: ``fit_init``'s."""
    from photon_tpu.optim import streamed

    return _probed_with(
        traffic, state, evidence, _chunk_init=lambda obj, w, b:
        _one_step_down(streamed._chunk_init_fn(obj, w, b)))


def _fault_ring_repeats_a_chunk(traffic, state, evidence):
    """An upload ring that hands out chunk 2 where chunk 1 was due, in
    every pass. At w = 0 the loss still reads n·log 2 and the sound
    solve's losses are untouched: only readings taken THROUGH the ring
    see it."""
    from unittest import mock

    real = dataset.ChunkedBatch.chunk
    with mock.patch.object(dataset.ChunkedBatch, "chunk",
                           lambda self, i: real(self, 2 if i == 1 else i)):
        return state, {**evidence, **traffic.probe(state, evidence["w"])}


def _fault_fp8_storage(traffic, state, evidence):
    """A ladder that STORES its hot blocks one step down (float8_e4m3
    where the configuration says bfloat16), computed with as before."""
    def low(c):
        return dataclasses.replace(c, dense=np.asarray(c.dense).astype(
            jnp.float8_e4m3fn).astype(c.dense.dtype))
    batch = state.batch
    stored = dataclasses.replace(state, batch=batch._replace(
        X=dataclasses.replace(batch.X, chunks=tuple(
            low(c) for c in batch.X.chunks))))
    return state, {**evidence, **traffic.probe(stored, evidence["w"])}


def _fault_uncounted_upload(traffic, state, evidence):
    """A pass that consumed three chunks where the ladder has four."""
    c = dict(evidence["counters"])
    c["stream.chunk_uploads"] -= 1
    c["stream.upload_bytes"] -= state.facts["chunk_bytes"]["total"]
    return state, {**evidence, "counters": c}


def _fault_bytes_of_another_ladder(traffic, state, evidence):
    c = dict(evidence["counters"])
    c["stream.upload_bytes"] *= 2  # an f32 ladder's hot blocks, say
    return state, {**evidence, "counters": c}


def _fault_risen_loss(traffic, state, evidence):
    history = np.array(evidence["history"], np.float64)
    history[3] = history[2] * 1.01
    return state, {**evidence, "history": history}


@pytest.mark.parametrize("fault,refused_by", [
    (_fault_none, None),
    (_fault_dropped_chunk, {"loss0_rel", "final_rel", "grad0_rel"}),
    (_fault_chunk_streamed_twice, {"final_rel", "margin_rel", "grad0_rel"}),
    (_fault_bf16_margins, {"margin_rel"}),
    (_fault_bf16_gradient, {"grad0_rel"}),
    (_fault_fp8_storage, {"margin_rel", "grad0_rel"}),
    (_fault_bf16_gradient_program, {"grad0_rel"}),
    (_fault_bf16_dz_program, {"margin_rel"}),
    (_fault_bf16_init_program, None),
    (_fault_ring_repeats_a_chunk, {"margin_rel", "grad0_rel"}),
    (_fault_uncounted_upload, None),
    (_fault_bytes_of_another_ladder, None),
    (_fault_risen_loss, {"monotone"}),
], ids=["sound", "dropped_chunk", "chunk_streamed_twice", "bf16_margins",
        "bf16_gradient", "fp8_storage", "bf16_gradient_program",
        "bf16_dz_program", "bf16_init_program", "ring_repeats_a_chunk",
        "uncounted_upload",
        "bytes_of_another_ladder", "risen_loss"])
def test_cell_comparison_refuses_planted_faults(stream_cell, fault,
                                                refused_by):
    """`glm_stream_solve.check` passes the sound solve and refuses each
    planted fault by the limit that is there for it: a chunk dropped or
    streamed in another's place by the losses and the first gradient, a
    precision step lost in storage or in either pass by the margins or the
    gradient, a pass that did not consume the ladder by the program's own
    counters — and, planted in the PROGRAMS the window runs and not in
    the numbers: each of the three chunk programs one step down, and a
    ring that repeats a chunk, none of which moves a summed loss of the
    sound solve. In every run its own three controls are refused: the lost
    chunk by n·log 2, the final loss and the first gradient; the unrounded
    values and the reference one step down by the margins and the first
    gradient, with an order of room — neither by the summed loss."""
    traffic, state, probed = stream_cell
    verdict = traffic.check(*fault(traffic, state, probed))
    controls = verdict["controls"]
    assert set(controls) == {"lost_chunk", "unrounded", "lower_precision"}
    if fault in (_fault_none, _fault_risen_loss, _fault_uncounted_upload,
                 _fault_bytes_of_another_ladder):
        assert verdict["controls_refused"]
        assert {"loss0_rel", "final_rel", "grad0_rel"} <= set(
            controls["lost_chunk"]["refused_by"])
        assert controls["lost_chunk"]["loss0_rel"] == pytest.approx(
            1 / 3, rel=1e-3)
        for name in ("unrounded", "lower_precision"):
            assert {"margin_rel", "grad0_rel"} <= set(
                controls[name]["refused_by"])
            assert controls[name]["margin_rel"] > 8 * traffic.MARGIN_RTOL
            assert controls[name]["grad0_rel"] > 8 * traffic.GRAD0_RTOL
        assert "final_rel" not in controls["unrounded"]["refused_by"]
        assert "final_rel" not in controls["lower_precision"]["refused_by"]
    if fault is _fault_none:
        assert verdict["ok"] and not verdict["fit"]["refused_by"]
        assert verdict["fit_init"]["ok"]
        assert verdict["fit_init"]["margin_rel"] < traffic.MARGIN_RTOL / 16
        assert verdict["fit_init"]["grad0_rel"] < traffic.GRAD0_RTOL / 16
        assert verdict["stream"]["ok"] and verdict["memory"]["ok"]
        assert verdict["stream"]["passes"] == 21
        assert verdict["stream"]["chunk_uploads"] == 84
        assert verdict["fit"]["margin_rel"] < traffic.MARGIN_RTOL / 16
        assert verdict["fit"]["grad0_rel"] < traffic.GRAD0_RTOL / 16
    elif fault in (_fault_uncounted_upload, _fault_bytes_of_another_ladder):
        assert verdict["fit"]["ok"] and not verdict["stream"]["ok"]
        assert not verdict["ok"]
    elif fault is _fault_bf16_init_program:
        assert verdict["fit"]["ok"] and not verdict["ok"]
        assert set(verdict["fit_init"]["refused_by"]) == {"margin_rel",
                                                          "grad0_rel"}
    else:
        assert not verdict["ok"]
        assert refused_by <= set(verdict["fit"]["refused_by"])
        in_programs = (_fault_bf16_gradient_program, _fault_bf16_dz_program,
                       _fault_ring_repeats_a_chunk)
        if fault in in_programs + (_fault_bf16_margins, _fault_bf16_gradient,
                                   _fault_fp8_storage):
            assert not {"loss0_rel", "final_rel", "monotone"} & set(
                verdict["fit"]["refused_by"])
        if fault in in_programs[:2]:  # one program each: the others sound
            assert set(verdict["fit"]["refused_by"]) == refused_by
            assert verdict["fit_init"]["ok"]


def test_memory_verdict_refuses_a_resident_data_set(stream_cell,
                                                    monkeypatch):
    """On a chip the peak has to lie between a quarter of its memory and
    the ladder's bytes; a backend without memory stats is not judged."""
    from benchmark.lib import harness, peaks

    traffic, state, _ = stream_cell
    assert traffic.memory_verdict(state)["ok"]  # the CPU reports none
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(peaks.DEVICE_PEAKS, kind, {"hbm_bytes": 16 * 2 ** 30})
    big = dataclasses.replace(
        state, facts={**state.facts, "ladder_bytes": 17.6e9})
    for peak, ok in ((9.4e9, True), (3.0e9, False), (17.6e9, False)):
        monkeypatch.setattr(harness, "memory_peak_bytes", lambda jax: peak)
        assert traffic.memory_verdict(big)["ok"] is ok


def test_probe_refuses_a_whole_block_build(monkeypatch, tmp_path):
    """A program that builds the ladder's hot block whole in float32 and
    recasts it afterwards (b6cea34's `chunk_blocked_ell`) is refused by
    the probe's message; the piece-by-piece build passes it."""
    from benchmark.traffic import glm_stream_solve

    config = _config()
    reading = glm_stream_solve.probe_piece_by_piece_build(
        config, str(tmp_path))
    assert reading["probe_blocks_beside"] < glm_stream_solve.PROBE_BLOCKS / 2
    monkeypatch.setattr(dataset, "chunk_blocked_ell",
                        _recast_whole_block_ladder)
    with pytest.raises(SystemExit, match="piece by piece.*8.0 chunks"):
        glm_stream_solve.probe_piece_by_piece_build(config, str(tmp_path))


def _pinned_and_plain(monkeypatch, n_shards=1):
    """The same rows as a chunk ladder with numpy blocks and as one whose
    hot block an accelerator would keep in pinned host memory (forced
    here; the CPU runtime has the memory kind), in pieces of 300 rows: a
    1024-row chunk is three whole pieces and a short one."""
    from photon_tpu.data import dataset

    rng = np.random.default_rng(7)
    n, k, d = 4096 + 512, 8, 3000
    ind = rng.integers(0, d, (n, k)).astype(np.int32)
    ind[:, :3] = rng.integers(0, 40, (n, 3))
    va = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    batch = dataset.make_batch(SparseRows(ind, va, d), y)

    def ladder():
        return dataset.chunk_blocked_ell(batch, 1024, d_dense=128,
                                         feature_dtype=jnp.bfloat16,
                                         n_shards=n_shards)
    plain = ladder()
    monkeypatch.setattr(dataset, "_pins_host_blocks", lambda: True)
    monkeypatch.setattr(dataset, "_UPLOAD_PIECE_BYTES", 300 * 128 * 2)
    return plain, ladder()


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and a.tobytes() == b.tobytes())


def test_pinned_ladder_is_the_numpy_ladder(monkeypatch):
    """A hot block kept in pinned host memory holds the numpy block's
    bytes, counts the same bytes a chunk, uploads to the same device
    chunk, slices by whole pieces only, and is freed on demand."""
    from photon_tpu.data import dataset
    from photon_tpu.data.matrix import PinnedRows

    plain, pinned = _pinned_and_plain(monkeypatch)
    assert dataset._pins_host_blocks() is True
    block = pinned.X.chunks[2].dense
    assert isinstance(block, PinnedRows)
    assert isinstance(plain.X.chunks[2].dense, np.ndarray)
    assert [r0 for r0, _ in block.pieces()] == [0, 300, 600, 900]
    assert {p.sharding.memory_kind for _, p in block.pieces()} == {
        "pinned_host"}
    for i in range(plain.n_chunks):
        a = jax.tree_util.tree_leaves(plain.X.chunks[i])
        b = jax.tree_util.tree_leaves(pinned.X.chunks[i])
        assert len(a) == len(b)
        assert all(_same_bits(u, v) for u, v in zip(a, b))
    assert pinned.chunk_nbytes() == plain.chunk_nbytes()
    assert pinned.X.nbytes() == plain.X.nbytes()
    up_a = dataset.device_put_in_pieces(plain.chunk(1))
    up_b = dataset.device_put_in_pieces(pinned.chunk(1))
    assert up_b.X.dense.sharding.memory_kind == "device"
    assert all(_same_bits(u, v) for u, v in zip(
        jax.tree_util.tree_leaves(up_a), jax.tree_util.tree_leaves(up_b)))
    with pytest.raises(ValueError, match="cut a pinned piece"):
        block[10:20]
    assert block[300:900].shape == (600, 128)
    block.delete()
    assert all(p.is_deleted() for _, p in block.pieces())


def test_streamed_solve_from_a_pinned_ladder(monkeypatch):
    """`train_glm` streams a pinned ladder to the numpy ladder's result,
    bit for bit: same values, same shapes, same programs."""
    plain, pinned = _pinned_and_plain(monkeypatch)
    cfg = OptimizerConfig(max_iters=5, tolerance=0.0, reg=l2(),
                          reg_weight=1e-3, history=5)
    _, a = train_glm(plain, TaskType.LOGISTIC_REGRESSION, cfg)
    _, b = train_glm(pinned, TaskType.LOGISTIC_REGRESSION, cfg)
    assert float(a.value) == float(b.value)
    assert np.abs(np.asarray(a.w) - np.asarray(b.w)).max() == 0.0
    assert _same_bits(a.loss_history, b.loss_history)


def test_mesh_ladder_and_cpu_keep_numpy_blocks(monkeypatch):
    """Only a one-device ladder on an accelerator pins: a mesh ladder's
    chunks are cut per device slot from numpy, and a CPU backend's device
    memory is host memory."""
    from photon_tpu.data import dataset

    assert dataset._pins_host_blocks() is False  # this is the CPU
    _, meshed = _pinned_and_plain(monkeypatch, n_shards=2)
    assert isinstance(meshed.X.chunks[0].dense, np.ndarray)


def test_cell_rehearses_to_its_rehearsal_line():
    """`benchmark/run.py --rehearse` runs the cell's whole control flow —
    probe, set-up, warm-up, window, check — on the CPU."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "0.5", "--trace", "0",
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["event"] == "rehearsal" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert {"rows_iters_per_s", "setup_s"} <= set(last["metric_names"])


def test_cell_rehearses_traced_with_its_timeline_metrics():
    """`--rehearse --trace 1` reads the five timeline metrics from the
    program's spans (the CPU's stand-in device for the idle split) and
    prints the two log lines they come with; the idle split adds up."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483693", "--seconds", "0.5", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["event"] == "rehearsal" and last["correct"] is True
    assert {"stream_upload_call_ms", "stream_handout_wait_ms",
            "stream_turnaround_ms", "stream_host_step_ms",
            "stream_idle_unattributed_share", "stream_pass_s"} <= set(
        last["metric_names"])
    by_event = {line["event"]: line for line in lines}
    assert set(NEW_SPANS) <= set(by_event["host_spans"]["spans"])
    idle = by_event["idle_by_span"]
    assert sum(idle["idle_by_span"].values()) == pytest.approx(
        idle["idle_s"])
    assert idle["frames"] == ["solve.lbfgs_streamed", "stream.pass"]

