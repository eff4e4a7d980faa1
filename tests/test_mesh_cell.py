"""The first mesh cell (PR 31, `glm-sparse10m-mesh4.single`): a sharded
blocked-ELL batch whose hot block is built shard by shard on the devices
that keep it, the `mesh.psum` scope and byte counter of the sharded solve,
the benchmark's shard-preserving generator, the three `mesh_*` readers, and
the cell's comparison with its controls — at tiny sizes on four of the
eight virtual CPU devices.
"""
import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.gen import reference, sparse_mesh
from benchmark.layer_metrics import (mesh_psum_ici_share, mesh_psum_ms,
                                     mesh_shard_padding)
from benchmark.lib import scope_reduce
from benchmark.lib.psum_bytes import ring_all_reduce_sent_bytes
from photon_tpu import telemetry
from photon_tpu.analysis import hlo_all_reduce_count
from photon_tpu.data import matrix
from photon_tpu.data.dataset import (cast_features, make_batch,
                                     shard_blocked_ell_batch)
from photon_tpu.data.matrix import (SparseRows, shard_blocked_ell,
                                    to_blocked_ell)
from photon_tpu.models.training import (_contract_sharded_vg, make_objective,
                                        place_sharded_batch, train_glm)
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.regularization import l2
from photon_tpu.parallel.mesh import make_mesh

LOGISTIC = TaskType.LOGISTIC_REGRESSION
S = 4
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(n_devices=S)


def _problem(n=256, d=400, k=7, seed=11, ragged=False):
    """Zipf columns, a tenth of the slots empty. ``ragged``: only shard 0
    has rows at the widest ELL width, so the other shards' last bucket is
    all padding."""
    rng = np.random.default_rng(seed)
    ind = ((rng.zipf(1.4, size=(n, k)) - 1) % (d - 1)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    val[rng.uniform(size=(n, k)) < 0.1] = 0.0
    if ragged:
        wide = 2 * k
        ind = np.concatenate([ind, np.zeros((n, wide - k), np.int32)], axis=1)
        val = np.concatenate([val, np.zeros((n, wide - k), np.float32)],
                             axis=1)
        ind[:3, k:] = d - 2 - np.arange(wide - k)  # cold columns, shard 0
        val[:3, k:] = 1.0
    return SparseRows(ind, val, d)


def _same_leaves(a, b):
    la, lb = jax.tree_util.tree_flatten(a), jax.tree_util.tree_flatten(b)
    assert la[1] == lb[1]  # structure and every meta field
    for x, y in zip(la[0], lb[0]):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y)


# ----------------------------------------- (a) the shard-by-shard build
@pytest.mark.parametrize("chunked", [False, True], ids=["whole", "chunked"])
@pytest.mark.parametrize("ragged", [False, True],
                         ids=["full_last_bucket", "ragged_last_bucket"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_shard_by_shard_build_is_the_whole_block_build(mesh4, monkeypatch,
                                                       dtype, ragged,
                                                       chunked):
    """Built on the mesh's devices, the hot block is bit for bit the whole
    block as ONE device scatters it (the one-device builders' front half:
    a row that repeats a column adds its repeats in f32, in the same
    order), every other leaf what the host-built layout has, and the hot
    block comes back with one addressable shard a device — also where a
    shard's rows take several scatter chunks."""
    X = _problem(ragged=ragged)
    whole = dataclasses.replace(
        shard_blocked_ell(X, S, 32),
        dense=matrix._hot_cold_split(X, 32, dtype)[0])
    if chunked:
        monkeypatch.setattr(matrix, "_SCATTER_CHUNK_ELEMS", 32 * 24)
    built = shard_blocked_ell(X, S, 32, device_dense_dtype=dtype, mesh=mesh4)
    _same_leaves(built, whole)
    if ragged:
        last = np.asarray(built.ell_vals[-1])
        assert last[0].any() and not last[1:].any()
    shards = built.dense.addressable_shards
    assert len(shards) == S
    assert [s.device for s in shards] == list(mesh4.devices.flat)
    assert all(s.data.shape == (256 // S, 32) for s in shards)
    # the solves' own placement finds it where it belongs
    placed = place_sharded_batch(
        make_batch(built, np.zeros(256, np.float32)), mesh4)
    assert placed.X.dense is built.dense or all(
        a.data.unsafe_buffer_pointer() == b.data.unsafe_buffer_pointer()
        for a, b in zip(placed.X.dense.addressable_shards, shards))


def _digest(layout) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(layout):
        a = np.asarray(leaf)
        if a.dtype == jnp.bfloat16:
            a = a.view(np.uint16)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("build,digest", [
    (lambda X, mesh: shard_blocked_ell(X, S, 32),
     "d076d1d752fe436beb18333df2cde881bfacf64a95ab5b4795f9e3365b44a243"),
    (lambda X, mesh: shard_blocked_ell(
        X, S, 32, device_dense_dtype=jnp.bfloat16, mesh=mesh),
     "495925f82c76bf766d5f61e0765d7dbce35ed1d3a11a6b06d48b36897b517fcb"),
    (lambda X, mesh: to_blocked_ell(X, 32, device_dense_dtype=jnp.bfloat16),
     "842826d39bc1ec878f10cc9b85a7e1775d62f899c4ee0c6acb44c83a40d8c574"),
    (lambda X, mesh: to_blocked_ell(X, 32),
     "beb6b5223ef21886359ca47a3342f84369e87af9a4ba31bcccfc47a87efab1a8"),
], ids=["sharded_host", "sharded_device", "one_device", "one_host"])
def test_layout_leaves_are_the_parents(mesh4, build, digest):
    """The four builds of one set of rows, byte for byte, as the tree of
    PR 32 laid them: the digests were retaken when the buckets' widths
    went from powers of two to the width ladder's rungs (PR 31 had taken
    them from a checkout of 19d3b58, to show that a host pass without an
    (n, k) int64 row-id array, and a hot block built shard by shard, lay
    what the parent laid). A pin, not a reference: what the leaves MEAN is
    held to float64 by tests/test_blocked_ell.py's wide-bucket matrix."""
    assert _digest(build(_problem(), mesh4)) == digest


def test_a_mesh_keeps_one_shard_a_device(mesh4):
    with pytest.raises(ValueError, match="one shard a device"):
        shard_blocked_ell(_problem(), 8, 32, device_dense_dtype=jnp.float32,
                          mesh=mesh4)


def test_a_sharded_device_build_needs_its_mesh():
    """There is no route that assembles a sharded layout's device-built
    hot block on one device."""
    with pytest.raises(ValueError, match="hand in the mesh"):
        shard_blocked_ell(_problem(), S, 32, device_dense_dtype=jnp.float32)
    with pytest.raises(ValueError, match="hand in the mesh"):
        shard_blocked_ell_batch(
            make_batch(_problem(), np.zeros(256, np.float32)), S, d_dense=32,
            device_dense_dtype=jnp.bfloat16)


def test_shard_build_reports_its_padding():
    """`layout.shard_bytes_real` is each shard laid out to its own shapes,
    `_padded` the common shapes; a ragged last bucket shows in the ratio."""
    ratios = {}
    for ragged in (False, True):
        with telemetry.run("t") as run:
            X = shard_blocked_ell(_problem(ragged=ragged), S, 32)
            report = run.report_compact()
        c = report["counters"]
        slots = (sum(int(np.prod(v.shape)) for v in X.ell_vals)
                 + sum(int(np.prod(v.shape)) for v in X.bucket_vals))
        assert c["layout.shard_bytes_padded"] == 8 * slots
        assert 0 < c["layout.shard_bytes_real"] <= 8 * slots
        assert any(k.endswith("layout.shard_build")
                   for k in report["span_totals"])
        ratios[ragged] = (c["layout.shard_bytes_padded"]
                          / c["layout.shard_bytes_real"])
    assert ratios[True] > ratios[False] >= 1.0


# ------------------------------------------------- (b) the sharded solve
def _np_objective(X: SparseRows, y, w, lam, dtype=np.float32):
    va = reference.stored(np.asarray(X.values), dtype)
    z = np.einsum("nk,nk->n", va, np.asarray(w, np.float64)[X.indices])
    return reference.np_logistic_objective(z, y, np.asarray(w, np.float64),
                                           lam)


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16"])
def test_mesh_solve_over_the_shard_built_batch(mesh4, bf16):
    """`train_glm(mesh=)` over the shard-built batch: its final loss is the
    float64 numpy objective of the `w` it returns and the one-device
    `to_blocked_ell` solve's; `mesh.psum_bytes` is the gradient's bytes ×
    (iterations + 1)."""
    X = _problem(n=512, seed=3)
    rng = np.random.default_rng(3)
    y = (rng.uniform(size=512) < 0.4).astype(np.float32)
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    cfg = OptimizerConfig(max_iters=12, tolerance=0.0, reg=l2(),
                          reg_weight=0.3, history=5)
    batch = shard_blocked_ell_batch(make_batch(X, y), S, d_dense=32,
                                    device_dense_dtype=dtype, mesh=mesh4)
    one = make_batch(to_blocked_ell(X, 32, device_dense_dtype=dtype), y)
    if bf16:
        batch, one = cast_features(batch), cast_features(one)
    with telemetry.run("t") as run:
        model, res = train_glm(batch, LOGISTIC, cfg, mesh=mesh4)
        counters = run.report_compact()["counters"]
    _, ref = train_glm(one, LOGISTIC, cfg)
    w = np.asarray(model.coefficients.means)
    f64 = _np_objective(X, y, w, 0.3, dtype)
    rtol = reference.LOSS_RTOL if bf16 else 1e-5
    assert abs(float(res.value) - f64) <= rtol * f64
    assert abs(float(res.value) - float(ref.value)) <= rtol * f64
    assert int(res.iterations) == 12
    assert counters["mesh.psum_bytes"] == 4.0 * X.n_features * (12 + 1)
    assert counters["solver.iterations"] == 12


# ------------------------------------------------- (c) the generator
def test_generator_keeps_every_shards_rows_and_shapes(tmp_path):
    sizes = dict(rows=1024, features=3000, nnz=8, zipf=1.4, hot_signal=300,
                 n_shards=S, cache_dir=str(tmp_path))
    a = sparse_mesh.sharded_coo(7, **sizes)
    b = sparse_mesh.sharded_coo(2147483659, **sizes)
    assert not np.array_equal(a[0], b[0]) and not np.array_equal(a[1], b[1])
    n_loc = 1024 // S

    def rows_of(ind, s):
        return np.unique(ind[s * n_loc:(s + 1) * n_loc], axis=0)

    for s in range(S):
        assert np.array_equal(rows_of(a[0], s), rows_of(b[0], s))
    assert not np.array_equal(rows_of(a[0], 0), rows_of(a[0], 1))
    layouts = [shard_blocked_ell(SparseRows(ind, va, 3000), S, 64)
               for ind, va, _ in (a, b)]
    shapes = [[np.shape(x) for x in jax.tree_util.tree_leaves(L)]
              for L in layouts]
    assert shapes[0] == shapes[1]
    assert layouts[0].n_prefix == layouts[1].n_prefix
    assert not np.array_equal(layouts[0].ell_vals[0], layouts[1].ell_vals[0])
    # a permutation of ALL rows (gen/sparse.py's) does change them
    from benchmark.gen import sparse

    whole = [shard_blocked_ell(SparseRows(
        *sparse.sparse_coo(seed, 1024, 3000, 8, 1.4, 300,
                           str(tmp_path))[:2], 3000), S, 64)
        for seed in (7, 2147483659)]
    assert ([np.shape(x) for x in jax.tree_util.tree_leaves(whole[0])]
            != [np.shape(x) for x in jax.tree_util.tree_leaves(whole[1])])


def test_generator_draws_no_exact_zero(tmp_path):
    """An exact 0.0 among the f32 draws would leave the layout (and change
    its shapes with the seed): the generator nudges it."""
    block = np.array([[0.0, 1.5, -0.0], [2.0, 0.0, -3.0]], np.float32)
    out = sparse_mesh.never_zero(block)
    assert out is block and np.count_nonzero(out) == out.size
    assert np.array_equal(out[[0, 1], [1, 2]], np.float32([1.5, -3.0]))
    ind, va, _ = sparse_mesh.sharded_coo(
        11, 256, 500, 8, 1.4, 50, S, cache_dir=str(tmp_path))
    assert np.count_nonzero(va) == va.size


# ------------------------------------- (d) one all-reduce, under mesh.psum
def test_sharded_evaluation_is_one_all_reduce_under_mesh_psum(mesh4):
    X = _problem()
    batch = place_sharded_batch(shard_blocked_ell_batch(
        make_batch(X, np.zeros(256, np.float32)), S, d_dense=32,
        device_dense_dtype=jnp.float32, mesh=mesh4), mesh4)
    obj = make_objective(LOGISTIC, OptimizerConfig(reg=l2(), reg_weight=0.5),
                         X.n_features, axis_name="data",
                         intercept_index=batch.X.last_col_pos)
    text = jax.jit(_contract_sharded_vg(batch, mesh4)).lower(
        obj, batch, jnp.zeros((X.n_features,), jnp.float32)
    ).compile().as_text()
    assert hlo_all_reduce_count(text) == 1
    line = next(ln for ln in text.splitlines()
                if " all-reduce(" in ln or " all-reduce-start(" in ln)
    op_name = re.search(r'op_name="([^"]*)"', line).group(1)
    assert "mesh.psum" in op_name.split("/")


# --------------------------------------------- (e) bytes and the readers
def test_ring_all_reduce_bytes():
    assert ring_all_reduce_sent_bytes(40e6, 4) == 60e6
    assert ring_all_reduce_sent_bytes(40e6, 1) == 0.0
    assert ring_all_reduce_sent_bytes(64.0, 64) == 126.0
    with pytest.raises(ValueError):
        ring_all_reduce_sent_bytes(1.0, 0)


def _ctx(counters=None, build=None, steps=(40, 40)):
    class _State:
        facts = {"build_counters": build or {}}

    return {"config": {"n_shards": 4}, "peaks": {"hbm_bytes_per_s": 819e9},
            "state": _State(), "results": {"unit": [{"steps": s}
                                                    for s in steps]},
            "telemetry": {"counters": counters or {}}}


def test_mesh_readers_on_hand_made_input(monkeypatch):
    table = {"scopes": {"mesh.psum": 0.12, "xpass.fwd.tail": 3.0},
             "chains": {"lbfgs.update>mesh.psum": 0.10,
                        "lbfgs.linesearch>objective.loss>mesh.psum": 0.02,
                        "xpass.fwd>xpass.fwd.tail": 3.0}}
    for module in (scope_reduce, mesh_psum_ms, mesh_psum_ici_share):
        monkeypatch.setattr(module, "unit_scopes", lambda: table)
    monkeypatch.setattr(mesh_psum_ici_share, "_device_kind",
                        lambda: "TPU v5 lite")
    ctx = _ctx(counters={"mesh.psum_bytes": 82 * 40e6})
    assert mesh_psum_ms.read(ctx) == pytest.approx(0.12 / 80 * 1e3)
    # 82 all-reduces of 40 MB: 60 MB sent a chip each, in 0.12 s, of 200 GB/s
    assert mesh_psum_ici_share.read(ctx) == pytest.approx(
        100.0 * 82 * 60e6 / 0.12 / 200e9)
    assert mesh_shard_padding.read(_ctx(build={
        "layout.shard_bytes_real": 200.0,
        "layout.shard_bytes_padded": 250.0})) == 1.25
    # a program without the scope or the counters: nothing, no error
    bare = {"scopes": {"xpass.fwd.tail": 3.0},
            "chains": {"xpass.fwd>xpass.fwd.tail": 3.0}}
    for module in (scope_reduce, mesh_psum_ms, mesh_psum_ici_share):
        monkeypatch.setattr(module, "unit_scopes", lambda: bare)
    assert mesh_psum_ms.read(ctx) is None
    assert mesh_psum_ici_share.read(ctx) is None
    assert mesh_psum_ici_share.read(_ctx()) is None
    assert mesh_shard_padding.read(_ctx()) is None
    for module in (scope_reduce, mesh_psum_ms, mesh_psum_ici_share):
        monkeypatch.setattr(module, "unit_scopes", lambda: None)
    assert mesh_psum_ms.read(ctx) is None
    assert mesh_psum_ici_share.read(ctx) is None
    monkeypatch.setattr(mesh_psum_ici_share, "_device_kind", lambda: "cpu")
    for module in (scope_reduce, mesh_psum_ms, mesh_psum_ici_share):
        monkeypatch.setattr(module, "unit_scopes", lambda: table)
    with pytest.raises(KeyError, match="interconnect peak"):
        mesh_psum_ici_share.read(ctx)


def test_benchmark_json_lists_the_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = "glm-sparse10m-mesh4.single"
    entry = next(w for w in spec["workloads"] if w["name"] == cell)
    assert entry["chips"] == 4
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    mine = {m["name"] for m in spec["per_layer"] if cell in m["workloads"]}
    assert mine == {"layout_build_s", "xpass_eval_ms", "xpass_hbm_share",
                    "solve_iter_device_ms",
                    "solve_xpass_ms", "solve_xpass_tail_ms",
                    "solve_state_ms", "solve_linesearch_ms",
                    "linesearch_evals_per_iter", "mesh_psum_ms",
                    "mesh_psum_ici_share", "mesh_shard_padding"}
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           f"{name}.py"))


# ------------------------------- (f) the cell's comparison and its faults
@pytest.fixture(scope="module")
def mesh_cell(tmp_path_factory):
    """`glm-sparse10m-mesh4.single` at its rehearse sizes: (traffic
    module, state, the warm-up solve's evidence), as `benchmark/run.py`
    builds them."""
    from benchmark.traffic import glm_mesh_solve

    with open(os.path.join(BENCH, "configs",
                           "glm-sparse10m-mesh4.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "workloads",
                           "glm-sparse10m-mesh4.single.json")) as f:
        params = json.load(f)["params"]
    config = {**config, **config["rehearse"]}
    state = glm_mesh_solve.setup(
        config, params, 2147483659,
        {"shared": str(tmp_path_factory.mktemp("pattern"))})
    evidence = glm_mesh_solve.unit(state, keep=True)["evidence"]
    return glm_mesh_solve, state, evidence


@pytest.fixture(scope="module")
def probed(mesh_cell):
    """The warm-up's evidence with the program's probe readings in it."""
    traffic, state, evidence = mesh_cell
    return {**evidence, **traffic.probe(state, evidence["w"])}


def _fault_none(traffic, state, evidence):
    return state, evidence


def _fault_lost_shard(traffic, state, evidence):
    """A program that never sees the last shard's rows (their weights 0):
    its solve, and its first gradient."""
    n_loc = state.rows // state.n_shards
    weights = np.asarray(state.batch.weights).copy()
    weights[-n_loc:] = 0.0
    batch = state.batch._replace(weights=jax.device_put(
        weights, state.batch.weights.sharding))
    lost = dataclasses.replace(state, batch=batch, programs={})
    solved = traffic.unit(lost, keep=True)["evidence"]
    return state, {**solved, **traffic.probe(lost, solved["w"])}


def _fault_bf16_margins(traffic, state, evidence):
    """A program whose forward pass keeps its margins in bf16: the
    program's own margins, rounded once."""
    z = np.asarray(jnp.asarray(evidence["margins"], jnp.float32).astype(
        jnp.bfloat16), np.float64)
    return state, {**evidence, "margins": z}


def _fault_bf16_gradient(traffic, state, evidence):
    """A program whose transposed pass hands its sums back in bf16."""
    g = np.asarray(jnp.asarray(evidence["grad0"], jnp.float32).astype(
        jnp.bfloat16), np.float64)
    return state, {**evidence, "grad0": g}


def _fault_fp8_storage(traffic, state, evidence):
    """A program that STORES its hot block one step down (float8_e4m3
    where the configuration says bfloat16) and computes as before."""
    X = state.batch.X
    dense = X.dense.astype(jnp.float8_e4m3fn).astype(X.dense.dtype)
    low = dataclasses.replace(
        state, programs={},
        batch=state.batch._replace(X=dataclasses.replace(X, dense=dense)))
    return state, {**evidence, **traffic.probe(low, evidence["w"])}


def _fault_wrong_gradient(traffic, state, evidence):
    """A gradient that is right in value and wrong in one tail column."""
    g = evidence["grad0"].copy()
    g[int(np.argmax(np.abs(g[:-1])))] *= -1.0
    return state, {**evidence, "grad0": g}


def _fault_risen_loss(traffic, state, evidence):
    history = np.array(evidence["history"], np.float64)
    history[3] = history[2] * 1.01
    return state, {**evidence, "history": history}


def _fault_unbalanced(traffic, state, evidence):
    """Everything resident on one device beside the others' shares."""
    facts = {**state.facts, "bytes_in_use": [4.0e9, 1.0e9, 1.0e9, 1.0e9]}
    return dataclasses.replace(state, facts=facts), evidence


@pytest.mark.parametrize("fault,refused_by", [
    (_fault_none, None),
    (_fault_lost_shard, {"loss0_rel", "final_rel", "grad0_rel"}),
    (_fault_bf16_margins, {"margin_rel"}),
    (_fault_bf16_gradient, {"grad0_rel"}),
    (_fault_fp8_storage, {"margin_rel", "grad0_rel"}),
    (_fault_wrong_gradient, {"grad0_rel"}),
    (_fault_risen_loss, {"monotone"}),
    (_fault_unbalanced, None),
], ids=["sound", "lost_shard", "bf16_margins", "bf16_gradient",
        "fp8_storage", "wrong_gradient", "risen_loss", "unbalanced"])
def test_cell_comparison_refuses_planted_faults(mesh_cell, probed, fault,
                                                refused_by):
    """`glm_mesh_solve.check` passes the sound solve and refuses each
    planted fault by the limit that is there for it — a precision step
    lost in storage, in the forward or in the transposed pass among them,
    each COMPUTED in that precision, none of which the summed loss sees.
    In every run its own three controls are refused: the lost shard by the
    n·log 2 reading, the final loss and the first gradient; the unrounded
    values and the reference computed in bf16 by the margins and the first
    gradient, with an order of room."""
    traffic, state, _ = mesh_cell
    verdict = traffic.check(*fault(traffic, state, probed))
    controls = verdict["controls"]
    assert set(controls) == {"lost_shard", "unrounded", "lower_precision"}
    if fault in (_fault_none, _fault_risen_loss, _fault_unbalanced):
        assert verdict["controls_refused"]
        assert {"loss0_rel", "final_rel", "grad0_rel"} <= set(
            controls["lost_shard"]["refused_by"])
        assert controls["lost_shard"]["loss0_rel"] == pytest.approx(
            1 / 3, rel=1e-3)
        for name in ("unrounded", "lower_precision"):
            assert {"margin_rel", "grad0_rel"} <= set(
                controls[name]["refused_by"])
            assert controls[name]["margin_rel"] > 8 * traffic.MARGIN_RTOL
            assert controls[name]["grad0_rel"] > 8 * traffic.GRAD0_RTOL
        # what the summed loss alone would have let through
        assert "final_rel" not in controls["unrounded"]["refused_by"]
        assert "final_rel" not in controls["lower_precision"]["refused_by"]
    assert verdict["all_reduces_per_evaluation"] == 1
    assert verdict["hot_block_shards"] == S
    if fault is _fault_none:
        assert verdict["ok"] and not verdict["fit"]["refused_by"]
        assert verdict["fit"]["margin_rel"] < traffic.MARGIN_RTOL / 16
        assert verdict["fit"]["grad0_rel"] < traffic.GRAD0_RTOL / 16
    elif fault is _fault_unbalanced:
        assert verdict["fit"]["ok"] and not verdict["ok"]
        assert verdict["bytes_in_use_spread"] > 0.15
    else:
        assert not verdict["ok"]
        assert refused_by <= set(verdict["fit"]["refused_by"])
        if fault in (_fault_bf16_margins, _fault_bf16_gradient,
                     _fault_fp8_storage, _fault_wrong_gradient):
            assert not {"loss0_rel", "final_rel", "monotone"} & set(
                verdict["fit"]["refused_by"])


def test_reference_keeps_one_entry_a_hot_cell():
    """`reference_blocked.merged`: a row's repeats of a hot column are one
    stored number (the sum), a cold column's repeats stay apart."""
    from benchmark.gen import reference_blocked

    ind = np.array([[5, 2, 5, 9, 9, 2]], np.int32)
    va = np.array([[1.0, 2.0, 4.0, 8.0, 16.0, 32.0]], np.float32)
    is_hot = np.zeros(10, bool)
    is_hot[[2, 5]] = True
    cols, vals = reference_blocked.merged(ind, va, is_hot)
    assert cols.tolist() == [[2, 2, 5, 5, 9, 9]]
    assert vals.tolist() == [[34.0, 0.0, 5.0, 0.0, 8.0, 16.0]]
    scale = np.array([2.0, 0.0, 4.0])
    assert reference_blocked.gradient_error(
        np.array([1.0, 7.0, 2.0]), np.array([3.0, 0.0, 2.0]),
        scale) == pytest.approx(np.sqrt(0.5))


def test_one_shard_xpass_bytes_are_a_chips_share(mesh_cell):
    """What ONE chip moves an evaluation: a quarter of every sharded leaf
    at the padded shapes, the whole of ``w`` and the gradient."""
    from benchmark.lib.xpass_bytes import _nbytes

    traffic, state, _ = mesh_cell
    X = state.batch.X
    parts = state.facts["xpass_bytes"]
    n, d = X.shape
    assert parts["hot_block_twice"] == 2 * _nbytes(X.dense) // S
    assert parts["occ_tail_transposed"] == sum(
        _nbytes(r) + _nbytes(v)
        for r, v in zip(X.bucket_rows, X.bucket_vals)) // S
    assert parts["w_and_gradient"] == 2 * 4 * d
    assert parts["margin_write_read"] == 2 * 4 * n // S
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    name, section = traffic.traced_sections(state)[0]
    assert name == "xpass" and section() == {"evaluations": 10}


def test_probe_refuses_a_whole_block_build(mesh_cell, mesh4, monkeypatch,
                                           tmp_path):
    """A program that takes no mesh (19d3b58's signature), or that hands
    the hot block back on one device, is refused by the probe's message."""
    traffic, state, _ = mesh_cell
    from photon_tpu.data import dataset

    with open(os.path.join(BENCH, "configs",
                           "glm-sparse10m-mesh4.json")) as f:
        config = json.load(f)
    real = dataset.shard_blocked_ell_batch
    monkeypatch.setattr(
        dataset, "shard_blocked_ell_batch",
        lambda batch, n_shards, d_dense=1024, device_dense_dtype=None:
        real(batch, n_shards, d_dense, device_dense_dtype))
    with pytest.raises(SystemExit, match="shard by shard.*takes no mesh"):
        traffic.probe_shard_by_shard_build(config, mesh4, str(tmp_path))
    def on_one_device(batch, n_shards, d_dense=1024, device_dense_dtype=None,
                      mesh=None):
        out = real(batch, n_shards, d_dense)
        return out._replace(X=dataclasses.replace(out.X, dense=jnp.asarray(
            out.X.dense).astype(device_dense_dtype)))

    monkeypatch.setattr(dataset, "shard_blocked_ell_batch", on_one_device)
    with pytest.raises(SystemExit, match="on 1 device"):
        traffic.probe_shard_by_shard_build(config, mesh4, str(tmp_path))
    monkeypatch.undo()
    traffic.probe_shard_by_shard_build(config, mesh4, str(tmp_path))
