"""Compile the repo's Pallas kernels — and count the mesh evaluation's
all-reduces — for a DESCRIBED TPU v5e (`v5e:2x2`), with no chip attached.

Every other kernel test runs Pallas interpret mode, which accepts programs
the chip's compiler refuses. Here each kernel of `photon_tpu/kernels/` and
`photon_tpu/ops/fused.py` goes through the real TPU compiler at the shapes
`chip_smoke.py`'s `glm` and `serve` phases really have, without
``interpret``:

- the ones that compile are pinned as compiling;
- the ones the compiler refuses are pinned as refused, message and all, and
  `kernels.active()` keeps ``auto`` off them (PERF.md records the list).
  When a later PR repairs one, its case here flips from "refused" to
  "compiles" in the same diff that puts it back into ``auto``.

A compile that passes is not a chip run: nothing here says anything about
results or times.

The topology is described inside a module-scoped fixture (never at import:
only the xdist worker that runs this file may load libtpu), and the
persistent compilation cache is off around the compiles (an executable for
a described chip can be written to the cache but not read back).
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

# The blocked-ELL layout of chip_smoke.py's `glm` phase — bench.py's sparse
# problem at seed 0, 2^21 rows, 10M features, 1024-column bf16 hot block —
# as `to_blocked_ell` built it in this sandbox: row count n, tail length
# U = n_prefix - d_sel, the five pow2 ELL width buckets (rows, W) and the
# twelve occurrence buckets (columns, k). The smoke prints the same summary
# on the chip's host, where the ELL row counts came out a few rows
# different (680022 for 680032, ...; same ladder, same U, same occurrence
# buckets) — shape structure, not the last digit, is what the compiler
# sees.
GLM_N = 1 << 21
GLM_U = 540082 - 1024
GLM_ELL = ((680032, 1), (560138, 2), (410297, 4), (43626, 8), (53, 16))
GLM_BUCKETS = ((380559, 1), (58680, 2), (37970, 4), (23819, 8),
               (15253, 16), (9252, 32), (5701, 64), (3441, 128),
               (2104, 256), (1290, 512), (781, 1024), (208, 2048))
GLM_FEATURES = 10_000_000
# the serve phase's store: the flagship GAME model (benches/_flagship_data)
SERVE_D_FIXED, SERVE_D_RE = 33, 4
SERVE_USERS, SERVE_ITEMS = 100_000, 50_000
SERVE_RUNGS = (8, 256)  # smallest and largest rung of the default ladder

# Mosaic's gather rule takes a 2-D operand whose shape equals the indices'
# and the output's (an in-vreg `take_along_axis`); a table gather is neither
_GATHER_1D = "Only 2D gather is supported"
_GATHER_LANES = "Shape mismatch in input, indices and output"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_mode(monkeypatch):
    """The product's mode: conftest turns Pallas interpret mode on for the
    suite; these tests compile, so turn it back off."""
    from photon_tpu import kernels as K

    monkeypatch.setattr(K, "_INTERPRETED", False)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


def _compile(fn, *shapes, precision="default"):
    # conftest pins JAX_DEFAULT_MATMUL_PRECISION=highest for the CPU
    # suite's numeric comparisons; the product runs at jax's default
    with jax.default_matmul_precision(precision):
        return jax.jit(fn).lower(*shapes).compile()


def _vec(rows, G):
    return (rows, G) if G else (rows,)


# ------------------------------------------------------------- ops/fused.py
def _compile_fused(one_chip, n, d, dtype, precision="default"):
    from photon_tpu.ops.fused import _fused_call
    from photon_tpu.ops.losses import TaskType

    def fn(X, w, y, wt, off):
        return _fused_call(TaskType.LOGISTIC_REGRESSION, X, w, y, wt, off,
                           interpret=False)

    rows = _shape((n,), "float32", one_chip)
    return _compile(fn, _shape((n, d), dtype, one_chip),
                    _shape((d,), "float32", one_chip), rows, rows, rows,
                    precision=precision)


@pytest.mark.parametrize("n,d,dtype", [
    (524288, 256, "float32"),     # bench.py's dense problem
    (1 << 21, 1024, "bfloat16"),  # the glm phase's hot block
])
def test_fused_objective_compiles(one_chip, n, d, dtype):
    """ops/fused.py's compiled branch (manual double-buffered DMA) — a
    different kernel from the interpreted one the CPU tests run."""
    compiled = _compile_fused(one_chip, n, d, dtype)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_objective_highest_precision_refused(one_chip):
    """REFUSED at ``highest`` matmul precision: the multi-pass f32 dots'
    temporaries push the kernel's two 4 MB X slots past the 16 MB scoped
    VMEM limit (`_X_CHUNK_BYTES` budgets for the default precision only).
    Recorded as a limit of the kernel, not repaired here."""
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="exceeded scoped vmem limit"):
        _compile_fused(one_chip, 524288, 256, "float32",
                       precision="highest")


# ------------------------------------------------- kernels/blocked_ell.py
def _tail_fused(G, s):
    from photon_tpu.kernels.blocked_ell import _tail_call

    args = [_shape((GLM_N,), "int32", s), _shape(_vec(GLM_U, G), "float32", s)]
    for shape in GLM_ELL:
        args += [_shape(shape, "int32", s), _shape(shape, "bfloat16", s)]
    return _tail_call(len(GLM_ELL), bool(G), False, GLM_N, G), args


def _tail_tiled(G, s):
    from photon_tpu.kernels.blocked_ell import _tiled_tail_call

    r_b, W = GLM_ELL[2]
    T = 256
    R = -(-r_b // T) * T
    return (_tiled_tail_call(W, T, R // T, bool(G), False, GLM_U, G),
            [_shape(_vec(GLM_U, G), "float32", s),
             _shape((R, W), "int32", s), _shape((R, W), "bfloat16", s)])


def _rmatvec_fused(G, s):
    from photon_tpu.kernels.blocked_ell import _rmatvec_call

    args = [_shape(_vec(GLM_N, G), "float32", s)]
    for shape in GLM_BUCKETS:
        args += [_shape(shape, "int32", s), _shape(shape, "bfloat16", s)]
    return (_rmatvec_call(len(GLM_BUCKETS), bool(G), False, False, GLM_U, G),
            args)


def _rmatvec_tiled(G, s):
    from photon_tpu.kernels.blocked_ell import _tiled_rmatvec_call

    c_b, kk = GLM_BUCKETS[7]
    T = 256
    C = -(-c_b // T) * T
    return (_tiled_rmatvec_call(kk, T, C // T, bool(G), False, False,
                                GLM_N, G),
            [_shape(_vec(GLM_N, G), "float32", s),
             _shape((C, kk), "int32", s), _shape((C, kk), "bfloat16", s)])


@pytest.mark.parametrize("G", [0, 8], ids=["scalar", "G8"])
@pytest.mark.parametrize("build", [_tail_fused, _tail_tiled,
                                   _rmatvec_fused, _rmatvec_tiled],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_blocked_ell_kernel_refused(one_chip, build, G):
    """REFUSED by the v5e's compiler — recorded, not hidden: all four
    blocked-ELL kernel forms gather from a VMEM-resident table with
    arbitrary (rows, W) indices (`wt[pc]`, `r[br]`), and Mosaic lowers
    only same-shape 2-D gathers. A repair needs a different algorithm
    (DMA gather or one-hot matmul), so `kernels.active()` keeps ``auto``
    on the XLA path and mode ``on`` surfaces this error."""
    call, args = build(G, one_chip)
    exc, msg = ((ValueError, _GATHER_LANES) if G
                else (NotImplementedError, _GATHER_1D))
    with pytest.raises(exc, match=msg):
        _compile(call, *args)


# ------------------------------------------------------ kernels/serving.py
@pytest.mark.parametrize("B", SERVE_RUNGS)
def test_serving_int8_kernel_refused(one_chip, compiled_mode, B):
    """REFUSED: the fused int8 rung gathers per-entity rows inside the
    kernel (`q[eids]`), the same unsupported table gather — so an int8
    ladder's default route on the chip is the XLA rung."""
    from photon_tpu.kernels.serving import fused_int8_margin

    s = one_chip
    coords = (("fixed", "fixed", "fixed"), ("per_user", "random", "u_re"),
              ("per_item", "random", "i_re"))
    shards = {"fixed": _shape((B, SERVE_D_FIXED), "float32", s),
              "u_re": _shape((B, SERVE_D_RE), "float32", s),
              "i_re": _shape((B, SERVE_D_RE), "float32", s)}
    ids = {"per_user": _shape((B,), "int32", s),
           "per_item": _shape((B,), "int32", s)}
    fixed_ws = {"fixed": (_shape((SERVE_D_FIXED,), "int8", s),
                          _shape((), "float32", s))}
    re_cs = {
        "per_user": (_shape((SERVE_USERS + 1, SERVE_D_RE), "int8", s),
                     _shape((SERVE_USERS + 1,), "float32", s)),
        "per_item": (_shape((SERVE_ITEMS + 1, SERVE_D_RE), "int8", s),
                     _shape((SERVE_ITEMS + 1,), "float32", s))}

    def fn(offsets, shards, ids, fixed_ws, re_cs):
        return fused_int8_margin(coords, offsets, shards, ids, fixed_ws,
                                 re_cs)

    with pytest.raises(ValueError, match=_GATHER_LANES):
        _compile(fn, _shape((B,), "float32", s), shards, ids, fixed_ws,
                 re_cs)


# --------------------------- gathers per evaluation (stored-order layout)
def _gather_outputs(jaxpr):
    """Output shapes of every `gather` equation, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out += [tuple(v.aval.shape) for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _gather_outputs(sub)
    return out


@pytest.mark.parametrize("G", [0, 8], ids=["scalar", "G8"])
def test_blocked_ell_evaluation_gathers_only_by_bucket(G):
    """One value-and-gradient over a `to_blocked_ell` batch gathers once
    per ELL width bucket (of `w`) and once per occurrence bucket (of the
    cotangent) and NOWHERE else: the forward tail is laid over the rows
    by concatenation, so no gather produces an (n,) / (n, G) vector."""
    from photon_tpu.data.dataset import cast_features, make_batch
    from photon_tpu.data.matrix import _contract_blocked_ell
    from photon_tpu.ops import lane_objective
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.ops.objective import Objective

    X = _contract_blocked_ell()
    n, d = X.shape
    rng = np.random.default_rng(0)
    batch = cast_features(make_batch(
        X, (rng.uniform(size=n) < 0.5).astype(np.float32),
        rng.uniform(0.5, 2.0, size=n), rng.normal(size=n)))
    obj = Objective(TaskType.LOGISTIC_REGRESSION, l2=0.5)
    if G:
        l2s = jnp.full((G,), 0.5, jnp.float32)

        def fn(W):
            z = lane_objective.margin_lanes(obj, W, batch)
            return lane_objective.value_and_grad_at_margin_lanes(
                obj, l2s, W, z, batch)

        w = jnp.zeros((d, G), jnp.float32)
    else:
        fn = lambda w: obj.value_and_grad(w, batch)  # noqa: E731
        w = jnp.zeros((d,), jnp.float32)
    shapes = _gather_outputs(jax.make_jaxpr(fn)(w).jaxpr)
    assert len(X.ell_vals) >= 3 and len(X.bucket_vals) >= 3
    assert len(shapes) == len(X.ell_vals) + len(X.bucket_vals), shapes
    expected = [tuple(v.shape) + ((G,) if G else ())
                for v in X.ell_vals + X.bucket_vals]
    assert sorted(shapes) == sorted(expected)
    assert (n, G) not in shapes and (n,) not in shapes
    # and so does the lowered program: the jitted evaluation has that many
    assert len(re.findall(r'stablehlo\.gather"?\(',
                          jax.jit(fn).lower(w).as_text())) == len(shapes)


# ------------------------------------- one all-reduce per evaluation (HLO)
def _all_reduces(compiled) -> int:
    from photon_tpu.analysis import hlo_all_reduce_count

    return hlo_all_reduce_count(compiled.as_text())


def test_mesh_value_and_grad_is_one_all_reduce(topo):
    """The design's law on COMPILED HLO: the row-sharded blocked-ELL
    value-and-gradient at the glm phase's 10M-feature width, partitioned
    over the four described chips, closes with ONE tuple all-reduce — the
    trace's `psum_invariant` pair (one equation per leaf of the variadic
    psum; `analysis.walker` counts the run once) merges in XLA's
    all-reduce combiner."""
    from photon_tpu.analysis import collective_counts
    from photon_tpu.data.dataset import (cast_features, make_batch,
                                         shard_blocked_ell_batch)
    from photon_tpu.data.matrix import SparseRows
    from photon_tpu.models.training import (_contract_sharded_vg,
                                            _hybrid_specs, make_objective)
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    n_sh, d = len(topo.devices), GLM_FEATURES
    rng = np.random.default_rng(0)
    n = 16 * n_sh  # rows are free: the all-reduce payload is (1 + d) f32
    sp = SparseRows(rng.integers(0, d, size=(n, 4)).astype(np.int32),
                    rng.normal(size=(n, 4)).astype(np.float32), d)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    batch = cast_features(shard_blocked_ell_batch(make_batch(sp, y), n_sh,
                                                  d_dense=16))
    cfg = OptimizerConfig(max_iters=2, reg=l2(), reg_weight=0.5)
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d,
                         axis_name="data",
                         intercept_index=batch.X.last_col_pos)
    vg = _contract_sharded_vg(batch, mesh)
    w = jnp.zeros((d,), jnp.float32)
    assert collective_counts(jax.make_jaxpr(vg)(obj, batch, w)) \
        == {"psum": 1}

    rep = NamedSharding(mesh, P())
    specs = _hybrid_specs(batch.X, ("data",),
                          wrap=lambda s: NamedSharding(mesh, s))
    shapes = jax.tree_util.tree_map(
        lambda leaf, sh: _shape(np.shape(leaf), leaf.dtype, sh),
        batch, specs)
    obj_shapes = jax.tree_util.tree_map(
        lambda leaf: _shape(np.shape(leaf), jnp.asarray(leaf).dtype, rep),
        obj)
    compiled = _compile(vg, obj_shapes, shapes, _shape((d,), "float32", rep))
    assert _all_reduces(compiled) == 1


@pytest.mark.parametrize("gshape,expected", [
    ((GLM_FEATURES,), 1),     # single lane: 40 MB gradient + the value
    ((GLM_FEATURES, 8), 2),   # 8-lane sweep: 320 MB gradient
], ids=["single-lane", "G8"])
def test_all_reduce_combiner_threshold(topo, gshape, expected):
    """The combiner is size-bounded: it merged the (value, gradient) pair
    up to a 120 MB gradient and split it from 160 MB on (probed on this
    compiler). So the 8-lane sweep at 10M features — a 320 MB gradient —
    pays a SECOND, (8,)-float all-reduce per evaluation. Pinned as found
    (PERF.md, ROADMAP Speed queue), so a compiler that changes either
    count shows up here."""
    from jax import lax, shard_map

    mesh = Mesh(np.asarray(topo.devices), ("data",))

    def body(x, g):
        v = jnp.sum(x) * jnp.ones(gshape[1:], jnp.float32)
        return lax.psum((v, g * jnp.sum(x)), "data")

    fn = shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                   out_specs=(P(), P()))
    compiled = _compile(
        fn, _shape((64, 8), "float32", NamedSharding(mesh, P("data"))),
        _shape(gshape, "float32", NamedSharding(mesh, P())))
    assert _all_reduces(compiled) == expected


# ----------------------------------------- the L-BFGS history's byte budget
def _while_bodies(hlo_text):
    """The text of every computation a `while` of the module runs as its
    body."""
    bodies = []
    for name in re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", hlo_text):
        start = hlo_text.index(f"%{name} ")
        bodies.append(hlo_text[start:hlo_text.index("\n}", start)])
    return bodies


def _readers_of(hlo_text, shape_prefix):
    """{parameter: [(name, op), ...]} — for each entry parameter of the
    given shape, the instructions that take it, or a buffer updated in
    place from it, as an operand (tuple plumbing aside)."""
    entry = hlo_text[hlo_text.index("ENTRY"):]
    shapes, origin, readers = {}, {}, {}
    for line in entry.splitlines():
        mt = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?[\}\)\]]) "
                      r"([a-z][\w\-]*)\((.*)", line)
        if not mt:
            continue
        name, shape, op, rest = mt.groups()
        shapes[name] = shape
        if op == "parameter" and shape.startswith(shape_prefix):
            origin[name] = name
            readers[name] = []
        plumbing = op in ("tuple", "get-tuple-element", "bitcast",
                          "parameter")
        for o in re.findall(r"%([\w.\-]+)", rest.split("), ")[0]):
            if o in origin:
                if not plumbing:
                    readers[origin[o]].append((name, op))
                if shape_prefix in shape and op != "tuple":
                    origin[name] = origin[o]  # updated in place, or a
                    # multi-output fusion that also returns the update
    return readers


@pytest.mark.parametrize("form", ["scalar", "lanes-bf16"])
def test_history_step_is_two_passes(one_chip, form):
    """One iteration's push + direction at the cells' own shapes (history
    5, 10M features; 8 bf16 lanes) reads the (S, Y) history twice — one
    fused reduction pass, one combination pass — writes one slot of each
    and makes O(1) passes over d-vectors, and no loop of the program
    touches a d-sized operand. The recursion this replaced fetched a slot
    and re-read and re-wrote the working vector in each of 2m dependent
    `while` steps.

    Pinned by structure: each of S and Y (the buffer and its in-place
    update) is an operand of exactly three instructions of the compiled
    step, all fusions — the reduction pass, the slot update, the
    combination pass. A later edit that un-fuses a pass adds readers.

    The byte count is the second fence, set at what `cost_analysis()`
    reads today plus 4 %: 1.681e9 bytes for the scalar form (3.50 × the
    480 MB of S and Y) and 7.201e9 for the lane form (4.50 × its 1.6 GB).
    Both are above the two reads they make because a slot update is
    charged its whole in-place operand (as read in the scalar form, as
    read and written in the lane form) — 1.0 and 2.0 histories that no
    pass moves — and the rest is the d-vectors: s, y, v in, the written
    pair, the direction. A third read of the history would add 1.0. (At
    d = 2^20 the compiler stages the history through its near memory and
    the copies swamp both counts, so this compiles the real width.)"""
    from photon_tpu.optim import lane_lbfgs, lbfgs

    m, d, G = 5, GLM_FEATURES, 8

    def shapes_of(make):
        return jax.tree_util.tree_map(
            lambda x: _shape(x.shape, x.dtype, one_chip),
            jax.eval_shape(make))

    if form == "scalar":
        h = shapes_of(lambda: lbfgs.empty_history(m, d, jnp.float32))
        vec = _shape((d,), "float32", one_chip)

        def step(h, s, y, v):
            h = lbfgs._push(h, s, y, v)
            return h, lbfgs.two_loop(h, v)

        args = (h, vec, vec, vec)
    else:
        h = shapes_of(lambda: lane_lbfgs.empty_lane_history(
            m, d, G, jnp.bfloat16))
        vec = _shape((d, G), "float32", one_chip)

        def step(h, s, y, accept, v):
            h = lane_lbfgs._push_lanes(h, s, y, accept, v)
            return h, lane_lbfgs.two_loop_lanes(h, v)

        args = (h, vec, vec, _shape((G,), "bool", one_chip), vec)
    compiled = jax.jit(step, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()

    dims = ",".join(str(n) for n in h.S.shape)
    prefix = f"{'bf16' if form == 'lanes-bf16' else 'f32'}[{dims}]"
    readers = _readers_of(text, prefix)
    assert len(readers) == 2, readers  # S and Y
    for took in readers.values():
        assert len(took) == 3 and {op for _, op in took} == {"fusion"}, \
            readers

    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    history = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in (h.S, h.Y))
    measured = {"scalar": 1.681e9, "lanes-bf16": 7.201e9}[form]
    assert cost["bytes accessed"] <= 1.04 * measured, (
        cost["bytes accessed"], cost["bytes accessed"] / history)

    for body in _while_bodies(text):
        assert str(d) not in body and f"[{h.S.shape[1]}," not in body, \
            body[:400]
