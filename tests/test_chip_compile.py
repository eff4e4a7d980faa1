"""Compile what the repo runs on the chip — its one Pallas kernel, the
serving rungs, the blocked-ELL X-pass forms no cell runs — and count the
mesh evaluation's all-reduces, for a DESCRIBED TPU v5e (`v5e:2x2`), with
no chip attached.

`photon_tpu/ops/fused.py` (the one Pallas kernel: its other tests run
Pallas interpret mode, which accepts programs the chip's compiler
refuses), the serving rung bodies and the second-order X passes go through
the real TPU compiler at the shapes `chip_smoke.py`'s `glm` and `serve`
phases really have, without ``interpret``. What compiles is pinned as
compiling; what the compiler refuses is pinned as refused, message and
all.

A NEW KERNEL STARTS HERE (ROADMAP Reach A2): Mosaic lowers only same-shape
2-D gathers (an in-vreg `take_along_axis`), so a kernel that gathers from
a table with arbitrary indices — what every blocked-ELL tail form and the
fused int8 rung this repo once carried did — is refused ("Only 2D gather
is supported"). Prove in this file that a form compiles at the `GLM_*`
shapes BEFORE any dispatch code exists.

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

# The blocked-ELL layout of the benchmark's one-chip GLM cells
# (`glm-sparse10m.single` / `.sweep8`: `gen/sparse.py`'s fixed pattern,
# 2^21 rows, 10M features, 1024-column bf16 hot block) as `to_blocked_ell`
# built it in this sandbox under the width ladder 1, 2, 3, 4, 6, 8, 12, …
# (the shapes follow from the pattern, so every seed has them): row count
# n, tail length U = n_prefix - d_sel, the seven ELL width buckets
# (rows, W) — three quarters of the old width-4 bucket are the rows of
# width 3 — and the twenty-one occurrence buckets (columns, k): 3,412,948
# and 3,911,675 slots for 3,377,451 tail nonzeros (powers of two alone:
# 3,793,085 and 4,607,800). chip_smoke.py's `glm` phase lays bench.py's
# seed-0 problem, whose counts differ in the third digit — shape
# structure, not the last digit, is what the compiler sees.
GLM_N = 1 << 21
GLM_U = 541054 - 1024
GLM_ELL = ((683659, 1), (558905, 2), (296641, 3), (113929, 4), (41676, 6),
           (1919, 8), (36, 12))
GLM_BUCKETS = ((382064, 1), (58188, 2), (24269, 3), (13789, 4), (15334, 6),
               (8573, 8), (9639, 12), (5458, 16), (5865, 24), (3304, 32),
               (3684, 48), (1977, 64), (2228, 96), (1275, 128), (1348, 192),
               (778, 256), (802, 384), (460, 512), (512, 768), (277, 1024),
               (206, 1536))
GLM_FEATURES = 10_000_000
# The SHARDED blocked-ELL layout of the benchmark's four-chip cell
# (`glm-sparse10m-mesh4.single`: 4 shards of 2^21 rows) as
# `shard_blocked_ell` laid it in this sandbox from `gen/sparse_mesh.py`'s
# draw (the shapes follow from the fixed pattern, so every seed has them):
# per-shard common shapes, (r_b, W) ELL buckets and (c_b, k) occurrence
# buckets under the same ladder: 3,420,070 and 5,434,375 slots a shard
# (powers of two alone: 3,798,048 and 6,269,888). Against the one-chip
# layout above the count-1 occurrence bucket is three times as long: a
# column's bucket comes from its MAX-LOCAL count.
MESH4_SHARDS, MESH4_N = 4, 1 << 23
MESH4_PREFIX = 1421599
MESH4_ELL = ((683602, 1), (560351, 2), (296824, 3), (114410, 4), (41973, 6),
             (1911, 8), (44, 12))
MESH4_BUCKETS = ((1130732, 1), (131102, 2), (46869, 3), (24449, 4),
                 (25452, 6), (13352, 8), (13969, 12), (7221, 16), (7779, 24),
                 (4121, 32), (4463, 48), (2348, 64), (2564, 96), (1402, 128),
                 (1508, 192), (812, 256), (882, 384), (491, 512), (541, 768),
                 (287, 1024), (231, 1536))
# the serve phase's store: the flagship GAME model (benches/_flagship_data)
SERVE_D_FIXED, SERVE_D_RE = 33, 4
SERVE_USERS, SERVE_ITEMS = 100_000, 50_000
SERVE_RUNGS = (8, 256)  # smallest and largest rung of the default ladder


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


# ---------------------------------------------------- serving/programs.py
@pytest.mark.parametrize("quantize", [None, "int8", "bf16"],
                         ids=["f32", "int8", "bf16"])
@pytest.mark.parametrize("B", SERVE_RUNGS)
def test_serving_rung_compiles(one_chip, B, quantize):
    """The body that actually serves — one XLA program a rung, the int8 /
    bf16 dequantization inside it — compiles at the serve phase's store,
    smallest and largest rung of the default ladder."""
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.serving.programs import _build_score_fn

    s = one_chip
    coords = (("fixed", "fixed", "fixed"), ("per_user", "random", "u_re"),
              ("per_item", "random", "i_re"))
    shards = {"fixed": _shape((B, SERVE_D_FIXED), "float32", s),
              "u_re": _shape((B, SERVE_D_RE), "float32", s),
              "i_re": _shape((B, SERVE_D_RE), "float32", s)}
    ids = {"per_user": _shape((B,), "int32", s),
           "per_item": _shape((B,), "int32", s)}

    def block(shape):
        if quantize == "int8":
            return (_shape(shape, "int8", s),
                    _shape(shape[:-1], "float32", s))
        return _shape(shape, "bfloat16" if quantize else "float32", s)

    fixed_ws = {"fixed": block((SERVE_D_FIXED,))}
    re_cs = {"per_user": block((SERVE_USERS + 1, SERVE_D_RE)),
             "per_item": block((SERVE_ITEMS + 1, SERVE_D_RE))}
    fn = _build_score_fn(coords, TaskType.LOGISTIC_REGRESSION, True,
                         quantize=quantize)
    compiled = _compile(fn, _shape((B,), "float32", s), shards, ids,
                        fixed_ws, re_cs)
    assert "tpu_custom_call" not in compiled.as_text()


# ----------------------- second-order X passes at the glm phase's shapes
def _glm_batch(s):
    """The glm phase's batch as shapes: the stored-order blocked-ELL
    layout `to_blocked_ell` built (module constants), bf16 values."""
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.data.matrix import BlockedEllRows

    d_sel = 1024
    rows = _shape((GLM_N,), "float32", s)
    X = BlockedEllRows(
        dense=_shape((GLM_N, d_sel), "bfloat16", s),
        ell_pcols=tuple(_shape(b, "int32", s) for b in GLM_ELL),
        ell_vals=tuple(_shape(b, "bfloat16", s) for b in GLM_ELL),
        row_pos=_shape((GLM_N,), "int32", s),
        bucket_rows=tuple(_shape(b, "int32", s) for b in GLM_BUCKETS),
        bucket_vals=tuple(_shape(b, "bfloat16", s) for b in GLM_BUCKETS),
        perm_cols=_shape((GLM_FEATURES,), "int32", s),
        inv_perm=_shape((GLM_FEATURES,), "int32", s),
        n_features=GLM_FEATURES, n_prefix=d_sel + GLM_U,
        last_col_pos=0, tail_nnz=sum(r * w for r, w in GLM_ELL),
        row_order=_shape((GLM_N,), "int32", s))
    return GLMBatch(X, rows, rows, rows)


@pytest.mark.parametrize("form,G", [("sq_rmatvec", 0), ("sq_rmatvec", 8),
                                    ("hvp", 8)],
                         ids=["sq_rmatvec-scalar", "sq_rmatvec-G8", "hvp-G8"])
def test_second_order_x_pass_compiles(one_chip, form, G):
    """Forms no benchmark cell runs (PERF.md §7 row 4): the squared
    transposed pass under the Hessian diagonal, scalar and eight lanes, and
    lane TRON's Hessian-vector product (`matvec` ∘ curvature weights ∘
    `rmatvec`, the margin cached) — single passes at 2,097,152 × 10M — fit
    the chip and compile. The SCALAR Hessian-vector product compiles too
    (PR 29, by hand) but takes 58 s of a worker alone and 87 s beside five
    others, so it is not kept here."""
    from photon_tpu.data.matrix import sq_rmatvec
    from photon_tpu.ops import lane_objective
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.ops.objective import Objective

    s = one_chip
    batch = _glm_batch(s)
    obj = Objective(TaskType.LOGISTIC_REGRESSION, l2=0.5)
    z = _shape(_vec(GLM_N, G), "float32", s)
    if form == "sq_rmatvec":
        compiled = _compile(lambda b, r: sq_rmatvec(b.X, r), batch, z)
    else:
        l2s = jnp.full((G,), 0.5, jnp.float32)
        compiled = _compile(
            lambda b, z, V: lane_objective.hvp_at_margin_lanes(
                obj, l2s, z, b, V),
            batch, z, _shape((GLM_FEATURES, G), "float32", s))
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 * 2 ** 30


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


def test_mesh4_sharded_solve_compiles(topo):
    """The whole sharded solve of `glm-sparse10m-mesh4.single` —
    `_train_run_sharded`, 40 L-BFGS iterations over 4 × 2,097,152 rows ×
    10M features in bf16 — compiles for the four described chips and fits
    one: three all-reduces in the program (the first evaluation's pair,
    the gradient an iteration, the line search's two scalars), each under
    `mesh.psum`."""
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.data.matrix import ShardedBlockedEllRows
    from photon_tpu.models.training import (_static_config,
                                            _train_run_sharded,
                                            make_objective)
    from photon_tpu.models.variance import VarianceComputationType
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    dat, rep = NamedSharding(mesh, P(("data",))), NamedSharding(mesh, P())
    S, n, d = MESH4_SHARDS, MESH4_N, GLM_FEATURES

    def per_shard(buckets, dtype):
        return tuple(_shape((S, r, w), dtype, dat) for r, w in buckets)

    X = ShardedBlockedEllRows(
        dense=_shape((n, 1024), "bfloat16", dat),
        ell_pcols=per_shard(MESH4_ELL, "int32"),
        ell_vals=per_shard(MESH4_ELL, "bfloat16"),
        row_pos=_shape((S, n // S), "int32", dat),
        bucket_rows=per_shard(MESH4_BUCKETS, "int32"),
        bucket_vals=per_shard(MESH4_BUCKETS, "bfloat16"),
        perm_cols=_shape((d,), "int32", rep),
        inv_perm=_shape((d,), "int32", rep),
        n_features=d, n_prefix=MESH4_PREFIX, last_col_pos=1023,
        tail_nnz=13509184)
    rows = _shape((n,), "float32", dat)
    cfg = OptimizerConfig(max_iters=40, tolerance=0.0, reg=l2(),
                          reg_weight=1e-3, history=5)
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d,
                         axis_name="data", intercept_index=1023)
    obj_shapes = jax.tree_util.tree_map(
        lambda leaf: _shape(np.shape(leaf), jnp.asarray(leaf).dtype, rep),
        obj)
    with jax.default_matmul_precision("default"):
        compiled = _train_run_sharded.lower(
            GLMBatch(X, rows, rows, rows), _shape((d,), "float32", rep),
            obj_shapes, None, _static_config(cfg),
            VarianceComputationType.NONE, mesh).compile()
    reduces = [ln for ln in compiled.as_text().splitlines()
               if " all-reduce(" in ln or " all-reduce-start(" in ln]
    assert len(reduces) == 3
    for ln in reduces:
        op_name = re.search(r'op_name="([^"]*)"', ln).group(1)
        assert "mesh.psum" in op_name.split("/"), op_name
    mem = compiled.memory_analysis()
    on_device = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes)
    assert 4.3e9 < on_device < 0.6 * 16 * 2 ** 30, on_device


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


# ------------- the streamed cell's chunk programs at 2,097,152 x 10M (PR 34)
def _stream_chunk(s):
    """One chunk of `glm-sparse10m-stream.single`'s host ladder as shapes:
    `chunk_blocked_ell(batch, 2097152, d_dense=1024,
    feature_dtype=bfloat16, n_shards=1)` over the mesh cell's rows is
    `shard_blocked_ell` with S = 4, so a chunk has the mesh cell's
    per-shard common shapes (`MESH4_*`), its rows in the caller's order
    (`row_order` None: the forward tail keeps its `row_pos` gather) and
    the ladder's two (d,) permutation vectors as leaves of its own."""
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.data.matrix import BlockedEllRows

    n = MESH4_N // MESH4_SHARDS
    rows = _shape((n,), "float32", s)
    X = BlockedEllRows(
        dense=_shape((n, 1024), "bfloat16", s),
        ell_pcols=tuple(_shape(b, "int32", s) for b in MESH4_ELL),
        ell_vals=tuple(_shape(b, "bfloat16", s) for b in MESH4_ELL),
        row_pos=_shape((n,), "int32", s),
        bucket_rows=tuple(_shape(b, "int32", s) for b in MESH4_BUCKETS),
        bucket_vals=tuple(_shape(b, "bfloat16", s) for b in MESH4_BUCKETS),
        perm_cols=_shape((GLM_FEATURES,), "int32", s),
        inv_perm=_shape((GLM_FEATURES,), "int32", s),
        n_features=GLM_FEATURES, n_prefix=MESH4_PREFIX, last_col_pos=1023,
        tail_nnz=13509184)
    return GLMBatch(X, rows, rows, rows)


@pytest.mark.parametrize("program", ["chunk_init", "chunk_grad_at_margin",
                                     "chunk_dz_phi"])
def test_streamed_chunk_program_compiles(one_chip, program):
    """The three donated per-chunk programs `_SingleDeviceStream` runs in
    a streamed L-BFGS solve — margins with partials (the first pass),
    partials at cached margins (a gradient pass), the direction's margins
    with the first trial (a dz pass) — at the cell's chunk: 2,097,152 rows
    x 10M features, bf16. Each compiles for the chip and, with a second
    chunk of the upload ring beside it and the solver state, fits it: the
    program's own arguments, outputs and temporaries stay under 5.2 GB (a
    chunk is 4.45), so the chunk being consumed, the one uploading behind
    it and 0.7 GB of solver state are 10 GB of the chip's 16 GiB (the ring
    frees a consumed chunk before it allocates the next; a third would
    make 14.5 GB)."""
    from photon_tpu.models.training import make_objective
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim import streamed
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    s = one_chip
    batch = _stream_chunk(s)
    n, d = batch.y.shape[0], GLM_FEATURES
    cfg = OptimizerConfig(max_iters=10, tolerance=0.0, reg=l2(),
                          reg_weight=1e-3, history=5)
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d,
                         intercept_index=1023)
    obj = jax.tree_util.tree_map(
        lambda leaf: _shape(np.shape(leaf), jnp.asarray(leaf).dtype, s), obj)
    w = _shape((d,), "float32", s)
    z = _shape((n,), "float32", s)
    step = _shape((), "float32", s)
    fn, args = {
        "chunk_init": (streamed._chunk_init_don, (obj, w, batch)),
        "chunk_grad_at_margin": (streamed._chunk_grad_at_margin_don,
                                 (obj, z, batch)),
        "chunk_dz_phi": (streamed._chunk_dz_phi_don,
                         (obj, w, z, step, batch)),
    }[program]
    with jax.default_matmul_precision("default"):
        compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    on_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
    assert 4.4e9 < on_device < 5.2e9, on_device
