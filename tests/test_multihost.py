"""Hybrid ICI×DCN mesh path (multi-host story), on the virtual 8-CPU mesh.

Mirrors the reference's cluster semantics (Spark executors over Ethernet)
with a 2-D (replica × data) mesh: examples shard over both axes, the
gradient all-reduce psums over both, and results must match the 1-D mesh
and single-device solves to f32 reduction noise.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from photon_tpu.parallel.mesh import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_tpu.data.dataset import make_batch
from photon_tpu.models.training import train_glm
from photon_tpu.ops.losses import TaskType
from photon_tpu.ops.objective import Objective
from photon_tpu.optim import regularization as reg
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.parallel.mesh import (
    data_sharding,
    initialize_distributed,
    make_hybrid_mesh,
    pad_to_multiple,
)


@pytest.fixture(scope="module")
def hybrid_mesh():
    return make_hybrid_mesh(n_replicas=2, devices=jax.devices("cpu"))


def _logistic(rng, n=2048, d=10):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32) / np.sqrt(d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
    return X, y


def test_hybrid_mesh_shape(hybrid_mesh):
    assert hybrid_mesh.axis_names == ("replica", "data")
    assert hybrid_mesh.devices.shape == (2, 4)
    spec = data_sharding(hybrid_mesh).spec
    assert spec == P(("replica", "data"))


def test_train_glm_on_hybrid_mesh(rng, hybrid_mesh):
    X, y = _logistic(rng)
    cfg = OptimizerConfig(max_iters=60, reg=reg.l2(), reg_weight=1.0,
                          regularize_intercept=True)
    m_single, _ = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION,
                            cfg)
    m_hybrid, res = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION,
                              cfg, mesh=hybrid_mesh)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(m_hybrid.coefficients.means),
                               np.asarray(m_single.coefficients.means),
                               atol=2e-3)


def test_hierarchical_psum_gradient(rng, hybrid_mesh):
    """Explicit shard_map over BOTH axes: psum(("replica","data")) equals the
    single-device gradient — pins the hierarchical collective pattern."""
    X, y = _logistic(rng, n=1024, d=6)
    batch = make_batch(X, y)
    w = jnp.asarray(rng.normal(size=6), jnp.float32) * 0.2

    obj_local = Objective(task=TaskType.LOGISTIC_REGRESSION, l2=0.3)
    v_ref, g_ref = obj_local.value_and_grad(w, batch)

    obj = Objective(task=TaskType.LOGISTIC_REGRESSION, l2=0.3,
                    axis_name=("replica", "data"))

    @jax.jit
    def sharded(batch, w):
        return shard_map(
            lambda b, w: obj.value_and_grad(w, b),
            mesh=hybrid_mesh,
            in_specs=(P(("replica", "data")), P()),
            out_specs=(P(), P()),
        )(batch, w)

    f, g = sharded(
        jax.device_put(batch, data_sharding(hybrid_mesh)),
        jax.device_put(w, NamedSharding(hybrid_mesh, P())))
    np.testing.assert_allclose(float(f), float(v_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_single_all_reduce_per_evaluation(rng, mesh8):
    """Pins the communication pattern: one value_and_grad under shard_map
    traces to exactly ONE psum equation (value and gradient partial sums
    ride the same variadic collective — the reference's single
    treeAggregate). Counted at the JAXPR level with the shared
    photon_tpu.analysis walker: backend-independent, where the old
    compiled-HLO `all-reduce(` text count broke on the CPU test backend's
    missing all-reduce combiner (it legally splits the variadic psum)."""
    from photon_tpu.analysis import collective_counts

    X, y = _logistic(rng, n=512, d=6)
    batch = make_batch(X, y)
    obj = Objective(task=TaskType.LOGISTIC_REGRESSION, l2=0.5,
                    axis_name="data")

    @jax.jit
    def vg(batch, w):
        return shard_map(
            lambda b, w: obj.value_and_grad(w, b), mesh=mesh8,
            in_specs=(P("data"), P()), out_specs=(P(), P()))(batch, w)

    counts = collective_counts(jax.make_jaxpr(vg)(batch, jnp.zeros(6)))
    assert counts == {"psum": 1}, \
        f"expected exactly 1 psum and no other collective, " \
        f"traced {dict(counts)}"


@pytest.mark.parametrize("solver", ["lbfgs", "owlqn", "tron"])
def test_entity_sharded_solve_under_shard_map(rng, mesh8, solver):
    """The vmapped per-entity solve traces — and agrees with plain jit —
    under a shard_map that shards the ENTITY axis: every loop carry a
    solver builds from fresh constants is cast to vary over the manual
    axes its state varies over (`parallel.mesh.vary_like`), which
    shard_map's varying-axes typing requires of `while_loop`/`scan`
    carries. (The data-sharded solves never needed it: their state is
    psum'd, hence invariant.)"""
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.models.training import make_objective, solve
    from photon_tpu.optim.config import OptimizerConfig, OptimizerType
    from photon_tpu.optim.regularization import l1, l2

    E, m, d = 16, 8, 5
    batch = GLMBatch(
        X=jnp.asarray(rng.normal(size=(E, m, d)), jnp.float32),
        y=jnp.asarray(rng.uniform(size=(E, m)) < 0.5, jnp.float32),
        weights=jnp.ones((E, m), jnp.float32),
        offsets=jnp.zeros((E, m), jnp.float32))
    w0 = jnp.zeros((E, d), jnp.float32)
    cfg = {
        "lbfgs": OptimizerConfig(max_iters=4, reg=l2(), reg_weight=0.3,
                                 history=3),
        "owlqn": OptimizerConfig(max_iters=4, reg=l1(), reg_weight=0.3,
                                 history=3),
        "tron": OptimizerConfig(optimizer=OptimizerType.TRON, max_iters=4,
                                reg=l2(), reg_weight=0.3),
    }[solver]
    obj = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d)

    def one(b, w):
        return solve(obj, b, w, cfg).w

    ent = P("data")
    sharded = jax.jit(shard_map(
        jax.vmap(one), mesh=mesh8,
        in_specs=(jax.tree_util.tree_map(lambda _: ent, batch), ent),
        out_specs=ent))
    np.testing.assert_array_equal(
        np.asarray(sharded(batch, w0)),
        np.asarray(jax.jit(jax.vmap(one))(batch, w0)))


def test_padding_divides_hybrid_mesh(hybrid_mesh):
    n_dev = hybrid_mesh.devices.size
    assert pad_to_multiple(1000, n_dev) % n_dev == 0


def test_initialize_distributed_noop(monkeypatch):
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    assert initialize_distributed() is False


def test_bad_replica_count(rng):
    with pytest.raises(ValueError):
        make_hybrid_mesh(n_replicas=3, devices=jax.devices("cpu"))


def test_sharded_hybrid_solve_collectives(rng, mesh8):
    """The ShardedHybridRows shard_map solve: its value_and_grad traces to
    exactly ONE psum and NO other collective — the per-shard tail
    gather/scatter provably never crosses devices (the point of the
    per-shard-tail layout; a global segment_sum under SPMD inference gives
    XLA no such guarantee). Jaxpr-level via photon_tpu.analysis:
    backend-independent, unlike the old HLO `all-reduce(` text count."""
    import scipy.sparse as sp

    from photon_tpu.analysis import collective_counts
    from photon_tpu.data.dataset import shard_hybrid_batch
    from photon_tpu.models.training import _hybrid_specs

    n, d, k = 512, 64, 8
    cols = rng.integers(0, d, size=(n, k))
    rows = np.repeat(np.arange(n), k)
    M = sp.csr_matrix((rng.normal(size=n * k).astype(np.float32),
                       (rows, cols.ravel())), shape=(n, d))
    M.sum_duplicates()
    from photon_tpu.data.matrix import from_scipy_csr

    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    batch = shard_hybrid_batch(make_batch(from_scipy_csr(M), y), 8,
                               d_dense=16)
    obj = Objective(task=TaskType.LOGISTIC_REGRESSION, l2=0.5,
                    axis_name="data")

    @jax.jit
    def vg(batch, w):
        def body(b, w):
            return obj.value_and_grad(w, b._replace(X=b.X.local()))

        return shard_map(
            body, mesh=mesh8,
            in_specs=(_hybrid_specs(batch.X, ("data",)), P()),
            out_specs=(P(), P()))(batch, w)

    counts = collective_counts(jax.make_jaxpr(vg)(batch, jnp.zeros(d)))
    assert counts == {"psum": 1}, \
        f"expected exactly 1 psum and no other collective in the hybrid " \
        f"solve, traced {dict(counts)}"


def test_sharded_permuted_solve_collectives_and_no_scatter(rng, mesh8):
    """The ShardedPermutedHybridRows shard_map solve — the multi-chip form
    of the scatter-free layout — traces to exactly ONE psum, NO other
    collectives, and ZERO scatter ops: the round-5 measured wall (TPU
    scatter-adds at ~12 ns/element vs ~7 ns/gather-index, docs/PERF.md) is
    eliminated by construction on the mesh path too, where
    ShardedHybridRows still pays a per-shard tail segment_sum. The pin
    covers one value_and_grad (scatter-free outright) and the FULL
    lane-grid solver program, whose only scatter eqns are `.at[i].set`
    L-BFGS history writes — plain `scatter`, lowered to
    dynamic-update-slice, never a combining scatter-add. Jaxpr-level via
    photon_tpu.analysis: backend-independent, unlike the old HLO text
    counts."""
    from photon_tpu.analysis import (SCATTER_ADD_PRIMITIVES,
                                     SCATTER_PRIMITIVES, collective_counts,
                                     count_primitives)
    from photon_tpu.data.dataset import shard_permuted_batch
    from photon_tpu.models.training import (_hybrid_specs,
                                            _train_run_sharded_grid_lanes,
                                            lane_weight_arrays,
                                            make_objective)
    from photon_tpu.optim.config import OptimizerConfig as OC

    n, d, k = 512, 300, 6
    cols = (rng.zipf(1.5, size=(n, k)).astype(np.int64) - 1) % d
    vals = rng.normal(size=(n, k)).astype(np.float32)
    from photon_tpu.data.matrix import SparseRows

    X = SparseRows(jnp.asarray(cols.astype(np.int32)), jnp.asarray(vals), d)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    batch = shard_permuted_batch(make_batch(X, y), 8, d_dense=16)
    obj = Objective(task=TaskType.LOGISTIC_REGRESSION, l2=0.5,
                    axis_name="data")

    @jax.jit
    def vg(batch, w):
        def body(b, w):
            return obj.value_and_grad(w, b._replace(X=b.X.local()))

        return shard_map(
            body, mesh=mesh8,
            in_specs=(_hybrid_specs(batch.X, ("data",)), P()),
            out_specs=(P(), P()))(batch, w)

    jaxpr = jax.make_jaxpr(vg)(batch, jnp.zeros(d))
    counts = collective_counts(jaxpr)
    assert counts == {"psum": 1}, \
        f"expected exactly 1 psum and no other collective, " \
        f"traced {dict(counts)}"
    scatters = count_primitives(jaxpr, SCATTER_PRIMITIVES)
    assert not scatters, \
        f"unexpected scatter in sharded permuted solve: {dict(scatters)}"

    # The whole lane-grid solver program: no combining scatter anywhere.
    cfg = OC(max_iters=10, tolerance=1e-7, reg=reg.l2(), reg_weight=0.0,
             history=5)
    l2s, l1s, static_cfg = lane_weight_arrays(cfg, [0.1, 1.0])
    obj_g = make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d,
                           axis_name="data",
                           intercept_index=batch.X.last_col_pos)
    jaxpr_g = jax.make_jaxpr(
        lambda b, w, o, l2v: _train_run_sharded_grid_lanes(
            b, w, o, l2v, None, static_cfg, mesh8))(
        batch, jnp.zeros(d), obj_g, l2s)
    adds = count_primitives(jaxpr_g, SCATTER_ADD_PRIMITIVES)
    assert not adds, \
        f"combining scatter in the sharded permuted lane-grid program: " \
        f"{dict(adds)}"


def test_sharded_hybrid_on_hybrid_mesh(rng, hybrid_mesh):
    """ShardedHybridRows solves on a 2-D (replica × data) mesh: tails shard
    over BOTH axes, psums lower hierarchically, results match single-device."""
    import scipy.sparse as sp

    from photon_tpu.data.dataset import shard_hybrid_batch
    from photon_tpu.data.matrix import from_scipy_csr
    from photon_tpu.optim.config import OptimizerConfig as OC

    n, d, k = 640, 48, 6
    cols = rng.integers(0, d, size=(n, k))
    M = sp.csr_matrix((rng.normal(size=n * k).astype(np.float32),
                       (np.repeat(np.arange(n), k), cols.ravel())),
                      shape=(n, d))
    M.sum_duplicates()
    X = from_scipy_csr(M)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    cfg = OC(max_iters=30, reg=reg.l2(), reg_weight=1.0,
             regularize_intercept=True)
    m_ref, _ = train_glm(make_batch(X, y), TaskType.LOGISTIC_REGRESSION, cfg)
    b = shard_hybrid_batch(make_batch(X, y), hybrid_mesh.devices.size,
                           d_dense=16)
    m_sh, res = train_glm(b, TaskType.LOGISTIC_REGRESSION, cfg,
                          mesh=hybrid_mesh)
    assert not bool(res.failed)
    np.testing.assert_allclose(np.asarray(m_sh.coefficients.means),
                               np.asarray(m_ref.coefficients.means),
                               atol=5e-3)


def test_sharded_hybrid_grid_on_hybrid_mesh(rng, hybrid_mesh):
    """Reg-weight grid over ShardedHybridRows on the 2-D (replica × data)
    mesh: lanes vmapped inside shard_map, psums over both axes."""
    import scipy.sparse as sp

    from photon_tpu.data.dataset import shard_hybrid_batch
    from photon_tpu.data.matrix import from_scipy_csr
    from photon_tpu.models.training import train_glm_grid
    from photon_tpu.optim.config import OptimizerConfig as OC

    n, d, k = 512, 32, 6
    cols = rng.integers(0, d, size=(n, k))
    M = sp.csr_matrix((rng.normal(size=n * k).astype(np.float32),
                       (np.repeat(np.arange(n), k), cols.ravel())),
                      shape=(n, d))
    M.sum_duplicates()
    X = from_scipy_csr(M)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    cfg = OC(max_iters=25, reg=reg.l2(), reg_weight=0.0,
             regularize_intercept=True)
    ref = train_glm_grid(make_batch(X, y), TaskType.LOGISTIC_REGRESSION,
                         cfg, [0.5, 5.0])
    b = shard_hybrid_batch(make_batch(X, y), hybrid_mesh.devices.size,
                           d_dense=8)
    got = train_glm_grid(b, TaskType.LOGISTIC_REGRESSION, cfg, [0.5, 5.0],
                         mesh=hybrid_mesh)
    for (m_r, _), (m_g, r_g) in zip(ref, got):
        assert not bool(r_g.failed)
        np.testing.assert_allclose(np.asarray(m_g.coefficients.means),
                                   np.asarray(m_r.coefficients.means),
                                   atol=5e-3)


# ------------------------------------------------------- round 17: the spine
class TestShardChunkRange:
    """The canonical per-process chunk split (data/chunk_cache.py) that
    the distributed cache AND the local_only ingest convention lean on:
    contiguous, in process order, an EXACT partition of [0, n_chunks)."""

    def test_union_is_exact_partition(self):
        from photon_tpu.data.chunk_cache import shard_chunk_range

        for n_chunks in (0, 1, 7, 8, 9, 64, 1000):
            for n_proc in (1, 2, 3, 4, 8):
                spans = [shard_chunk_range(n_chunks, k, n_proc)
                         for k in range(n_proc)]
                # contiguous in process order, starting at 0, ending at n
                assert spans[0][0] == 0
                assert spans[-1][1] == n_chunks
                for (a_lo, a_hi), (b_lo, b_hi) in zip(spans, spans[1:]):
                    assert a_hi == b_lo, (n_chunks, n_proc, spans)
                # balanced: sizes differ by at most one, big ones first
                sizes = [hi - lo for lo, hi in spans]
                assert max(sizes) - min(sizes) <= 1
                assert sizes == sorted(sizes, reverse=True)

    def test_fewer_chunks_than_processes(self):
        """n_chunks < n_processes: the tail processes get VALID empty
        ranges (lo == hi) — a zero-row cluster member is legal and must
        not crash the split."""
        from photon_tpu.data.chunk_cache import shard_chunk_range

        spans = [shard_chunk_range(2, k, 4) for k in range(4)]
        assert spans == [(0, 1), (1, 2), (2, 2), (2, 2)]
        assert all(lo <= hi for lo, hi in spans)

    def test_non_dividing_counts(self):
        from photon_tpu.data.chunk_cache import shard_chunk_range

        assert [shard_chunk_range(10, k, 4) for k in range(4)] == \
            [(0, 3), (3, 6), (6, 8), (8, 10)]

    def test_process_out_of_range(self):
        from photon_tpu.data.chunk_cache import shard_chunk_range

        with pytest.raises(ValueError, match="out of range"):
            shard_chunk_range(10, 4, 4)
        with pytest.raises(ValueError, match="out of range"):
            shard_chunk_range(10, -1, 4)


class TestInitializeDistributedValidation:
    """Round-17 satellite: loud validation BEFORE any network traffic,
    and the PHOTON_TPU_* knob plumbing the launcher rides."""

    def test_process_id_out_of_range(self):
        with pytest.raises(ValueError, match=r"ranks are 0\.\.3"):
            initialize_distributed("127.0.0.1:9", num_processes=4,
                                   process_id=4)
        with pytest.raises(ValueError, match="out of range"):
            initialize_distributed("127.0.0.1:9", num_processes=2,
                                   process_id=-1)

    def test_process_id_without_num_processes(self):
        with pytest.raises(ValueError, match="without num_processes"):
            initialize_distributed("127.0.0.1:9", process_id=0)

    def test_bad_num_processes(self):
        with pytest.raises(ValueError, match="num_processes"):
            initialize_distributed("127.0.0.1:9", num_processes=0)

    def test_knobs_feed_validation(self, monkeypatch):
        """The PHOTON_TPU_* env knobs land in the same validation path
        as explicit arguments."""
        monkeypatch.setenv("PHOTON_TPU_NUM_PROCESSES", "2")
        monkeypatch.setenv("PHOTON_TPU_PROCESS_ID", "5")
        with pytest.raises(ValueError, match="out of range"):
            initialize_distributed()

    def test_double_initialize_refused(self, monkeypatch):
        """A live distributed client means a second initialize must be
        refused with the fix spelled out, not forwarded to jax's opaque
        failure."""
        from photon_tpu.parallel import mesh as mesh_mod

        monkeypatch.setattr(mesh_mod, "distributed_client",
                            lambda: object())
        with pytest.raises(RuntimeError, match="already initialized"):
            mesh_mod.initialize_distributed("127.0.0.1:9",
                                            num_processes=2, process_id=0)

    def test_knobs_are_registered(self):
        from photon_tpu.utils.env import KNOB_DOCS

        for knob in ("PHOTON_TPU_COORDINATOR", "PHOTON_TPU_NUM_PROCESSES",
                     "PHOTON_TPU_PROCESS_ID",
                     "PHOTON_TPU_BARRIER_TIMEOUT_S"):
            assert knob in KNOB_DOCS, knob


class TestGradOnlyDcnContract:
    """The round-17 wire bill, priced: the one psum closing a sharded
    evaluation carries O(d) bytes — the features (O(n*d)) never ride a
    collective. (The contract itself — exactly one psum — is checked
    with the whole registry; here the BYTES are pinned.)"""

    def test_collective_bytes_are_gradient_sized(self):
        from photon_tpu.analysis.contracts import REGISTRY
        from photon_tpu.analysis import trace_contract
        from photon_tpu.profiling.model import estimate_jaxpr

        spec = REGISTRY["multihost_grad_only_dcn"]
        traced = trace_contract(spec)
        cost = estimate_jaxpr(traced.closed_jaxpr)
        d = 48
        # per-shard psum payload: the (d,) gradient partial + the scalar
        # value partial, f32
        assert cost.collective_bytes == (d + 1) * 4
        batch = traced.example_args[0]
        feature_bytes = int(np.asarray(batch.X).nbytes)
        per_shard_features = feature_bytes // len(jax.devices())
        assert per_shard_features >= 100 * cost.collective_bytes


class TestLaunchValidation:
    """parallel.launch argument validation — no processes are spawned."""

    def test_non_dividing_device_count(self):
        from photon_tpu.parallel.launch import launch

        with pytest.raises(ValueError, match="does not divide"):
            launch(len, 3, total_devices=8)

    def test_bad_process_count(self):
        from photon_tpu.parallel.launch import launch

        with pytest.raises(ValueError, match="n_processes"):
            launch(len, 0)


def _launch_or_skip(target, n, **kwargs):
    from photon_tpu.parallel.launch import ClusterUnavailable, launch

    try:
        return launch(target, n, **kwargs)
    except ClusterUnavailable as e:
        pytest.skip(f"jax.distributed cluster unavailable in this "
                    f"sandbox: {e}")


@pytest.mark.tier2
class TestMultiProcessSpine:
    """The round-17 acceptance matrix across REAL process boundaries:
    1/2/4 spawned cluster members over the SAME 8-device global mesh.
    Promoted straight to tier-2 (each case spawns + initializes several
    jax runtimes); the umbrella `python -m photon_tpu.parallel
    --selftest` keeps a bounded smoke of the same targets."""

    def test_psum_bit_identical_across_process_counts(self):
        from photon_tpu.parallel import selfcheck as sc

        digests = set()
        for n in (1, 2, 4):
            res = _launch_or_skip(sc.target_psum_signature, n,
                                  timeout_s=180)
            assert [r["rank"] for r in res] == list(range(n))
            assert all(r["n_devices"] == 8 for r in res)
            digests.update(r["digest"] for r in res)
        assert len(digests) == 1, digests

    def test_e2e_solve_bit_identical_and_ingest_split(self, tmp_path):
        """The tentpole bar: scan -> local_only ingest -> mesh GLM solve
        at 1, 2 and 4 processes — f64 coefficients BIT-identical, and
        each multi-process rank provably decoded only a strict subset of
        the chunks."""
        from photon_tpu.parallel import selfcheck as sc

        sc.write_e2e_dataset(tmp_path)
        w_by_n = {}
        for n in (1, 2, 4):
            res = _launch_or_skip(sc.target_stream_solve, n,
                                  args=(str(tmp_path),), timeout_s=420)
            assert all(r["n_real"] == 1200 for r in res)
            if n == 1:
                assert res[0]["chunks_skipped"] == 0
            else:
                # every rank decoded SOME chunks and skipped SOME —
                # the disk/decode work is genuinely partitioned
                assert all(r["chunks_decoded"] >= 1 for r in res)
                assert all(r["chunks_skipped"] >= 1 for r in res)
            w_by_n[n] = np.stack([r["w"] for r in res])
            # replicated model: every rank returns the same bits
            assert all(np.array_equal(w_by_n[n][0], w) for w in w_by_n[n])
        np.testing.assert_array_equal(w_by_n[1][0], w_by_n[2][0])
        np.testing.assert_array_equal(w_by_n[1][0], w_by_n[4][0])

    def test_two_proc_snapshot_restores_at_1_and_4_procs(self, tmp_path):
        """Elastic restore across process counts: a 2-process mesh-
        streamed solve killed mid-run leaves per-process p<k>_ payloads
        with per-slot row-cache entries; 1- and 4-process clusters must
        both finish BIT-identical to the uninterrupted run (the global
        8-slot mesh is the same at every count)."""
        from photon_tpu.parallel import selfcheck as sc

        ref = _launch_or_skip(sc.target_resume_solve, 1,
                              args=(str(tmp_path / "ref"),),
                              timeout_s=300)[0]
        for resume_n in (1, 4):
            ck = tmp_path / f"snap_{resume_n}"
            killed = _launch_or_skip(sc.target_snapshot_kill, 2,
                                     args=(str(ck), "evaluation", 7),
                                     timeout_s=300)
            assert all(r["killed"] for r in killed), killed
            assert all(r["latest_seq"] >= 0 for r in killed), killed
            res = _launch_or_skip(sc.target_resume_solve, resume_n,
                                  args=(str(ck),), timeout_s=300)
            for r in res:
                np.testing.assert_array_equal(ref["w"], r["w"])

    def test_commit_kill_fails_loudly_previous_manifest_intact(
            self, tmp_path):
        """Satellite 1: rank 1 dies BETWEEN its durable payload write
        and the commit barrier. The surviving rank's commit must fail
        within PHOTON_TPU_BARRIER_TIMEOUT_S (loud, not hung), the
        manifest must still point at the last fully-committed snapshot,
        and every payload it references must exist."""
        import os

        from photon_tpu.checkpoint import SnapshotStore
        from photon_tpu.parallel import selfcheck as sc

        ck = tmp_path / "ck"
        res = _launch_or_skip(
            sc.target_commit_kill, 2, args=(str(ck), 1, 2),
            timeout_s=300, env={"PHOTON_TPU_BARRIER_TIMEOUT_S": "8"})
        by_rank = {r["rank"]: r for r in res}
        assert by_rank[1]["outcome"] == "killed"
        assert by_rank[0]["outcome"] == "commit_failed", by_rank[0]
        store = SnapshotStore(str(ck))
        manifest = store.read_manifest()
        assert manifest is not None and manifest["seq"] == 0
        # the committed snapshot fully resolves: no referenced payload
        # is missing even though a LATER snapshot attempt died half-way
        state, _ = store.load_latest()
        assert state
        snap_dir = os.path.join(str(ck), manifest["latest"])
        assert os.path.isdir(snap_dir)
