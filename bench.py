"""Headline benchmark: logistic training throughput on one chip, on the
NORTH-STAR-SHAPED workload.

BASELINE.json's metric line is "samples/sec/chip + wall-clock-to-target-AUC
on 1B-row logistic GAME" over a 10M-feature sparse space (the reference:
DistributedGLMLossFunction + Breeze LBFGS on a 64-executor Spark cluster).
The headline leg here matches that SHAPE on one chip — and, like the
reference's actual production job, it is a REGULARIZATION SWEEP (the
reference trains one Spark run per λ; GameEstimator grid mode):

- 10M-feature space, power-law (zipf) sparse rows — the ads-features regime
  the reference was built for;
- PermutedHybridRows storage (hot columns dense on the MXU; cold tail laid
  out so both X passes are scatter-free — TPU scatter-adds are the
  measured wall, docs/PERF.md) in bfloat16 with f32 accumulation;
- an 8-lane reg-weight grid solved lock-step by the lane-minor
  margin-cached L-BFGS (optim/lane_lbfgs.py): full 10M-dimensional
  optimizer state PER LANE (no support compression — the solver really
  works in R^10M × 8), every X pass shared across lanes;
- aggregate rows·iters/s = rows × total lane-iterations / wall-clock —
  every lane-iteration is a genuine L-BFGS iteration of an independent
  grid point a photon-ml user would otherwise pay a full Spark run for.

Legs: the same problem solved single-lane (train_glm, the scalar
margin-cached solver — the non-sweep workload), and the previous dense
reg-grid ceiling (524k×256 f32, 16 lanes).

The baseline is the documented Spark-derived estimate of 1.0e6
rows·iters/sec *cluster-wide* (64 executors × 4 cores) on the reference's
own sparse workload; vs_baseline is ours (ONE chip) divided by that
whole-cluster number. (The ≥20× north star is stated for a v5e-64.)

Wall-clock-to-target-AUC on a GAME fit is benches/game_auc.py (recorded in
docs/PERF.md); it has no single-number/second contract so it lives outside
this file's one-JSON-line protocol.

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "legs": {...}}
"""
from __future__ import annotations

import json
import os
import sys
import time

# --check-contracts: trace the full photon_tpu.analysis contract registry
# and exit — a no-op guard proving every benchmarked hot path still holds
# its communication/dtype/transfer/retrace contracts, runnable anywhere
# (CI pins `JAX_PLATFORMS=cpu python bench.py --check-contracts`). The
# platform env must be set BEFORE jax initializes, hence before the
# imports below.
if "--check-contracts" in sys.argv:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if os.environ.get("JAX_PLATFORMS") == "cpu" and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count"
                                   "=8").strip()

# --check-lint: the source-level convention auditor (photon_tpu/lint) —
# durable-write discipline, fault-site/telemetry/env-knob registries,
# lock/spawn/exception hygiene, contract + sentinel coverage, plus the
# whole-program concurrency rules (thread inventory, lock-order graph,
# blocking-under-lock, guarded-by race detection). Jax-free AST rules
# over the repo source: milliseconds, runs before the heavyweight
# imports below, exit 1 on any finding (CI pins
# `python bench.py --check-lint` beside --check-contracts; pass
# --threads to dump the thread model itself).
if "--check-lint" in sys.argv:
    from photon_tpu.lint.__main__ import main as _lint_main

    raise SystemExit(_lint_main([a for a in sys.argv[1:]
                                 if a != "--check-lint"]))

# --gate: the noise-aware bench regression sentinel
# (photon_tpu/profiling/sentinel.py) — judge the latest BENCH_r0*.json
# round (or --gate-candidate FILE) against the earlier trajectory with
# per-leg median/MAD robust z-scores; exit 1 iff any leg regressed
# beyond --gate-z. Runs BEFORE the benchmark imports: gating a PR costs
# milliseconds, never a benchmark run. [--gate-dir DIR] [--gate-z Z]
if "--gate" in sys.argv:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from photon_tpu.profiling.sentinel import gate_main

    raise SystemExit(gate_main(
        sys.argv, bench_dir=os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.dataset import cast_features, chunk_batch, make_batch
from photon_tpu.data.matrix import SparseRows, to_blocked_ell
from photon_tpu.models.training import train_glm, train_glm_grid
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.regularization import l2

BASELINE_CLUSTER_ROWS_ITERS_PER_SEC = 1.0e6

# --- sparse leg (headline): the north-star shape --------------------------
# 2M rows: an iteration is d-linear solver-state work plus row-linear
# X-pass work (benches/roofline.py's model), so more rows amortize the
# d-term directly. The dense hot block is scattered ON DEVICE from the
# compact hot COO (to_hybrid device_dense_dtype) instead of being
# materialized on the host and copied over.
S_ROWS = 1 << 21        # 2097152
S_FEATURES = 10_000_000
S_NNZ = 32              # per row, + intercept
S_ZIPF = 1.4            # power-law exponent of column frequencies
S_DENSE = 1024          # hot-column block width
S_ITERS = 40
S_GRID = list(np.geomspace(1e-4, 1e-2, 8))  # 8 reg lanes, one program
# G=8 is the measured sweet spot: 1.35e8 aggregate vs 8.0e7 at G=4 and
# 1.12e8 at G=16 (the (m, d, G) solver state saturates HBM past 8 lanes
# — benches/grid_lanes.py table in docs/PERF.md).

# --- dense leg: solver-throughput ceiling ---------------------------------
D_ROWS = 1 << 19
D_FEATURES = 256
D_ITERS = 40
D_GRID = list(np.geomspace(1e-4, 1e-2, 16))  # 16 reg weights, one program
# Model-selection-scale ceiling: at G=256 the (n, 256) x (256, G) lane
# matmul finally feeds the MXU — G=64 -> 128 runs at FLAT wall time and
# the knee is ~256 (7.5e9 aggregate; 512 adds only 5% — docs/PERF.md
# lane curve). 256 lanes = a fine-grained lambda sweep or a q-EI tuner
# batch; the reference runs one Spark job per point.
D_GRID_BIG = list(np.geomspace(1e-5, 1e-1, 256))

REPS = 5  # keep the best of five: a shared host is noisy run to run


def sparse_coo(seed: int = 0, rows: int = S_ROWS):
    """(indices (n, k+1), values (n, k+1), labels (n,)) — the host COO of
    the power-law 10M-feature logistic rows with a planted hot-end signal
    (last slot = intercept column d-1)."""
    rng = np.random.default_rng(seed)
    n, k, d = rows, S_NNZ, S_FEATURES
    col = (rng.zipf(S_ZIPF, size=(n, k)).astype(np.int64) - 1) % (d - 1)
    val = rng.normal(size=(n, k)).astype(np.float32)
    ind = np.concatenate([col, np.full((n, 1), d - 1)], axis=1).astype(
        np.int32)
    va = np.concatenate([val, np.ones((n, 1), np.float32)], axis=1)
    w_true = np.zeros(d, np.float32)
    hot = 200_000
    w_true[:hot] = rng.normal(size=hot) / np.sqrt(np.arange(1, hot + 1))
    w_true[d - 1] = -0.2
    margin = np.einsum("nk,nk->n", va, w_true[ind])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return ind, va, y


def sparse_problem(seed: int = 0, rows: int = S_ROWS):
    """(batch, layout stats) — `sparse_coo` laid out as BlockedEllRows."""
    return sparse_batch(*sparse_coo(seed, rows))


def sparse_batch(ind, va, y):
    """(device batch, layout stats) of a `sparse_coo` problem."""
    n, k, d = ind.shape[0], S_NNZ, S_FEATURES
    # The hot dense block builds ON DEVICE from the compact hot COO
    # (device_dense_dtype): the host→device copy carries ~0.8 GB of
    # triples (12 B/hot nnz) instead of the materialized 4.3 GB bf16 block
    # (~5x fewer bytes). Tail/scalars still cast bf16 on host first
    # (cast_features), then one device_put. BlockedEllRows keeps both X
    # passes scatter-free AND scan-free: the tail matvec is ladder-width ELL
    # row buckets (gather + dense einsum, bf16 multiply / f32 accumulate)
    # instead of a full-tail cumsum — the layout exists to avoid TPU
    # scatter-adds and scans (not measured on the current chip — PERF.md).
    # The solver still works in the full R^10M space.
    H = to_blocked_ell(SparseRows(ind, va, d), S_DENSE,
                       device_dense_dtype=jnp.bfloat16)
    total_nnz = n * (k + 1)
    stats = {
        # hot/tail split + the width ladder's pad waste in the blocked-ELL
        # tail: layout facts (not wall-clocks) that make the sparse legs' cost model
        # auditable from the JSON line alone.
        "sparse10m_tail_pad_waste": round(float(H.tail_pad_waste), 4),
        "sparse10m_tail_nnz_frac": round(H.tail_nnz / total_nnz, 4),
        "sparse10m_hot_nnz_frac": round(1.0 - H.tail_nnz / total_nnz, 4),
        "sparse10m_ell_width_buckets": len(H.ell_vals),
    }
    return jax.device_put(cast_features(make_batch(H, y))), stats


def dense_arrays(seed: int = 0, rows: int = D_ROWS):
    """Host (X, y) of the dense logistic problem: a full-strength planted
    signal over D_FEATURES standard-normal columns."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, D_FEATURES)).astype(np.float32)
    w_true = rng.normal(size=D_FEATURES).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.uniform(size=rows) < p).astype(np.float32)
    return X, y


def dense_problem(seed: int = 0):
    # Full-strength planted signal + weak regularization: the solve stays
    # below the f32 precision floor for the whole iteration budget, so the
    # metric measures steady-state iteration throughput.
    #
    # Storage stays f32 HERE deliberately: measured A/B (interleaved reps,
    # same data) has bf16 ~30% SLOWER on this leg — at (524k, 256)×16 lanes
    # the X passes are already amortized across lanes and the inserted
    # converts outweigh the bandwidth saving. bf16 pays off where feature
    # bytes dominate (the sparse leg's 2 GB hot block); see docs/PERF.md.
    return jax.device_put(make_batch(*dense_arrays(seed)))


def _best_of(fn) -> tuple:
    """(best_seconds, last_result); ``fn`` closes its own timing with a
    host readback of an O(1)-byte result."""
    fn()  # warm-up: compile + autotune
    best, out = float("inf"), None
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def run_sparse(batch) -> float:
    """Single-lane leg: the scalar margin-cached solve (non-sweep shape)."""
    rows = int(batch.y.shape[0])  # derived: a stale rows= can't skew the JSON
    cfg = OptimizerConfig(max_iters=S_ITERS, tolerance=0.0, reg=l2(),
                          reg_weight=1e-3, history=5)

    def once():
        import jax.numpy as jnp

        _, res = train_glm(batch, TaskType.LOGISTIC_REGRESSION, cfg)
        # O(1)-byte readback closes the timing — fetching the 10M-dim w
        # itself would put a ~40 MB device→host copy inside the timed region
        return jax.device_get((jnp.sum(res.w), res.iterations))

    best, (_, iters) = _best_of(once)
    return rows * int(iters) / best


def run_sparse_grid(batch) -> float:
    """Headline: the 8-lane reg-weight sweep, one lock-step program.

    S/Y history stored bf16 (lane_history_dtype): the (m, d, G) buffers
    are the biggest solver-state HBM stream at d=10M × 8 lanes, and every
    steering inner product stays f32 (cached at push from the unrounded
    pair) — measured +7% at G=8 / +10% at G=16 with per-lane final losses
    within the f32 run's own noise floor (docs/PERF.md; quality pinned by
    tests/test_lane_solver.py::test_lane_grid_bf16_history_quality)."""
    rows = int(batch.y.shape[0])
    cfg = OptimizerConfig(max_iters=S_ITERS, tolerance=0.0, reg=l2(),
                          reg_weight=0.0, history=5,
                          lane_history_dtype="bfloat16")

    def once():
        import jax.numpy as jnp

        res, _ = train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                                S_GRID, device_results=True)
        return jax.device_get((jnp.sum(res.w), jnp.sum(res.iterations)))

    best, (_, iters) = _best_of(once)
    return rows * int(iters) / best


def _streamed_problem(chunk_rows: int):
    """The dense problem re-laid as HOST chunks + the streamed solve
    config (shared by the single-chip and mesh streamed legs)."""
    rng = np.random.default_rng(1)
    X = rng.normal(size=(D_ROWS, D_FEATURES)).astype(np.float32)
    w_true = rng.normal(size=D_FEATURES).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.uniform(size=D_ROWS) < p).astype(np.float32)
    cb = chunk_batch(make_batch(X, y), chunk_rows)
    cfg = OptimizerConfig(max_iters=D_ITERS, tolerance=0.0, reg=l2(),
                          reg_weight=1e-3, history=5)
    return cb, cfg


def run_streamed(chunk_rows: int = 1 << 16) -> float:
    """Streamed-objective leg (round 6): the out-of-HBM execution regime —
    the dense problem re-laid as HOST chunks, solved by the streamed
    L-BFGS (optim/streamed.py), so every iteration re-uploads the dataset
    twice (direction pass + gradient pass). The number is the price of
    training past HBM: rows·iters/s here ÷ the resident single-lane number
    is the host-link tax, and the flagship's 100M-row auto-trip pays
    exactly this rate on its fixed-effect solves."""
    cb, cfg = _streamed_problem(chunk_rows)

    def once():
        # the streamed solver's own host readbacks close the timing
        _, res = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
        return int(res.iterations)

    best, iters = _best_of(once)
    return D_ROWS * iters / best


def run_streamed_mesh(chunk_rows: int = 1 << 16) -> tuple:
    """Streamed-MESH leg (round 7): the same out-of-HBM problem with every
    chunk row-sharded across a mesh over ALL visible chips
    (optim/streamed.py mesh mode — each device streams 1/D of each chunk,
    one hierarchical psum per evaluation). Aggregate rows·iters/s measures
    the pod-scale streamed regime; per-chip = aggregate / n_chips pins the
    sharding overhead against the single-chip `streamed_dense` leg (the
    acceptance bound: within 2x)."""
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    n_chips = int(mesh.devices.size)
    cb, cfg = _streamed_problem(chunk_rows)

    def once():
        _, res = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg,
                           mesh=mesh)
        return int(res.iterations)

    best, iters = _best_of(once)
    return D_ROWS * iters / best, n_chips


# --- GAME random-effect leg (round 8): pipelined, straggler-free blocks ---
# A skewed (power-law) entity-size distribution with a thin slice of
# ill-conditioned straggler entities — the workload where the sequential
# block loop pays `chunks × max(lane iters)` device time plus a blocking
# readback per bucket. The pipelined leg runs the depth-1 double-buffered
# loop with the compacted straggler re-solve (budget below); the
# sequential leg is the pre-round-8 shape (depth 0, no compaction).
GR_ENTITIES = 1024
GR_D = 8
GR_ITERS = 48
GR_BUDGET = 8


def game_re_problem(seed: int = 0):
    """(RandomEffectDataset, rows-per-raw-entity) for the game_re legs."""
    from photon_tpu.game.dataset import GameData, RandomEffectDataset

    rng = np.random.default_rng(seed)
    E, d = GR_ENTITIES, GR_D
    sizes = np.clip(rng.zipf(1.3, size=E) * 8, 8, 256).astype(np.int64)
    ids = np.repeat(np.arange(E), sizes)
    n = ids.shape[0]
    X = rng.normal(size=(n, d)).astype(np.float32)
    u = rng.normal(size=(E, d)).astype(np.float32)
    # ~2% stragglers: wildly anisotropic feature scaling + separable labels
    # drag those entities' L-BFGS lanes to the iteration cap while typical
    # entities converge in a handful of steps.
    bad = rng.choice(E, size=max(E // 50, 1), replace=False)
    mask = np.isin(ids, bad)
    X[mask] *= np.geomspace(1e-2, 1e2, d).astype(np.float32)[None, :]
    margin = np.einsum("nd,nd->n", X, u[ids])
    y = (rng.uniform(size=n)
         < 1 / (1 + np.exp(-np.clip(margin, -30, 30)))).astype(np.float32)
    y[mask] = (margin[mask] > 0).astype(np.float32)
    data = GameData.build(y, {"re": X}, {"e": ids})
    ds = RandomEffectDataset.build(data, "e", "re")
    return ds, np.bincount(ids, minlength=E)


def run_game_re(ds, rows, pipelined: bool) -> float:
    """rows·iters/s: Σ_e active-rows_e × iters_e / wall. Per-entity
    iterations are GENUINE solver iterations (vmap freezes finished
    lanes), so wall-clock wasted running finished lanes to a chunk
    straggler's horizon shows up directly as a lower rate."""
    from photon_tpu.game.random_effect import RandomEffectCoordinate

    cfg = OptimizerConfig(max_iters=GR_ITERS, tolerance=1e-6, reg=l2(),
                          reg_weight=1e-3, history=5)
    coord = RandomEffectCoordinate(
        ds, TaskType.LOGISTIC_REGRESSION, cfg,
        pipeline_depth=1 if pipelined else 0,
        straggler_budget=GR_BUDGET if pipelined else None)
    offs = np.zeros(int(ds.entity_dense.shape[0]), np.float32)

    def once():
        # train()'s own final-block readback closes the timing
        _, stats = coord.train(offs)
        return stats

    best, stats = _best_of(once)
    # iterations_per_entity is dense-id-indexed; entity_keys maps it back
    # to the raw ids the row counts are keyed by.
    keys = np.asarray(ds.entity_keys).astype(np.int64)
    work = float((rows[keys] * stats.iterations_per_entity).sum())
    return work / best


# --- GAME end-to-end leg (round 13): the composed pod-scale regime --------
# The paper's headline workload, run through EVERY composition layer at
# once: a sparse fixed-effect coordinate whose shard lives as a HOST
# blocked-ELL chunk ladder and solves on the mesh-streamed backend (one
# psum per evaluation), random-effect buckets entity-sharded over the
# same mesh, and inter-coordinate scores exchanged through host margin
# caches. The resident leg is the same 2-coordinate, 2-sweep fit with the
# fixed shard device-resident (blocked-ELL) on one chip — the acceptance
# bar is streamed+mesh within 1.3x of its rows·iters/s (the streaming
# tax at resident-feasible scale); `game_e2e_beyond_resident_ok` is the
# existence proof that the streamed fit completes with the dataset
# estimate ABOVE the (synthetic) per-chip budget — the regime that
# previously raised outright for blocked-ELL + mesh.
GE_ROWS = 1 << 16
GE_ENTITIES = 1024
GE_D_FIXED = 4096
GE_NNZ = 8
GE_D_RE = 8
GE_D_DENSE = 256
GE_CHUNK_ROWS = 1 << 13
GE_SWEEPS = 2
GE_ITERS_F = 12
GE_ITERS_R = 8
GE_REPS = 2


def game_e2e_problem(seed: int = 0):
    """(y, sparse fixed shard, dense RE shard, entity ids) — a planted
    mixed-effect logistic problem with a power-law sparse fixed space."""
    rng = np.random.default_rng(seed)
    n, E, df, dr, k = GE_ROWS, GE_ENTITIES, GE_D_FIXED, GE_D_RE, GE_NNZ
    col = (rng.zipf(1.4, size=(n, k)).astype(np.int64) - 1) % (df - 1)
    ind = np.concatenate([col, np.full((n, 1), df - 1)], axis=1).astype(
        np.int32)
    val = np.concatenate([rng.normal(size=(n, k)).astype(np.float32),
                          np.ones((n, 1), np.float32)], axis=1)
    w_true = np.zeros(df, np.float32)
    hot = 2048
    w_true[:hot] = rng.normal(size=hot) / np.sqrt(np.arange(1, hot + 1))
    ent = rng.integers(0, E, size=n)
    Xr = rng.normal(size=(n, dr)).astype(np.float32)
    u_true = rng.normal(size=(E, dr)).astype(np.float32) * 0.5
    margin = np.einsum("nk,nk->n", val, w_true[ind]) + \
        np.einsum("nd,nd->n", Xr, u_true[ent])
    y = (rng.uniform(size=n)
         < 1 / (1 + np.exp(-np.clip(margin, -30, 30)))).astype(np.float32)
    return y, SparseRows(ind, val, df), Xr, ent


def _game_e2e_fit(y, fixed_shard, Xr, ent, mesh):
    from photon_tpu.game.dataset import GameData
    from photon_tpu.game.estimator import (FixedEffectConfig,
                                           GameEstimator,
                                           RandomEffectConfig)

    cfg_f = OptimizerConfig(max_iters=GE_ITERS_F, tolerance=0.0, reg=l2(),
                            reg_weight=1e-3, history=5)
    cfg_r = OptimizerConfig(max_iters=GE_ITERS_R, tolerance=1e-6, reg=l2(),
                            reg_weight=1.0, history=4)
    data = GameData.build(y, {"fx": fixed_shard, "rs": Xr}, {"e": ent})
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectConfig("fx", cfg_f),
            "re": RandomEffectConfig("e", "rs", cfg_r)},
        n_sweeps=GE_SWEEPS, mesh=mesh)
    return est.fit(data)[0]


def _game_e2e_work(result, n_rows: int, n_entities: int) -> float:
    """rows·iters of one fit: full-row fixed-effect iterations plus the
    random-effect iteration total at the mean entity row count (the fused
    resident path keeps only totals, so both legs use the same
    accounting)."""
    fixed_iters = sum(int(r.iterations)
                      for r in result.descent.coordinate_stats["fixed"])
    re_iters = sum(int(s.total_iterations)
                   for s in result.descent.coordinate_stats["re"])
    return n_rows * fixed_iters + (n_rows / n_entities) * re_iters


def run_game_e2e(problem, streamed: bool) -> dict:
    """One leg: best-of-GE_REPS wall over the full 2-coordinate fit."""
    from photon_tpu.data.dataset import chunk_blocked_ell, make_batch
    from photon_tpu.data.matrix import to_blocked_ell
    from photon_tpu.parallel.mesh import make_mesh

    y, sp, Xr, ent = problem
    n = int(y.shape[0])
    if streamed:
        mesh = make_mesh()
        n_chips = int(mesh.devices.size)
        cb = chunk_blocked_ell(make_batch(sp, y), GE_CHUNK_ROWS,
                               GE_D_DENSE, n_shards=n_chips)
        fixed_shard = cb.X
        est_bytes = int(sp.indices.nbytes + sp.values.nbytes + 12 * n)
        budget = est_bytes // 2  # synthetic: the estimate EXCEEDS it
    else:
        mesh = None
        n_chips = 1
        fixed_shard = jax.device_put(to_blocked_ell(sp, GE_D_DENSE))
        est_bytes = budget = 0

    _game_e2e_fit(y, fixed_shard, Xr, ent, mesh)  # compile warm-up
    best, result = float("inf"), None
    for _ in range(GE_REPS):
        t0 = time.perf_counter()
        result = _game_e2e_fit(y, fixed_shard, Xr, ent, mesh)
        best = min(best, time.perf_counter() - t0)
    work = _game_e2e_work(result, n, GE_ENTITIES)
    out = {"rows_iters_per_sec": work / best, "n_chips": n_chips,
           "wall_s": best, "_result": result}
    if streamed:
        out["beyond_resident_ok"] = est_bytes > budget
    return out
# --- continual refresh leg (round 14): rows changed → new model serving --
# The flywheel's headline number: with a trained GAME model saved (the
# resident game_e2e fit doubles as the full retrain), a delta drop
# touches RF_TOUCHED_FRAC of the random-effect entities; the measured
# wall is delta-diff → prior warm-started partial re-solve of ONLY the
# touched entities (photon_tpu/continual) → parity-probed atomic publish
# + hot swap into a live CoefficientStore. The refresh is measured at
# hourly steady state (a warming refresh with a DIFFERENT touched set
# runs first, and the leg asserts the measured refresh added ZERO
# compacted-solve program signatures — the continual_refresh_no_retrace
# fact, live). Acceptance: speedup_vs_full_retrain ≥ 10× at 2% touched.
RF_TOUCHED_FRAC = 0.02
RF_ROWS_PER_TOUCHED = 64


def _refresh_drop(problem, touched, seed: int):
    """A delta drop: RF_ROWS_PER_TOUCHED fresh rows per touched entity,
    same feature distributions as the training data."""
    rng = np.random.default_rng(seed)
    _, sp, Xr, _ = problem
    df, dr, k = sp.n_features, Xr.shape[1], GE_NNZ
    ent_d = np.repeat(np.asarray(touched, np.int64), RF_ROWS_PER_TOUCHED)
    n = ent_d.shape[0]
    col = (rng.zipf(1.4, size=(n, k)).astype(np.int64) - 1) % (df - 1)
    ind = np.concatenate([col, np.full((n, 1), df - 1)], axis=1).astype(
        np.int32)
    val = np.concatenate([rng.normal(size=(n, k)).astype(np.float32),
                          np.ones((n, 1), np.float32)], axis=1)
    Xr_d = rng.normal(size=(n, dr)).astype(np.float32)
    y_d = (rng.uniform(size=n) < 0.5).astype(np.float32)
    from photon_tpu.game.dataset import GameData

    return GameData.build(y_d, {"fx": SparseRows(ind, val, df),
                                "rs": Xr_d}, {"e": ent_d})


def run_refresh_e2e(problem, resident: dict) -> dict:
    """One leg: full-retrain wall (the resident game_e2e fit) vs the
    "rows changed → new model serving" wall of the continual path."""
    import tempfile

    from photon_tpu import continual
    from photon_tpu.game.dataset import GameData
    from photon_tpu.serving.store import CoefficientStore

    y, sp, Xr, ent = problem
    prev = resident["_result"].model
    full_wall = resident["wall_s"]
    cfg_r = resident["_result"].configs["re"].optimizer
    data = GameData.build(y, {"fx": sp, "rs": Xr}, {"e": ent})
    manifest = continual.build_manifest(data)
    live = CoefficientStore.from_game_model(prev)

    rng = np.random.default_rng(3)
    n_touch = max(int(GE_ENTITIES * RF_TOUCHED_FRAC), 1)
    touched_w = rng.choice(GE_ENTITIES, size=n_touch, replace=False)
    touched = rng.choice(np.setdiff1d(np.arange(GE_ENTITIES), touched_w),
                         size=n_touch, replace=False)
    # warm the refresh programs with a DIFFERENT touched set (steady state)
    drop_w = _refresh_drop(problem, touched_w, seed=5)
    plan_w = continual.diff_manifest(manifest, drop_w, prev)
    continual.refresh_game_model(prev, drop_w, plan_w, {"re": cfg_r})
    sig_baseline = len(continual.RefreshResult.signatures())

    drop = _refresh_drop(problem, touched, seed=6)
    with tempfile.TemporaryDirectory(prefix="photon_refresh_bench_") as root:
        # the staleness clock starts when the delta's rows changed — here,
        # the moment the drop exists; hot_swap gauges rows-changed →
        # servable seconds (continual.staleness_s) at cutover
        rows_changed_unix = time.time()
        t0 = time.perf_counter()
        plan = continual.diff_manifest(manifest, drop, prev)
        res = continual.refresh_game_model(prev, drop, plan, {"re": cfg_r})
        new_store = CoefficientStore.from_game_model(res.model)
        swap = continual.hot_swap(live, new_store, root=root,
                                  probe=continual.ParityProbe(bound=1e3),
                                  rows_changed_unix=rows_changed_unix)
        wall = time.perf_counter() - t0
    # the acceptance bar's no-retrace half, asserted live: the measured
    # (steady-state) refresh compiled nothing
    continual.RefreshResult.assert_no_retrace(sig_baseline)
    return {
        "wall_s": wall, "full_retrain_wall_s": full_wall,
        "speedup_vs_full_retrain": full_wall / wall,
        "touched_frac": n_touch / GE_ENTITIES,
        "n_touched": int(plan.n_touched),
        "staleness_s": swap["staleness_s"],
    }


# The "millions of users" regime: many tiny requests against the program
# ladder + coefficient store + micro-batching dispatcher
# (photon_tpu/serving/). A closed loop of SV_CLIENTS synchronous clients
# drives a zipf entity mix (the reference's ads traffic shape: a few hot
# members dominate, a long cold tail — the tail beyond the store's E
# entities exercises the cold-miss fixed-effect-only fallback). Reported:
# QPS + p50/p95/p99 request latency; the leg ASSERTS the steady state
# never retraced (TraceSignatureLog: ≤ one program per ladder rung).
SV_ENTITIES = 4096
SV_D_FIXED = 64
SV_D_RE = 8
SV_SPARSE_K = 8
SV_ZIPF = 1.2
SV_CLIENTS = 32
SV_WARM_REQUESTS = 512
SV_REQUESTS = 8192
SV_MAX_BATCH = 64
SV_MAX_DELAY_US = 200


def serving_problem(seed: int = 0):
    """(ladder, request pool) for the serving leg: a fixed+random GAME
    model frozen into a CoefficientStore, its pow2 program ladder warmed,
    and a pre-generated zipf request mix (request build cost must not
    pollute the measured serving loop)."""
    import jax.numpy as jnp

    from photon_tpu import serving
    from photon_tpu.game.model import (FixedEffectModel, GameModel,
                                       RandomEffectModel)
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel

    rng = np.random.default_rng(seed)
    E, df, dr, k = SV_ENTITIES, SV_D_FIXED, SV_D_RE, SV_SPARSE_K
    task = TaskType.LOGISTIC_REGRESSION
    keys = np.asarray(sorted(str(i) for i in range(E)))
    model = GameModel({
        "fixed": FixedEffectModel(GeneralizedLinearModel(
            Coefficients(jnp.asarray(
                rng.normal(size=df).astype(np.float32))), task), "global"),
        "perMember": RandomEffectModel(
            entity_name="memberId", feature_shard="member", task=task,
            coefficients=jnp.asarray(
                rng.normal(size=(E, dr)).astype(np.float32)),
            entity_keys=keys,
            key_to_index={kk: i for i, kk in enumerate(keys.tolist())}),
    }, task)
    store = serving.CoefficientStore.from_game_model(model)
    ladder = serving.ProgramLadder(store, floor=8, max_batch=SV_MAX_BATCH,
                                   sparse_k={"member": k}, output_mean=True)
    ladder.warmup()

    n = SV_WARM_REQUESTS + SV_REQUESTS
    # zipf entity popularity; ranks past E are the cold tail (~ unseen
    # members), scoring the fixed-effect-only fallback
    ents = (rng.zipf(SV_ZIPF, size=n).astype(np.int64) - 1) % (2 * E)
    xg = rng.normal(size=(n, df)).astype(np.float32)
    ind = rng.integers(0, dr, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    pool = [serving.ScoreRequest(
        features={"global": xg[i], "member": (ind[i], val[i])},
        entities={"memberId": str(int(ents[i]))}) for i in range(n)]
    return ladder, pool


def run_serving(ladder, pool) -> dict:
    """Closed-loop QPS + latency percentiles: SV_CLIENTS threads issue
    synchronous requests until the pool drains. A fresh dispatcher serves
    the timed portion (the warm one absorbed compile/dispatch jitter);
    both ride the SAME ladder, so the retrace assertion spans the whole
    run."""
    import threading

    from photon_tpu import serving

    def drive(pool_slice) -> dict:
        d = serving.MicroBatchDispatcher(
            ladder, max_batch=SV_MAX_BATCH, max_delay_us=SV_MAX_DELAY_US)
        it = iter(pool_slice)
        lock = threading.Lock()

        def client():
            while True:
                with lock:
                    req = next(it, None)
                if req is None:
                    return
                d.score(req, timeout=60)

        threads = [threading.Thread(target=client)
                   for _ in range(SV_CLIENTS)]
        t0 = time.perf_counter()
        [t.start() for t in threads]
        [t.join() for t in threads]
        wall = time.perf_counter() - t0
        d.close()
        stats = d.latency_stats()
        stats["wall_s"] = wall
        return stats

    from photon_tpu.telemetry import trace

    drive(pool[:SV_WARM_REQUESTS])
    # the timed drive runs with request tracing ARMED: the retrace
    # assertion below then proves arming tracing adds zero new rung
    # signatures (the live half of serving_trace_off_is_free)
    with trace.tracing(k=8):
        stats = drive(pool[SV_WARM_REQUESTS:])
    # the acceptance bar: steady-state serving provably never retraces
    # (at most one compiled program per ladder rung, zero weak-type drift)
    ladder.assert_no_retrace()
    n = stats["n"]
    return {
        "qps": n / stats["wall_s"],
        "p50_ms": stats["p50_ms"], "p95_ms": stats["p95_ms"],
        "p99_ms": stats["p99_ms"], "n_requests": n,
    }


# --- quantized serving rung leg (round 15) --------------------------------
# The SAME closed-loop drive as serving_qps through an int8-quantized
# ProgramLadder (photon_tpu/serving: row-wise scales computed at store
# load via data.matrix.quantize_blocks, dequant fused into the margin
# matvec — coefficient HBM/gather traffic drops 4x). warmup() runs the
# measured accuracy gate (probe margin max |Δ| vs the f32 rungs must sit
# within SVQ_EPSILON or the ladder REFUSES to serve), and the leg
# reports that measured delta as serving_quantized_margin_maxdiff —
# sentinel-gated LOWER-better ("maxdiff" direction pattern): a quieter
# quantization is a win, a louder one is a regression even if QPS holds.
SVQ_EPSILON = 0.5


def serving_quantized_ladder(ladder):
    from photon_tpu import serving

    q = serving.ProgramLadder(
        ladder.store, floor=8, max_batch=SV_MAX_BATCH,
        sparse_k={"member": SV_SPARSE_K}, output_mean=True,
        model_tag="model-int8", quantize="int8",
        quant_epsilon=SVQ_EPSILON)
    q.warmup()  # the accuracy gate: QuantizationRefused on breach
    return q


# --- open-loop SLO leg (overload round) -----------------------------------
# serving_qps is CLOSED-loop: clients wait for answers, so offered load
# can never exceed capacity and overload is unobservable by construction.
# Production traffic is OPEN-loop — arrivals at a fixed rate, indifferent
# to our latency — so this leg drives the dispatcher at a swept arrival
# rate with the admission policy ARMED (per-request deadline, watermark
# shedding, non-blocking submit; photon_tpu/serving/admission.py) and
# emits an SLO verdict (the highest offered rate with served p99 <=
# SLO_TARGET_P99_MS and shed <= SLO_SHED_PASS_FRAC) plus the
# graceful-degradation curve past saturation: shed fraction RISES while
# the p99 of requests actually served stays BOUNDED near the deadline,
# and every submitted future resolves (zero lost). The closing
# assert_no_retrace spans the admission-OFF serving_qps run and this
# admission-ON sweep on the same ladder — the on/off program-invariance
# fact, live (its static twin is the registered
# serving_admission_program_invariance contract).
SLO_TARGET_P99_MS = 50.0
SLO_DEADLINE_MS = 100.0
SLO_WATERMARK = 512
SLO_RATE_FACTORS = (0.25, 0.5, 1.0, 2.5)
SLO_SECONDS_PER_RATE = 1.5
SLO_MIN_REQUESTS = 256
SLO_MAX_REQUESTS = 8192
SLO_SHED_PASS_FRAC = 0.01


def _slo_policy():
    from photon_tpu import serving

    return serving.AdmissionPolicy(deadline_ms=SLO_DEADLINE_MS,
                                   shed_watermark=SLO_WATERMARK,
                                   submit_timeout_s=0.0)


def _drive_open_loop(ladder, reqs, qps: float) -> dict:
    """Fixed-arrival-rate driver: request i submits at t0 + i/qps
    regardless of completions (the open loop), then every future
    resolves — a float score or a typed `Shed`, never a leak."""
    from photon_tpu import serving

    d = serving.MicroBatchDispatcher(
        ladder, max_batch=SV_MAX_BATCH, max_delay_us=SV_MAX_DELAY_US,
        policy=_slo_policy())
    period = 1.0 / qps
    futs = []
    t0 = time.perf_counter()
    for i, r in enumerate(reqs):
        lag = (t0 + i * period) - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        futs.append(d.submit(r))
    submit_wall = time.perf_counter() - t0
    results = [f.result(timeout=120) for f in futs]
    d.close()
    n = len(results)
    sheds = [r for r in results if isinstance(r, serving.Shed)]
    stats = d.latency_stats()
    return {
        "offered_qps": round(qps, 1),
        "achieved_submit_qps": round(n / submit_wall, 1),
        "n": n,
        "served": stats["n"],
        "shed_frac": round(len(sheds) / n, 4),
        "deadline_expired": sum(
            1 for s in sheds if s.reason == "deadline_expired"),
        "served_p99_ms": (None if stats["p99_ms"] is None
                          else round(stats["p99_ms"], 3)),
        "lost_futures": sum(1 for f in futs if not f.done()),
    }


def _calibrate_capacity(ladder, reqs) -> float:
    """Short closed-loop burst (8 clients) → the saturation QPS the
    open-loop sweep brackets with SLO_RATE_FACTORS."""
    import threading

    from photon_tpu import serving

    d = serving.MicroBatchDispatcher(
        ladder, max_batch=SV_MAX_BATCH, max_delay_us=SV_MAX_DELAY_US)
    it = iter(reqs)
    lock = threading.Lock()

    def client():
        while True:
            with lock:
                req = next(it, None)
            if req is None:
                return
            d.score(req, timeout=60)

    threads = [threading.Thread(target=client) for _ in range(8)]
    t0 = time.perf_counter()
    [t.start() for t in threads]
    [t.join() for t in threads]
    wall = time.perf_counter() - t0
    d.close()
    return len(reqs) / wall


def run_serving_slo(ladder, pool, capacity_qps: float | None = None) -> dict:
    """The open-loop QPS sweep: SLO verdict + degradation curve (see the
    leg comment above)."""
    from photon_tpu.telemetry import trace

    if capacity_qps is None:
        capacity_qps = _calibrate_capacity(ladder, pool[:512])
    curve = []
    # the whole sweep runs with request tracing armed: the reservoir
    # keeps the K slowest requests ACROSS every offered rate with their
    # full hop breakdown — the overload tail, attributed
    with trace.tracing(k=8) as reservoir:
        for f in SLO_RATE_FACTORS:
            rate = capacity_qps * f
            n = int(min(max(rate * SLO_SECONDS_PER_RATE, SLO_MIN_REQUESTS),
                        SLO_MAX_REQUESTS))
            reqs = [pool[i % len(pool)] for i in range(n)]
            curve.append(_drive_open_loop(ladder, reqs, rate))
        exemplars = reservoir.snapshot()
    # the retrace bound now spans admission off (serving_qps) AND on
    ladder.assert_no_retrace()
    lost = sum(pt["lost_futures"] for pt in curve)
    passing = [pt for pt in curve
               if pt["served_p99_ms"] is not None
               and pt["served_p99_ms"] <= SLO_TARGET_P99_MS
               and pt["shed_frac"] <= SLO_SHED_PASS_FRAC]
    sustained = passing[-1] if passing else None
    overload = curve[-1]
    # "bounded" past saturation: served requests waited at most their
    # deadline before dispatch, so p99 must sit near the deadline, not
    # grow with offered load (2x = deadline + generous program/readback)
    p99_bound_ms = 2.0 * SLO_DEADLINE_MS
    bounded = (overload["served_p99_ms"] is not None
               and overload["served_p99_ms"] <= p99_bound_ms)
    degradation = (sustained is None
                   or overload["shed_frac"] >= sustained["shed_frac"])
    ok = bool(sustained is not None and bounded and degradation
              and lost == 0)
    sus_qps = 0.0 if sustained is None else sustained["offered_qps"]
    sus_p99 = (curve[0]["served_p99_ms"] if sustained is None
               else sustained["served_p99_ms"]) or 0.0
    verdict = (
        f"SLO {'PASS' if ok else 'FAIL'}: served p99 <= "
        f"{SLO_TARGET_P99_MS:.0f} ms at {sus_qps:.0f} QPS offered "
        f"(shed <= {100 * SLO_SHED_PASS_FRAC:.0f}%); past saturation "
        f"({overload['offered_qps']:.0f} QPS): shed "
        f"{100 * overload['shed_frac']:.1f}%, served p99 "
        f"{overload['served_p99_ms']} ms (bound {p99_bound_ms:.0f} ms), "
        f"lost futures {lost}")
    return {
        "sustained_qps": sus_qps,
        "p99_ms": sus_p99,
        "overload_qps": overload["offered_qps"],
        "overload_p99_ms": overload["served_p99_ms"] or 0.0,
        "overload_shed_pct": round(100 * overload["shed_frac"], 2),
        "lost_futures": lost,
        "ok": ok,
        "verdict": verdict,
        "curve": curve,
        # tail exemplars (slowest-first, full hop breakdown) + the
        # slowest request's total as a gateable lower-better number
        "exemplars": exemplars,
        "exemplar_slowest_ms":
            exemplars[0]["total_ms"] if exemplars else 0.0,
    }


# --- checkpoint-overhead leg (round 10) -----------------------------------
# The elasticity tax: the SAME streamed-dense problem as `streamed_dense`,
# solved with crash-consistent snapshots every CK_EVERY_EVALS objective
# evaluations (photon_tpu/checkpoint — async writer thread, so the solver
# only pays state packing) vs. none. Reported as the rows·iters/s delta
# plus snapshot volume; the acceptance bound is ≤5% overhead at this
# default cadence (docs/ELASTICITY.md / PERF.md).
CK_EVERY_EVALS = 16


def run_checkpoint_overhead(chunk_rows: int = 1 << 16,
                            baseline_rate: float | None = None,
                            reps: int = REPS) -> dict:
    import shutil
    import tempfile

    from photon_tpu import checkpoint
    from photon_tpu import telemetry as _tm

    cb, cfg = _streamed_problem(chunk_rows)
    rows = cb.n

    def once_plain():
        _, res = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
        return int(res.iterations)

    if baseline_rate is None:
        best, iters = _best_of(once_plain)
        baseline_rate = rows * iters / best

    ck_dir = tempfile.mkdtemp(prefix="photon_ckpt_bench_")

    def once_ck():
        # fresh store per rep: a leftover snapshot would resume (and
        # shortcut) the solve instead of measuring it
        shutil.rmtree(ck_dir, ignore_errors=True)
        with checkpoint.session(ck_dir, every_evals=CK_EVERY_EVALS,
                                every_s=None, async_writer=True):
            _, res = train_glm(cb, TaskType.LOGISTIC_REGRESSION, cfg)
        return int(res.iterations)

    run = _tm.current_run()
    c0 = dict(run.counters) if run is not None else {}
    t0 = time.perf_counter()
    global REPS
    saved, REPS = REPS, reps
    try:
        best_ck, iters_ck = _best_of(once_ck)
    finally:
        REPS = saved
    wall = time.perf_counter() - t0
    if run is not None:
        c1 = run.counters
        n_snaps = c1.get("checkpoint.snapshots", 0) - \
            c0.get("checkpoint.snapshots", 0)
        n_bytes = c1.get("checkpoint.bytes", 0) - \
            c0.get("checkpoint.bytes", 0)
    else:  # no telemetry attached: estimate from the retained snapshots
        store = checkpoint.SnapshotStore(ck_dir)
        n_snaps = store.latest_seq() + 1
        n_bytes = sum(os.path.getsize(os.path.join(dp, f))
                      for dp, _, fs in os.walk(ck_dir) for f in fs)
    shutil.rmtree(ck_dir, ignore_errors=True)
    rate_ck = rows * iters_ck / best_ck
    return {
        "rows_iters_per_sec": rate_ck,
        "baseline_rows_iters_per_sec": baseline_rate,
        "overhead_pct": 100.0 * max(1.0 - rate_ck / baseline_rate, 0.0),
        "cadence_evals": CK_EVERY_EVALS,
        "snapshots": int(n_snaps),
        "snapshot_bytes": int(n_bytes),
        "snapshot_bytes_per_sec": (n_bytes / wall if wall > 0 else 0.0),
    }


# --- ingest data plane leg (round 14): decode-once vs cold Avro -----------
# The cold leg decodes a real Avro container through the sharded worker
# pool (data/ingest_plane.py) while committing the columnar chunk cache;
# the cached leg re-opens the SAME dataset from the mmap'd cache (Avro
# untouched — the decode-once regime every epoch after the first pays).
# Acceptance: cached >= 5x cold on this container. The stall leg runs the
# streamed solve's chunk stream under the stall-driven AdaptivePrefetch
# controller and reports the upload-stall share of the pass wall — the
# telemetry-proven "stalled_passes -> ~0" claim in PERF.md round 14.
ING_ROWS = 60_000
ING_NNZ = 8
ING_FILES = 2
ING_CHUNK_ROWS = 1 << 13
ING_SPARSE_K = ING_NNZ + 1
ING_WORKERS = 2


def ingest_problem(seed: int = 0):
    """(avro dir, GameDataConfig, IngestScan) — a wide sparse bag + an
    entity column, written as real deflate containers."""
    import tempfile

    from photon_tpu.data.avro_io import write_avro
    from photon_tpu.data.feature_bags import FeatureShardConfig
    from photon_tpu.data.ingest import (GameDataConfig,
                                        training_example_schema)
    from photon_tpu.data.streaming import scan_ingest

    rng = np.random.default_rng(seed)
    root = tempfile.mkdtemp(prefix="photon_ingest_bench_")
    schema = training_example_schema(feature_bags=("features",),
                                     entity_fields=("memberId",))
    per_file = ING_ROWS // ING_FILES
    for fi in range(ING_FILES):
        names = rng.integers(0, 50_000, size=(per_file, ING_NNZ))
        vals = rng.normal(size=(per_file, ING_NNZ))
        records = [{
            "response": float(rng.integers(0, 2)),
            "offset": None, "weight": None, "uid": str(i),
            "memberId": f"m{rng.integers(0, 5000)}",
            "features": [
                {"name": f"f{names[i, j]}", "term": "",
                 "value": float(vals[i, j])} for j in range(ING_NNZ)],
        } for i in range(per_file)]
        write_avro(os.path.join(root, f"part-{fi:03d}.avro"), records,
                   schema, block_records=2048)
    config = GameDataConfig(
        shards={"features": FeatureShardConfig(bags=("features",),
                                               has_intercept=True,
                                               dense_threshold=64)},
        entity_fields=("memberId",))
    return root, config, scan_ingest(root, config)


def run_ingest(problem) -> dict:
    """{cold_rows_per_sec, cached_rows_per_sec, cached_over_cold,
    upload_stall_pct, stalled_passes} — see the leg comment above."""
    import shutil
    import tempfile

    from photon_tpu import telemetry
    from photon_tpu.data.ingest_plane import (AdaptivePrefetch,
                                              open_chunk_source)

    root, config, scan = problem
    cache_dir = tempfile.mkdtemp(prefix="photon_ingest_cache_")

    def one_pass(cache):
        t0 = time.perf_counter()
        _, chunks = open_chunk_source(
            root, config, scan.index_maps, chunk_rows=ING_CHUNK_ROWS,
            sparse_k=ING_SPARSE_K, workers=ING_WORKERS, cache_dir=cache,
            block_index=scan.block_index)
        rows = sum(c.n for c in chunks)
        return rows, time.perf_counter() - t0

    # cold epoch: worker-pool decode + cache build (what a first run pays)
    rows, cold_s = one_pass(cache_dir)
    # cached epochs: mmap open, Avro untouched; best-of like every leg
    best_cached = float("inf")
    for _ in range(REPS):
        r2, dt = one_pass(cache_dir)
        assert r2 == rows
        best_cached = min(best_cached, dt)
    shutil.rmtree(cache_dir, ignore_errors=True)

    # upload-stall share of a streamed pass under the adaptive controller:
    # the same host-chunked stream the streamed solvers ride, a trivial
    # per-chunk consumer, stall/(stall+compute) from the run's counters.
    cb, _ = _streamed_problem(1 << 16)
    ctl = AdaptivePrefetch()
    run = telemetry.start_run("ingest_stall")
    for _ in range(4):
        for _, b in cb.iter_device(prefetch=ctl):
            jax.block_until_ready(b.y)
    telemetry.finish_run()
    stall = float(run.counters.get("stream.stall_seconds", 0.0))
    # a pass's wall: the waits, the upload calls, the consumer's own time
    wall = (stall + float(run.counters.get("stream.issue_seconds", 0.0))
            + float(run.counters.get("stream.compute_seconds", 0.0)))
    stalled = int(run.counters.get("stream.stalled_passes", 0))
    return {
        "rows": rows,
        "cold_rows_per_sec": rows / cold_s,
        "cached_rows_per_sec": rows / best_cached,
        "cached_over_cold": cold_s / best_cached,
        "upload_stall_pct": 100.0 * stall / max(wall, 1e-9),
        "stalled_passes": stalled,
        "prefetch_depth_final": int(ctl.depth),
    }


def run_dense(batch, grid_weights) -> float:
    cfg = OptimizerConfig(max_iters=D_ITERS, tolerance=0.0, reg=l2(),
                          reg_weight=0.0)

    def once():
        # train_glm_grid's internal device_get closes the timing
        return train_glm_grid(batch, TaskType.LOGISTIC_REGRESSION, cfg,
                              grid_weights)

    best, grid = _best_of(once)
    iters = sum(int(res.iterations) for _, res in grid)
    return D_ROWS * iters / best


# --- tuning_e2e leg (round 16): configs per wall-clock ---------------------
# The lane-batched cost-aware tuner (tuning/lane_tuner.py) evaluating
# TU_CONFIGS hyperparameter configs — GP proposal rounds dispatched as
# fixed pow2 lane chunks with capped-budget screening and warm-started
# survivor re-solves — against the point-at-a-time tuner architecture
# (one full-depth train_glm_grid([w]) program per candidate, the
# reference's one-Spark-job-per-candidate HyperparameterTuner loop,
# timed on a sample and extrapolated). Acceptance: ≥8× configs per
# wall-clock at 256 configs. The leg asserts the tuner's own no-retrace
# bound LIVE: the whole multi-round tune must dispatch exactly two lane
# program signatures (screen + re-solve).
TU_ROWS = 1 << 15
TU_FEATURES = 64  # wide enough that per-config GEMV re-reads X from DRAM
TU_ITERS = 24
TU_CONFIGS = 256
TU_CHUNK = 64
TU_SEQ_SAMPLE = 16  # sequential-baseline sample size (extrapolated)


def tuning_problem(seed: int = 0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=TU_FEATURES).astype(np.float32)

    def draw(n, s):
        r = np.random.default_rng(s)
        X = r.normal(size=(n, TU_FEATURES)).astype(np.float32)
        p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
        y = (r.uniform(size=n) < p).astype(np.float32)
        return jax.device_put(make_batch(X, y))

    return draw(TU_ROWS, seed + 1), draw(TU_ROWS // 4, seed + 2)


def run_tuning_e2e(problem) -> dict:
    from photon_tpu.evaluation.evaluator import default_evaluator
    from photon_tpu.models.training import evaluate_glm_grid
    from photon_tpu.tuning.lane_tuner import (LaneTuningResult,
                                              tune_glm_reg_lanes)

    train, val = problem
    task = TaskType.LOGISTIC_REGRESSION
    cfg = OptimizerConfig(max_iters=TU_ITERS, reg=l2(), history=5)
    evaluator = default_evaluator(task)

    # warm both architectures' programs at FULL size — a chunk-sized warm
    # tune only reaches the first GP observation rung, leaving the later
    # rungs' hyperparameter fits to compile inside the timed run — then
    # assert the lane tuner's retrace bound over the TIMED run below
    tune_glm_reg_lanes(train, task, cfg, val, n_configs=TU_CONFIGS,
                       lane_chunk=TU_CHUNK, seed=7)
    base_sigs = LaneTuningResult.signature_count()
    t0 = time.perf_counter()
    _, best_w, res = tune_glm_reg_lanes(train, task, cfg, val,
                                        n_configs=TU_CONFIGS,
                                        lane_chunk=TU_CHUNK, seed=0)
    lane_wall = time.perf_counter() - t0
    LaneTuningResult.assert_no_retrace(base_sigs)

    # point-at-a-time baseline: each candidate is a full-depth single-lane
    # program + its own validation scoring pass (sampled + extrapolated)
    sample = list(np.geomspace(1e-4, 1e4, TU_SEQ_SAMPLE))

    def one_point(w):
        grid = train_glm_grid(train, task, cfg, [w])
        evaluate_glm_grid(grid, val, evaluator)

    one_point(sample[0])  # warm the single-lane + scoring programs
    t0 = time.perf_counter()
    for w in sample:
        one_point(w)
    seq_wall = time.perf_counter() - t0
    lane_rate = TU_CONFIGS / lane_wall
    seq_rate = TU_SEQ_SAMPLE / seq_wall
    return {"configs_per_sec": lane_rate,
            "sequential_configs_per_sec": seq_rate,
            "speedup_vs_sequential": lane_rate / seq_rate,
            "n_configs": TU_CONFIGS,
            "best_reg_weight": float(best_w),
            "n_rounds": len(res.rounds),
            "round_model_flops": float(res.rounds[0].modeled_flops)}


# ---------------------------------------------------------------------------
# multihost_e2e (round 17): the multi-process data-parallel spine — the
# SAME mesh-streamed GLM solve launched at 1, 2 and 4 spawned processes
# over one 8-device global mesh. Coefficients must be BIT-identical
# across process counts (gloo's reduction tree depends only on the
# global rank count — docs/MULTIHOST.md), every child must return a
# result (parallel.launch raises on a lost or hung rank), and the gated
# number is the priced per-evaluation DCN wire bill: the one psum's
# (d+1)-float payload, while the per-shard features stay host-local.
# Sandboxes that block the localhost gRPC coordinator report
# available=False and the leg's numbers are omitted (an environment
# fact, not a regression — the same convention as the parallel CLI).
MH_PROCESS_COUNTS = (1, 2, 4)


def run_multihost_e2e() -> dict:
    import pathlib
    import tempfile

    from photon_tpu.parallel import selfcheck as sc
    from photon_tpu.parallel.launch import ClusterUnavailable, launch

    root = tempfile.mkdtemp(prefix="photon_bench_mh_")
    sc.write_e2e_dataset(pathlib.Path(root))
    runs: dict = {}
    tdirs: dict = {}
    try:
        for n in MH_PROCESS_COUNTS:
            # each rank writes its p<k>.jsonl event log here — the input
            # the cross-rank aggregation merges
            tdirs[n] = tempfile.mkdtemp(prefix=f"photon_bench_mh_t{n}_")
            t0 = time.perf_counter()
            res = launch(sc.target_stream_solve, n, args=(root, tdirs[n]),
                         timeout_s=420)
            runs[n] = {"wall_s": time.perf_counter() - t0, "res": res}
    except ClusterUnavailable as e:
        return {"available": False,
                "reason": str(e).splitlines()[0][:200]}
    digests = set()
    for n, entry in runs.items():
        ranks = [r["rank"] for r in entry["res"]]
        if ranks != list(range(n)):
            raise AssertionError(
                f"multihost_e2e: lost ranks at n={n}: {ranks}")
        digests.update(r["digest"] for r in entry["res"])
    if len(digests) != 1:
        raise AssertionError("multihost_e2e: coefficient drift across "
                             f"process counts: {sorted(digests)}")
    # price the wire bill straight off the traced psum program — the
    # same estimator the roofline model uses, not a hand-typed constant
    from photon_tpu.analysis import trace_contract
    from photon_tpu.analysis.registry import load_registry
    from photon_tpu.profiling.model import estimate_jaxpr

    spec = load_registry()["multihost_grad_only_dcn"]
    traced = trace_contract(spec)
    cost = estimate_jaxpr(traced.closed_jaxpr)
    feature_bytes = int(np.asarray(traced.example_args[0].X).nbytes)
    # merge the widest run's per-rank event logs into ONE cluster report:
    # per-rank rollups, barrier-wait + decode skew with the straggler
    # rank named, wall-clock-aligned span timeline
    from photon_tpu.telemetry.aggregate import aggregate_cluster

    n_max = max(MH_PROCESS_COUNTS)
    cluster = aggregate_cluster(tdirs[n_max], expect_ranks=n_max)
    cluster["timeline"] = cluster["timeline"][:64]  # bound the JSON line
    if not cluster["complete"]:
        raise AssertionError(
            f"multihost_e2e: cluster report incomplete at n={n_max}: "
            f"missing={cluster['missing_ranks']}")
    return {
        "available": True,
        "dcn_bytes_per_eval": float(cost.collective_bytes),
        "feature_bytes_per_shard": feature_bytes // len(jax.devices()),
        "launch_wall_s": {n: round(runs[n]["wall_s"], 2)
                          for n in MH_PROCESS_COUNTS},
        "n_processes_verified": max(MH_PROCESS_COUNTS),
        "digest": digests.pop(),
        "iterations": int(runs[max(MH_PROCESS_COUNTS)]["res"][0]
                          ["iterations"]),
        "cluster_report": cluster,
    }


def check_contracts() -> int:
    """Trace-only registry check (no benchmark legs, no compiles): exit 0
    iff every hot-path contract holds. See photon_tpu/analysis."""
    from photon_tpu.analysis.contracts import check_registry
    from photon_tpu.analysis.registry import load_registry

    report = check_registry(load_registry())
    violations = [v for entry in report.values()
                  for v in entry.get("violations", [])]
    print(json.dumps({"metric": "analysis_contracts", "ok": not violations,
                      "n_specs": len(report),
                      "n_violations": len(violations)}))
    return 1 if violations else 0


def _telemetry_out_path() -> str | None:
    """--telemetry-out PATH: also write the run's JSONL event stream."""
    if "--telemetry-out" in sys.argv:
        return sys.argv[sys.argv.index("--telemetry-out") + 1]
    return None


def main() -> None:
    if "--check-contracts" in sys.argv:
        raise SystemExit(check_contracts())
    # Every bench run records telemetry (photon_tpu/telemetry): the spans
    # name the legs, and the counters put stall/eval/trial/retrace counts
    # in BENCH_*.json next to the wall-clock numbers. --telemetry-out PATH
    # additionally streams the full JSONL event log for offline reading
    # (python -m photon_tpu.telemetry --report PATH).
    from photon_tpu import profiling, telemetry

    run = telemetry.start_run("bench", jsonl_path=_telemetry_out_path())
    profiling.start_ledger("bench")
    with telemetry.span("leg.sparse_data"):
        batch, sparse_stats = sparse_problem()
    with telemetry.span("leg.sparse_grid8"):
        grid_value = run_sparse_grid(batch)
    with telemetry.span("leg.sparse_single"):
        single_value = run_sparse(batch)
    with telemetry.span("leg.dense_data"):
        dense_batch = dense_problem()
    with telemetry.span("leg.dense_grid16"):
        dense_value = run_dense(dense_batch, D_GRID)
    with telemetry.span("leg.dense_grid256"):
        dense_big_value = run_dense(dense_batch, D_GRID_BIG)
    with telemetry.span("leg.streamed_dense"):
        streamed_value = run_streamed()
    with telemetry.span("leg.checkpoint_overhead"):
        ck_stats = run_checkpoint_overhead(baseline_rate=streamed_value)
    with telemetry.span("leg.streamed_mesh"):
        streamed_mesh_value, streamed_mesh_chips = run_streamed_mesh()
    with telemetry.span("leg.ingest_data"):
        ing_problem = ingest_problem()
    with telemetry.span("leg.ingest_throughput"):
        ing_stats = run_ingest(ing_problem)
    with telemetry.span("leg.game_re_data"):
        gr_ds, gr_rows = game_re_problem()
    with telemetry.span("leg.game_re_sequential"):
        game_re_seq = run_game_re(gr_ds, gr_rows, pipelined=False)
    with telemetry.span("leg.game_re"):
        game_re_value = run_game_re(gr_ds, gr_rows, pipelined=True)
    with telemetry.span("leg.game_e2e_data"):
        ge_problem = game_e2e_problem()
    with telemetry.span("leg.game_e2e_resident"):
        ge_res = run_game_e2e(ge_problem, streamed=False)
    with telemetry.span("leg.game_e2e"):
        ge_str = run_game_e2e(ge_problem, streamed=True)
    with telemetry.span("leg.refresh_e2e"):
        rf_stats = run_refresh_e2e(ge_problem, ge_res)
    with telemetry.span("leg.serving_data"):
        sv_ladder, sv_pool = serving_problem()
    with telemetry.span("leg.serving_qps"):
        serving_stats = run_serving(sv_ladder, sv_pool)
    with telemetry.span("leg.serving_quantized"):
        svq_ladder = serving_quantized_ladder(sv_ladder)
        svq_stats = run_serving(svq_ladder, sv_pool)
    with telemetry.span("leg.serving_slo"):
        slo_stats = run_serving_slo(sv_ladder, sv_pool,
                                    capacity_qps=serving_stats["qps"])
    with telemetry.span("leg.tuning_e2e_data"):
        tu_problem = tuning_problem()
    with telemetry.span("leg.tuning_e2e"):
        tu_stats = run_tuning_e2e(tu_problem)
    with telemetry.span("leg.multihost_e2e"):
        mh_stats = run_multihost_e2e()
    telemetry.finish_run()
    ledger_report = profiling.finish_ledger()
    base = BASELINE_CLUSTER_ROWS_ITERS_PER_SEC
    doc = {
        # schema 2 (profiling.sentinel.SCHEMA_VERSION): the line is
        # self-describing for the regression sentinel — it carries its
        # schema version and the per-leg gate verdicts computed against
        # the BENCH_r0*.json trajectory beside this script.
        "schema": None,  # filled below (sentinel owns the version)
        "gate": None,
        "telemetry": run.report_compact(),
        "metric": "sparse10m_logistic_grid8_rows_iters_per_sec_per_chip",
        "value": round(grid_value, 1),
        "unit": "rows*iters/sec/chip",
        "vs_baseline": round(grid_value / base, 3),
        "legs": {
            "sparse10m_single_lane_rows_iters_per_sec_per_chip":
                round(single_value, 1),
            "sparse10m_single_lane_vs_baseline": round(single_value / base,
                                                       3),
            # blocked-ELL layout facts (round 12): pad waste is gated
            # lower-better by the sentinel; the split/bucket legs are
            # config facts the sentinel excludes from gating.
            **sparse_stats,
            "dense_grid16_rows_iters_per_sec_per_chip": round(dense_value, 1),
            "dense_grid16_vs_baseline": round(dense_value / base, 3),
            "dense_grid256_rows_iters_per_sec_per_chip":
                round(dense_big_value, 1),
            "dense_grid256_vs_baseline": round(dense_big_value / base, 3),
            # out-of-HBM regime (round 6): same dense shape, dataset on
            # HOST, streamed L-BFGS — the rate the 100M-row flagship pays
            "streamed_dense_rows_iters_per_sec_per_chip":
                round(streamed_value, 1),
            "streamed_dense_vs_baseline": round(streamed_value / base, 3),
            # elasticity tax (round 10): the same streamed problem with
            # async crash-consistent snapshots every CK_EVERY_EVALS
            # evaluations (photon_tpu/checkpoint); acceptance bound ≤5%
            "checkpoint_overhead_rows_iters_per_sec":
                round(ck_stats["rows_iters_per_sec"], 1),
            "checkpoint_overhead_pct": round(ck_stats["overhead_pct"], 2),
            "checkpoint_snapshots": ck_stats["snapshots"],
            "checkpoint_snapshot_bytes_per_sec":
                round(ck_stats["snapshot_bytes_per_sec"], 1),
            # streamed MESH regime (round 7): the same host-chunked problem
            # row-sharded over every visible chip, one psum per evaluation;
            # per-chip vs streamed_dense bounds the sharding overhead
            "streamed_mesh_rows_iters_per_sec_aggregate":
                round(streamed_mesh_value, 1),
            "streamed_mesh_rows_iters_per_sec_per_chip":
                round(streamed_mesh_value / streamed_mesh_chips, 1),
            "streamed_mesh_n_chips": streamed_mesh_chips,
            "streamed_mesh_vs_baseline": round(streamed_mesh_value / base,
                                               3),
            # ingest data plane (round 14): cold worker-pool Avro decode
            # (incl. the cache build) vs the decode-once mmap'd cache —
            # acceptance cached_over_cold >= 5 — plus the stall-driven
            # prefetch's upload-stall share of a streamed pass ("stall" in
            # the name gates it LOWER-better; stalled_passes is the
            # telemetry-proven ~0 claim)
            "ingest_throughput_cold_rows_per_sec":
                round(ing_stats["cold_rows_per_sec"], 1),
            "ingest_throughput_cached_rows_per_sec":
                round(ing_stats["cached_rows_per_sec"], 1),
            "ingest_throughput_cached_over_cold":
                round(ing_stats["cached_over_cold"], 2),
            "ingest_throughput_upload_stall_pct":
                round(ing_stats["upload_stall_pct"], 2),
            "ingest_stalled_passes": ing_stats["stalled_passes"],
            # GAME random-effect regime (round 8): skewed entity sizes +
            # ill-conditioned stragglers; pipelined = double-buffered block
            # loop + compacted straggler re-solve, sequential = the
            # pre-round-8 dispatch→blocking-readback→scatter loop
            "game_re_rows_iters_per_sec_per_chip": round(game_re_value, 1),
            "game_re_sequential_rows_iters_per_sec_per_chip":
                round(game_re_seq, 1),
            "game_re_speedup_vs_sequential":
                round(game_re_value / game_re_seq, 3),
            # GAME end-to-end regime (round 13): the composed pod-scale
            # fit — streamed+mesh blocked-ELL fixed effect, entity-sharded
            # RE buckets, host margin-cache score exchange — vs the same
            # fit resident on one chip. Acceptance: streamed_over_resident
            # >= 1/1.3, and the beyond-resident streamed run completed
            # (bool; excluded from gating).
            "game_e2e_rows_iters_per_sec_aggregate":
                round(ge_str["rows_iters_per_sec"], 1),
            "game_e2e_resident_rows_iters_per_sec":
                round(ge_res["rows_iters_per_sec"], 1),
            "game_e2e_streamed_over_resident":
                round(ge_str["rows_iters_per_sec"]
                      / ge_res["rows_iters_per_sec"], 3),
            "game_e2e_n_chips": ge_str["n_chips"],
            "game_e2e_beyond_resident_ok": bool(
                ge_str.get("beyond_resident_ok", False)),
            # continual refresh regime (round 14): rows changed → new
            # model serving, at steady state (warmed programs, zero new
            # signatures asserted by the leg itself). Acceptance:
            # speedup_vs_full_retrain ≥ 10 at 2% touched entities;
            # touched_frac is a config fact the sentinel excludes.
            "refresh_e2e_speedup_vs_full_retrain":
                round(rf_stats["speedup_vs_full_retrain"], 2),
            "refresh_e2e_wall_ms": round(rf_stats["wall_s"] * 1e3, 1),
            "refresh_e2e_full_retrain_wall_ms":
                round(rf_stats["full_retrain_wall_s"] * 1e3, 1),
            "refresh_e2e_touched_frac":
                round(rf_stats["touched_frac"], 4),
            # freshness (round 19): rows-changed → servable seconds,
            # gauged by hot_swap at cutover ("staleness" gates it
            # LOWER-better — a slower flywheel serves staler models)
            "refresh_e2e_staleness_s":
                round(rf_stats["staleness_s"], 3),
            # serving regime (round 9): closed-loop online scoring over a
            # zipf entity mix through the micro-batching dispatcher; the
            # leg itself asserts the TraceSignatureLog retrace bound
            "serving_qps": round(serving_stats["qps"], 1),
            "serving_p50_ms": round(serving_stats["p50_ms"], 3),
            "serving_p95_ms": round(serving_stats["p95_ms"], 3),
            "serving_p99_ms": round(serving_stats["p99_ms"], 3),
            # quantized rung (round 15): the same closed-loop mix through
            # the int8 ladder (gated at warmup by the measured accuracy
            # bound); margin_maxdiff gates LOWER-better — a louder
            # quantization is a regression even at the same QPS
            "serving_quantized_qps": round(svq_stats["qps"], 1),
            "serving_quantized_p99_ms": round(svq_stats["p99_ms"], 3),
            "serving_quantized_margin_maxdiff":
                round(svq_ladder.quant_report["max_abs_diff"], 6),
            # open-loop SLO regime (overload round): fixed arrival rates
            # with the admission policy armed. sustained_qps/p99 gate as
            # usual; overload_shed_pct gates LOWER-better ("shed" in the
            # sentinel direction map — more shedding at the same offered
            # rate means the tier got slower); slo_target_ms is a config
            # bar the sentinel excludes; the bool verdict is excluded by
            # type. Zero lost futures is asserted by the leg itself.
            "serving_slo_sustained_qps": round(slo_stats["sustained_qps"],
                                               1),
            "serving_slo_p99_ms": round(slo_stats["p99_ms"], 3),
            "serving_slo_overload_p99_ms":
                round(slo_stats["overload_p99_ms"], 3),
            "serving_slo_overload_shed_pct": slo_stats["overload_shed_pct"],
            "serving_slo_target_ms": SLO_TARGET_P99_MS,
            "serving_slo_ok": bool(slo_stats["ok"]),
            # tail attribution (round 19): the sweep runs with request
            # tracing armed; the slowest exemplar's total gates via
            # "_ms" (the full hop breakdowns ride nested below)
            "serving_slo_exemplar_slowest_ms":
                round(slo_stats["exemplar_slowest_ms"], 3),
            # lane-batched tuner regime (round 16): 256 configs through
            # GP-proposed fixed-chunk lane rounds with successive halving
            # vs the point-at-a-time architecture (sampled + extrapolated).
            # Acceptance: speedup ≥ 8; the leg itself asserts the
            # two-signature no-retrace bound; n_configs is a config fact
            # the sentinel excludes.
            "tuning_e2e_configs_per_sec":
                round(tu_stats["configs_per_sec"], 2),
            "tuning_e2e_sequential_configs_per_sec":
                round(tu_stats["sequential_configs_per_sec"], 2),
            "tuning_e2e_speedup_vs_sequential":
                round(tu_stats["speedup_vs_sequential"], 2),
            "tuning_e2e_n_configs": tu_stats["n_configs"],
            # multi-process spine (round 17): the per-evaluation DCN
            # wire bill, priced off the traced psum program — gates
            # LOWER-better ("dcn_bytes"); a grown payload means
            # something besides the gradient started riding DCN.
            # n_processes is the verified topology, a config fact the
            # sentinel excludes; the 4-process launch wall (spawn +
            # cluster init + solve) gates via "_ms". Keys are omitted
            # entirely when the sandbox blocks the coordinator.
            **({
                "multihost_e2e_dcn_bytes_per_eval":
                    mh_stats["dcn_bytes_per_eval"],
                "multihost_e2e_launch_4p_wall_ms":
                    round(mh_stats["launch_wall_s"][4] * 1e3, 1),
                "multihost_e2e_n_processes":
                    mh_stats["n_processes_verified"],
            } if mh_stats.get("available") else {}),
        },
        # the spine's full report (bit-identity digest, per-count walls,
        # per-shard feature bytes that never ride DCN) — nested, so
        # invisible to the sentinel's leg_values
        "multihost_e2e": mh_stats,
        # the verdict line + full degradation curve + tail exemplars ride
        # beside the legs (strings/lists/nested dicts are invisible to
        # the sentinel's leg_values)
        "serving_slo": {"verdict": slo_stats["verdict"],
                        "curve": slo_stats["curve"],
                        "exemplars": slo_stats["exemplars"]},
    }
    # the health plane's snapshot of this bench run: verdict + watchdog
    # rules + counter rates, embedded in every JSON line (nested — the
    # sentinel gates legs, operators read health)
    from photon_tpu.telemetry import health as _health

    doc["health"] = _health.snapshot(run).to_json()
    # attribution-ledger digest: the top measured programs + compile
    # accounting ride the JSON line next to the wall-clock legs
    doc["ledger"] = {"compile": ledger_report["compile"],
                     "attribution": ledger_report["attribution"][:8]}
    from photon_tpu.profiling import sentinel

    doc["schema"] = sentinel.SCHEMA_VERSION
    # this round gates only against rounds measured on the same host
    # fingerprint — a swapped container CPU is a new series, not a
    # regression (sentinel.same_env; the r06 TPU→CPU policy, automated)
    doc["env"] = sentinel.host_env()
    history = sentinel.same_env(
        sentinel.load_history(os.path.dirname(os.path.abspath(__file__))),
        doc["env"])
    verdicts = sentinel.gate(sentinel.leg_values(doc), history)
    doc["gate"] = {leg: v.to_json() for leg, v in verdicts.items()}
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
