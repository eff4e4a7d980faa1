#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that photon-tpu still starts on the chip.

One process, one TPU v5e chip, the entry points a user would call, at the
widths the repo advertises, random data made from ``--seed``:

- ``glm``   — `train_glm` / `train_glm_grid` take five iterations on
  bench.py's 10M-feature sparse problem (and its dense sibling), checked
  against a plain float64 numpy objective computed on the host from the
  same COO.
- ``game``  — the flagship GAME data written to Avro, then
  `drivers.train.main` (fixed + per-user + per-item, both ingest modes) and
  `drivers.score.main`; the AUC is recomputed from the scorer's file.
- ``serve`` — the saved model behind `CoefficientStore` → `ProgramLadder`
  (f32 and int8) → `MicroBatchDispatcher`, every answer compared with the
  offline score.

``--chips 4`` runs ONLY the ``mesh`` phase (sharded blocked-ELL solve,
mesh GAME fit, streamed mesh solve — each against its one-device twin) in
one process that drives all four devices.

Every line but the last is one JSON object per phase. The last line is the
contract's ``{"ok": true, "device": {...}}``. A phase that fails raises: the
exit code is non-zero and no success line is printed. Without a TPU the
script fails at once; ``--rehearse`` is the only CPU mode — tiny sizes, the
same control flow, Pallas interpret mode never involved — and never prints
the success line. What this prints is smoke output, not a benchmark.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# bf16 unit roundoff is 2^-9; every X-pass operand (stored values, and the
# coefficients at the matmul) is rounded to bf16 at most once on each side
# of a comparison, the logistic loss is 1-Lipschitz in the margin, and the
# per-row sum of |x_j w_j| stays below the per-row loss after five
# iterations from zero — so two correct evaluations of one objective agree
# to one bf16 ulp, relative. The TPU's default matmul precision rounds f32
# operands the same way, so the dense f32 problem gets the same bound. A
# route that lost a bucket, a lane or a precision step misses it by far.
LOSS_RTOL = 2.0 ** -8
# n·log 2 at w = 0 involves no coefficient: f32 summation noise only
LOSS0_RTOL = 1e-5
N_CLIENTS = 4  # serve phase: client threads
# mesh GAME fit against one device, per coefficient (values are O(1)): a
# few bf16 ulps (2^-8 ~ 4e-3). At the TPU's default matmul precision f32
# operands may be rounded to bf16, and the sharded and unsharded programs
# need not round in the same places; two sweeps of 15-iteration solves
# carry that through. The first four-chip run measured 6.6e-3; the CPU
# backend, which multiplies in true f32, gives 8.5e-4 at the same size.
GAME_COEF_ATOL = 2e-2


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling, plus persistent
    cache hits/misses — read from jax's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.hits, self.misses


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, report: dict):
    """Time one phase; the body fills ``report``; a failure propagates."""
    t0 = time.perf_counter()
    c0, h0, m0 = clock.snapshot()
    yield
    c1, h1, m1 = clock.snapshot()
    emit({"phase": name, "ok": True,
          "wall_s": round(time.perf_counter() - t0, 2),
          "compile_s": round(c1 - c0, 2),
          "compile_cache": {"hits": h1 - h0, "misses": m1 - m0},
          **report})


def check(ok: bool, what: str, **numbers) -> None:
    if not ok:
        raise AssertionError(f"{what}: {numbers}")


# ------------------------------------------------------------------ sizes
def sizes(rehearse: bool) -> dict:
    import bench

    if rehearse:
        return {"glm_rows": 1 << 12, "dense_rows": 1 << 12,
                "users": 500, "items": 250, "train_rows": 20_000,
                "val_rows": 4_000, "requests": 512,
                "stream_chunk_rows": 1 << 10}
    return {"glm_rows": bench.S_ROWS, "dense_rows": bench.D_ROWS,
            "users": 100_000, "items": 50_000, "train_rows": 1_000_000,
            "val_rows": 100_000, "requests": 2048,
            "stream_chunk_rows": 1 << 16}


# -------------------------------------------------------- numpy reference
def np_logistic_objective(z, y, w, l2: float) -> float:
    """Σ log(1 + e^z) − y·z + ½·l2·‖w‖², float64 on the host."""
    import numpy as np

    z = np.asarray(z, np.float64)
    return float(np.sum(np.logaddexp(0.0, z) - np.asarray(y, np.float64) * z)
                 + 0.5 * l2 * np.dot(w, w))


def stored(values, dtype):
    """Host values as the device stores them (e.g. rounded to bf16), f64."""
    import numpy as np

    return np.asarray(np.asarray(values).astype(dtype), np.float64)


def check_lane(what: str, history, value, w, reference: float,
               n_log2: float) -> float:
    """One solved lane against the plain reference: loss at w = 0 equals
    n·log 2, the reported final loss equals the numpy loss at the final
    w, the loss fell at every iteration, w is finite. Returns the
    relative difference to the reference."""
    import numpy as np

    h = np.asarray(history, np.float64)
    h = h[~np.isnan(h)]
    rel = abs(float(value) - reference) / reference
    check(abs(h[0] - n_log2) <= LOSS0_RTOL * n_log2,
          f"{what}: loss at w=0 is not n*log2", got=float(h[0]), want=n_log2)
    check(rel <= LOSS_RTOL, f"{what}: reported loss != numpy loss at the "
          "final w", got=float(value), want=reference)
    check(bool(np.all(np.isfinite(w))) and h[-1] < h[0]
          and bool(np.all(np.diff(h) <= 0)),
          f"{what}: loss did not decrease", history=h.tolist())
    return rel


def layout_summary(X) -> dict:
    return {"n": int(X.shape[0]), "d": int(X.shape[1]),
            "d_sel": int(X.d_sel), "tail_U": int(X.n_prefix - X.d_sel),
            "ell_buckets": [list(map(int, v.shape)) for v in X.ell_vals],
            "occ_buckets": [list(map(int, v.shape)) for v in X.bucket_vals],
            "dense_dtype": str(X.dense.dtype)}


# ------------------------------------------------------------- phase: glm
def run_glm(seed: int, sz: dict, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from photon_tpu.data.dataset import make_batch
    from photon_tpu.models.training import train_glm, train_glm_grid
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    task = TaskType.LOGISTIC_REGRESSION
    report: dict = {}
    with phase("glm", clock, report):
        rows = sz["glm_rows"]
        t0 = time.perf_counter()
        ind, va, y = bench.sparse_coo(seed, rows)
        batch, stats = bench.sparse_batch(ind, va, y)
        jax.block_until_ready(batch)
        report["rows"] = rows
        report["rows_cut_from"] = bench.S_ROWS if rows != bench.S_ROWS \
            else None
        report["features"] = bench.S_FEATURES
        report["build_s"] = round(time.perf_counter() - t0, 2)
        report["layout"] = {**layout_summary(batch.X), **stats}
        va64 = stored(va, batch.X.dense.dtype)
        n_log2 = rows * float(np.log(2.0))

        def reference(w, lam):
            w = np.asarray(w, np.float64)
            return np_logistic_objective(
                np.einsum("nk,nk->n", va64, w[ind]), y, w, lam)

        single = OptimizerConfig(max_iters=5, tolerance=0.0, reg=l2(),
                                 reg_weight=1e-3, history=5)
        grid = OptimizerConfig(max_iters=5, tolerance=0.0, reg=l2(),
                               reg_weight=0.0, history=5,
                               lane_history_dtype="bfloat16")
        lams = [float(v) for v in bench.S_GRID]
        h0, m0, c0 = clock.hits, clock.misses, clock.seconds
        t0 = time.perf_counter()
        _, res = train_glm(batch, task, single)
        w1 = np.asarray(res.w)
        gres, _ = train_glm_grid(batch, task, grid, lams,
                                 device_results=True)
        W = np.asarray(gres.w)                      # (G, d)
        histG = np.asarray(gres.loss_history)       # (G, iters + 1)
        report["solve"] = {
            "wall_s": round(time.perf_counter() - t0, 2),
            "compile_s": round(clock.seconds - c0, 2),
            "cache_hits": clock.hits - h0,
            "cache_misses": clock.misses - m0}
        ref1 = reference(w1, single.reg_weight)
        check_lane("glm single", res.loss_history, res.value, w1, ref1,
                   n_log2)
        worst = max(
            check_lane(f"glm grid lane {g}", histG[g], gres.value[g],
                       W[g], reference(W[g], lam), n_log2)
            for g, lam in enumerate(lams))
        report["checks"] = {
            "n_log2": n_log2, "loss_rtol": LOSS_RTOL,
            "single": {
                "single_loss": float(res.value), "single_numpy": ref1,
                "single_iters": int(res.iterations),
                "grid_losses": [float(v) for v in gres.value],
                "grid_worst_rel_vs_numpy": worst}}
        del batch

        # the dense sibling, cheaply: bench.py's 524,288 x 256 f32 problem
        drows = sz["dense_rows"]
        X, yd = bench.dense_arrays(seed, drows)
        dbatch = jax.device_put(make_batch(X, yd))
        dlams = [float(v) for v in bench.D_GRID]
        dcfg = OptimizerConfig(max_iters=5, tolerance=0.0, reg=l2(),
                               reg_weight=0.0, history=5)
        t0 = time.perf_counter()
        dres, _ = train_glm_grid(dbatch, task, dcfg, dlams,
                                 device_results=True)
        Wd = np.asarray(dres.w, np.float64)
        X64 = X.astype(np.float64)
        worst = max(
            check_lane(f"glm dense lane {g}", dres.loss_history[g],
                       dres.value[g], Wd[g],
                       np_logistic_objective(X64 @ Wd[g], yd, Wd[g], lam),
                       drows * float(np.log(2.0)))
            for g, lam in enumerate(dlams))
        report["dense"] = {
            "rows": drows, "features": bench.D_FEATURES,
            "lanes": len(dlams), "route": "xla",
            "wall_s": round(time.perf_counter() - t0, 2),
            "worst_rel_vs_numpy": worst,
            "final_losses": [float(v) for v in dres.value]}


# ---------------------------------------------------------- phase: parity
def optimum_distance_bound(tol: float, f: float, lam: float) -> float:
    """How far apart two solves of one lam-strongly-convex objective may
    stop: a relative-decrease stop at ``tol`` leaves a gap of order
    tol·f, a gap g allows a distance sqrt(2g/lam) from the optimum, and
    there are two solves."""
    return 2.0 * (2.0 * tol * f / lam) ** 0.5


def run_parity(seed: int, clock: CompileClock) -> None:
    """Two roads to one convex optimum, at the size of the CPU tests that
    pin them (tests/test_blocked_ell.py::test_bell_train_glm_parity,
    tests/test_game_pipeline.py::TestStragglerResolve): the chip's verdict
    is what those tests' coefficient tolerances are restated against."""
    import jax.numpy as jnp
    import numpy as np

    from photon_tpu.data.dataset import make_batch
    from photon_tpu.data.matrix import SparseRows, matvec, to_blocked_ell
    from photon_tpu.game.dataset import GameData, RandomEffectDataset
    from photon_tpu.game.random_effect import RandomEffectCoordinate
    from photon_tpu.models.training import train_glm
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    report: dict = {}
    with phase("parity", clock, report):
        rng = np.random.default_rng(seed)
        # (a) one sparse problem, two layouts
        n, d, k = 400, 400, 8
        col = (rng.zipf(1.5, size=(n, k)).astype(np.int64) - 1) % (d - 1)
        val = rng.normal(size=(n, k))
        for i in range(n):  # a repeated (row, col) slot is padding: value 0
            _, first = np.unique(col[i], return_index=True)
            val[i, np.setdiff1d(np.arange(k), first)] = 0.0
        ind = np.concatenate([col, np.full((n, 1), d - 1)], axis=1)
        va = np.concatenate([val, np.ones((n, 1))], axis=1).astype(
            np.float32)
        X = SparseRows(jnp.asarray(ind.astype(np.int32)), jnp.asarray(va), d)
        B = to_blocked_ell(X, 24)
        z = np.asarray(matvec(X, jnp.asarray(
            rng.normal(size=d).astype(np.float32) * 0.5)))
        tol, lam = 1e-6, 0.1
        cfg = OptimizerConfig(max_iters=200, tolerance=tol, reg=l2(),
                              reg_weight=lam, history=5)
        layouts = {}
        for task, y in (
                (TaskType.LOGISTIC_REGRESSION,
                 (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)),
                (TaskType.LINEAR_REGRESSION,
                 np.abs(rng.normal(size=n)).astype(np.float32))):
            m_b, r_b = train_glm(make_batch(B, jnp.asarray(y)), task, cfg)
            m_s, r_s = train_glm(make_batch(X, jnp.asarray(y)), task, cfg)
            fb, fs = float(r_b.value), float(r_s.value)
            dw = float(np.max(np.abs(np.asarray(m_b.coefficients.means)
                                     - np.asarray(m_s.coefficients.means))))
            bound = optimum_distance_bound(tol, fs, lam)
            iters = [int(r_b.iterations), int(r_s.iterations)]
            check(bool(r_b.converged) and bool(r_s.converged)
                  and abs(fb - fs) <= LOSS_RTOL * fs and dw <= bound,
                  "parity: blocked-ELL and SparseRows solves stop apart",
                  task=task.name, value_rel=abs(fb - fs) / fs,
                  coef_max_abs_diff=dw, bound=bound, iterations=iters)
            layouts[task.name] = {"value_rel": abs(fb - fs) / fs,
                                  "coef_max_abs_diff": dw, "bound": bound,
                                  "iterations": iters}
        report["layouts"] = layouts

        # (b) one random-effect bucket: full depth, or a capped first pass
        # plus the compacted re-solve of the stragglers
        E, dr, rows = 9, 3, 24
        ent = np.repeat(np.arange(E), rows)
        Xr = rng.normal(size=(E * rows, dr)).astype(np.float32)
        bad = ent == 0
        Xr[bad] *= np.geomspace(1e-1, 1e1, dr).astype(np.float32)[None, :]
        logit = np.einsum("nd,nd->n", Xr, rng.normal(size=(E, dr))[ent])
        yr = (rng.random(E * rows) < 1 / (1 + np.exp(-logit))).astype(
            np.float32)
        yr[bad] = (logit[bad] > 0).astype(np.float32)
        ds = RandomEffectDataset.build(
            GameData.build(yr, {"s": Xr}, {"e": ent}), "e", "s")
        lam_r = 1e-2
        rcfg = OptimizerConfig(max_iters=80, tolerance=tol, reg=l2(),
                               reg_weight=lam_r, history=5)
        coefs = []
        for budget in (None, 4):
            coord = RandomEffectCoordinate(
                ds, TaskType.LOGISTIC_REGRESSION, rcfg, pipeline_depth=1,
                straggler_budget=budget)
            model, _ = coord.train(np.zeros(E * rows, np.float32))
            coefs.append(np.asarray(model.coefficients))
        dw = float(np.max(np.abs(coefs[0] - coefs[1])))
        # per entity the loss starts at rows·log 2 and only falls
        bound = optimum_distance_bound(tol, rows * float(np.log(2.0)), lam_r)
        check(dw <= bound, "parity: straggler re-solve stops apart from "
              "the full-depth solve", coef_max_abs_diff=dw, bound=bound)
        report["straggler_resolve"] = {"coef_max_abs_diff": dw,
                                       "bound": bound}


# ------------------------------------------------------------ phase: game
def flagship_files(out_dir: str, seed: int, sz: dict) -> tuple:
    sys.path.insert(0, os.path.join(REPO, "benches"))
    import _flagship_data as fd

    data_dir = os.path.join(out_dir, "data")
    os.makedirs(data_dir, exist_ok=True)
    train = os.path.join(data_dir, "train.avro")
    val = os.path.join(data_dir, "val.avro")
    truth = fd.planted_truth(sz["users"], sz["items"], seed=seed)
    fd.write_flagship_avro(train, sz["train_rows"], sz["users"],
                           sz["items"], truth, seed=seed + 1)
    fd.write_flagship_avro(val, sz["val_rows"], sz["users"], sz["items"],
                           truth, seed=seed + 2)
    return fd, train, val


def flagship_data_config(fd):
    from photon_tpu.data.feature_bags import FeatureShardConfig
    from photon_tpu.data.ingest import GameDataConfig

    return GameDataConfig(
        shards={k: FeatureShardConfig.coerce(v)
                for k, v in fd.FEATURE_SHARDS.items()},
        entity_fields=("userId", "itemId"))


def call_main(main, argv) -> dict:
    """Run a driver's `main(argv)` and parse the JSON line it prints."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        main(argv)
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def coefficient_array(coordinate_model):
    """A coordinate's coefficients as float64: the (E, d) block of a
    random effect, the (d,) means of a fixed effect."""
    import numpy as np

    cm = coordinate_model
    return np.asarray(cm.coefficients if hasattr(cm, "coefficients")
                      else cm.model.coefficients.means, np.float64)


def rank_auc(scores, labels) -> float:
    """Mann-Whitney AUC with average ranks for ties — plain scipy/numpy,
    independent of the repo's evaluators."""
    import numpy as np
    from scipy.stats import rankdata

    y = np.asarray(labels) > 0.5
    r = rankdata(np.asarray(scores, np.float64))
    n1, n0 = int(y.sum()), int((~y).sum())
    return float((r[y].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


def run_game(seed: int, sz: dict, out_dir: str, clock: CompileClock) -> dict:
    import numpy as np

    from photon_tpu import telemetry
    from photon_tpu.data.avro_io import read_avro
    from photon_tpu.data.ingest import read_game_data
    from photon_tpu.data.model_io import load_game_model
    from photon_tpu.drivers import score as score_driver
    from photon_tpu.drivers import train as train_driver

    report: dict = {}
    with phase("game", clock, report):
        t0 = time.perf_counter()
        fd, train, val = flagship_files(out_dir, seed, sz)
        report["data"] = {
            "users": sz["users"], "items": sz["items"],
            "train_rows": sz["train_rows"], "val_rows": sz["val_rows"],
            "cut": ("rows only: the flagship is 10,000,000 train / "
                    "1,000,000 validation rows; widths, users and items "
                    "are the flagship's"),
            "write_s": round(time.perf_counter() - t0, 2)}
        runs = {}
        for workers in (0, 2):
            tag = f"train_w{workers}"
            cfg = {
                "train_path": train, "validation_path": val,
                "output_dir": os.path.join(out_dir, tag),
                "feature_shards": fd.FEATURE_SHARDS,
                "coordinates": fd.COORDINATES,
                "entity_fields": ["userId", "itemId"],
                "n_sweeps": 2, "evaluators": ["AUC"],
                # the streaming reader is the one the ingest workers feed
                "streaming": True, "ingest_workers": workers}
            path = os.path.join(out_dir, f"{tag}.json")
            with open(path, "w") as f:
                json.dump(cfg, f)
            t0 = time.perf_counter()
            c0 = clock.seconds
            with telemetry.run(tag) as trun:
                out = call_main(train_driver.main, ["--config", path])
            runs[workers] = out
            pool = {k: int(trun.counters.get(f"ingest.{k}", 0))
                    for k in ("worker_chunks", "worker_deaths")}
            # one process per chip: this process holds the chip, so a
            # decode worker that initialised a backend would have died
            # (and its chunk silently decoded in-process)
            check(pool["worker_deaths"] == 0
                  and (pool["worker_chunks"] > 0) == (workers > 0),
                  "game: the ingest worker pool did not decode the chunks",
                  workers=workers, **pool)
            report[tag] = {
                "wall_s": round(time.perf_counter() - t0, 2),
                "compile_s": round(clock.seconds - c0, 2),
                "validation_auc": out["validation_score"], **pool}
        model_dir = runs[0]["model_dir"]
        model, index_maps = load_game_model(model_dir)
        other, _ = load_game_model(runs[2]["model_dir"])
        coef_diff = {}
        for name, cm in model.coordinates.items():
            a = coefficient_array(cm)
            b = coefficient_array(other.coordinates[name])
            check(a.shape == b.shape and np.all(np.isfinite(a)),
                  "game: coefficients not finite / shapes differ",
                  coordinate=name)
            coef_diff[name] = float(np.max(np.abs(a - b)))
        # same chunks in the same order -> the same program on the same
        # data: the two ingest modes must agree exactly
        check(all(v == 0.0 for v in coef_diff.values())
              and runs[0]["validation_score"] == runs[2]["validation_score"],
              "game: ingest_workers=0 and =2 trained different models",
              coef_max_abs_diff=coef_diff)

        score_cfg = {"model_dir": model_dir, "data_path": val,
                     "output_dir": os.path.join(out_dir, "scores"),
                     "feature_shards": fd.FEATURE_SHARDS,
                     "entity_fields": ["userId", "itemId"],
                     "evaluators": ["AUC"]}
        path = os.path.join(out_dir, "score.json")
        with open(path, "w") as f:
            json.dump(score_cfg, f)
        t0 = time.perf_counter()
        scored = call_main(score_driver.main, ["--config", path])
        recs = read_avro(scored["output_path"])
        scores = np.asarray([r["predictionScore"] for r in recs], np.float64)
        labels = np.asarray([r["label"] for r in recs], np.float64)
        check(scores.shape[0] == sz["val_rows"]
              and np.all(np.isfinite(scores)),
              "game: scorer output incomplete or not finite",
              n=int(scores.shape[0]))
        auc = rank_auc(scores, labels)
        train_auc = runs[0]["validation_score"]
        # both sides rank the same f32 margins; the drivers' evaluator
        # accumulates in f32 on the device, the reference in f64
        check(abs(auc - train_auc) <= 1e-5 and abs(auc - scored["metric"])
              <= 1e-5, "game: AUC from the scorer's file != driver's AUC",
              recomputed=auc, train_driver=train_auc,
              score_driver=scored["metric"])

        # the same model's fixed-effect-only margin on the same rows
        shard_maps = {cm.feature_shard: index_maps[name]
                      for name, cm in model.coordinates.items()}
        vdata, _ = read_game_data(val, flagship_data_config(fd),
                                  index_maps=shard_maps)
        w_fixed = np.asarray(
            model.coordinates["fixed"].model.coefficients.means, np.float64)
        fixed_auc = rank_auc(
            np.asarray(vdata.shards["fixed"], np.float64) @ w_fixed, vdata.y)
        check(np.array_equal(np.asarray(vdata.y, np.float64), labels),
              "game: validation rows re-read in a different order")
        # stated margin: the planted per-user/per-item effects carry most
        # of the signal, so a fit that learned them lifts AUC by >= 0.05
        # over the same model's fixed effect (0.12 at ten rows per user)
        check(auc - fixed_auc >= 0.05,
              "game: random effects did not beat the fixed-effect margin",
              auc=auc, fixed_only_auc=fixed_auc)
        report["score"] = {
            "wall_s": round(time.perf_counter() - t0, 2),
            "n_scored": scored["n_scored"], "auc_recomputed": auc,
            "auc_train_driver": train_auc, "auc_score_driver":
            scored["metric"], "auc_fixed_effect_only": fixed_auc,
            "auc_lift_required": 0.05}
        report["ingest_modes"] = {"coef_max_abs_diff": coef_diff}
    return {"model": model, "vdata": vdata, "offline_scores": scores}


# ----------------------------------------------------------- phase: serve
def run_serve(seed: int, sz: dict, game: dict, clock: CompileClock) -> None:
    import numpy as np

    from photon_tpu import serving
    from photon_tpu.data.matrix import quantize_blocks

    model, vdata, offline = (game["model"], game["vdata"],
                             game["offline_scores"])
    report: dict = {}
    with phase("serve", clock, report):
        store = serving.CoefficientStore.from_game_model(model)
        rng = np.random.default_rng(seed)
        rows = rng.choice(vdata.n, size=sz["requests"], replace=False)
        shard_names = sorted(vdata.shards)
        feats = {s: np.asarray(vdata.shards[s], np.float32)
                 for s in shard_names}
        uid, iid = vdata.entity_ids["userId"], vdata.entity_ids["itemId"]
        pool = [serving.ScoreRequest(
            features={s: feats[s][i] for s in shard_names},
            entities={"userId": uid[i], "itemId": iid[i]}) for i in rows]
        want = offline[rows]

        # per-request bound on what quantization may move: each int8
        # coefficient is off by at most half its row's step (scale / 2),
        # so |d margin| <= sum over coordinates of (scale / 2) * sum|x|,
        # and the logistic mean moves at most a quarter of that
        bound = np.zeros(len(rows))
        for name, blk in store.fixed.items():
            _, s = quantize_blocks(np.asarray(blk.weights), "int8")
            bound += 0.5 * float(s) * np.abs(
                feats[blk.feature_shard][rows]).sum(axis=1)
        for name, blk in store.random.items():
            _, s = quantize_blocks(np.asarray(blk.coefficients), "int8")
            ids, _ = store.lookup(name, [pool[j].entities[blk.entity_name]
                                         for j in range(len(rows))])
            bound += 0.5 * s[np.asarray(ids)] * np.abs(
                feats[blk.feature_shard][rows]).sum(axis=1)
        int8_tol = 0.25 * bound

        for label, quant in (("f32", None), ("int8", "int8")):
            ladder = serving.ProgramLadder(store, quantize=quant,
                                           model_tag=f"smoke-{label}")
            c0 = clock.seconds
            t0 = time.perf_counter()
            ladder.warmup()
            warm_s = time.perf_counter() - t0
            warm_compile_s = clock.seconds - c0
            traced = ladder._jit._cache_size()
            disp = serving.MicroBatchDispatcher(ladder)
            got = np.full(len(pool), np.nan)
            errors: list = []

            def client(k):
                # bursts of growing size, each collected before the next:
                # flushes land on every rung, not only the smallest
                try:
                    mine = list(range(k, len(pool), N_CLIENTS))
                    burst = 1
                    while mine:
                        now, mine = mine[:burst], mine[burst:]
                        futs = [(j, disp.submit(pool[j])) for j in now]
                        for j, fut in futs:
                            got[j] = fut.result(timeout=120)
                        burst = burst * 3 if burst < 81 else 1
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    errors.append(e)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(N_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            stuck = [t for t in threads if t.is_alive()]
            disp.close()
            if errors:
                raise errors[0]
            check(not stuck, "serve: client threads did not finish")
            drive_s = time.perf_counter() - t0
            check(np.all(np.isfinite(got)), "serve: unanswered requests",
                  ladder=label, missing=int(np.isnan(got).sum()))
            n_sigs = ladder.assert_no_retrace()
            check(ladder._jit._cache_size() == traced,
                  "serve: new trace signatures after warm-up", ladder=label,
                  before=traced, after=ladder._jit._cache_size())
            diff = np.abs(got - want)
            if quant is None:
                # the same f32 program family at another batch size:
                # per-row reductions are row-independent, so only the
                # backend's reduction order may differ (a few f32 ulps of
                # a value in [0, 1])
                tol = np.full(len(rows), 1e-6)
            else:
                tol = int8_tol + 1e-6
            check(bool(np.all(diff <= tol)),
                  "serve: answers differ from the offline score",
                  ladder=label, max_abs_diff=float(diff.max()),
                  worst_tol=float(tol[np.argmax(diff - tol)]))
            report[label] = {
                "rungs": list(ladder.ladder),
                "warmup_s": round(warm_s, 2),
                "warmup_compile_s": round(warm_compile_s, 2),
                "drive_s": round(drive_s, 2), "requests": len(pool),
                "rungs_used": n_sigs, "programs_traced": traced,
                "max_abs_diff_vs_offline": float(diff.max()),
                "tolerance_max": float(tol.max()),
                **({"quant_gate": ladder.quant_report} if quant else {})}
        report["clients"] = N_CLIENTS


# ------------------------------------------------------------ phase: mesh
class HbmWatch:
    """Max ``bytes_in_use`` per device over a block, above its baseline —
    shows that a solve's operands really lived on every device (code that
    has never seen more than one chip may put everything on the first)."""

    def __init__(self, devices):
        self.devices = list(devices)
        self.base = [self._read(d) for d in self.devices]
        self.peak = list(self.base)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    @staticmethod
    def _read(d) -> int:
        return int((d.memory_stats() or {}).get("bytes_in_use", 0))

    def _poll(self) -> None:
        while not self._stop.wait(0.02):
            for i, d in enumerate(self.devices):
                self.peak[i] = max(self.peak[i], self._read(d))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)

    def gained(self) -> list:
        return [p - b for p, b in zip(self.peak, self.base)]


def all_reduces_per_evaluation(batch, mesh) -> dict:
    """all-reduce ops in the HLO the attached devices compile for ONE
    sharded value-and-gradient of this batch (the design's law: one)."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.analysis import collective_counts, hlo_all_reduce_count
    from photon_tpu.models.training import (_contract_sharded_vg,
                                            make_objective)
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    d = int(batch.X.shape[1])
    obj = make_objective(TaskType.LOGISTIC_REGRESSION,
                         OptimizerConfig(reg=l2(), reg_weight=1e-3), d,
                         axis_name=mesh.axis_names[0],
                         intercept_index=batch.X.last_col_pos)
    vg = _contract_sharded_vg(batch, mesh)
    w = jnp.zeros((d,), jnp.float32)
    traced = dict(collective_counts(jax.make_jaxpr(vg)(obj, batch, w)))
    compiled = jax.jit(vg).lower(obj, batch, w).compile()
    return {"traced": traced,
            "compiled_all_reduce_ops": hlo_all_reduce_count(
                compiled.as_text())}


def run_mesh(seed: int, sz: dict, out_dir: str, rehearse: bool,
             clock: CompileClock) -> None:
    """Three sub-phases, each its own line (a late failure keeps the
    earlier results): every mesh path against its one-device twin."""
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    devices = list(mesh.devices.reshape(-1))
    check(len(devices) == 4, "mesh: need exactly four devices",
          have=len(devices))
    # off-TPU (rehearsal) devices report no memory stats
    reports_hbm = (devices[0].memory_stats() or {}).get(
        "bytes_in_use") is not None
    check(reports_hbm or rehearse, "mesh: devices report no memory stats")
    ctx = MeshCtx(mesh, devices, reports_hbm)
    mesh_glm(seed, sz, ctx, clock)
    mesh_game(seed, sz, out_dir, ctx, clock)
    mesh_streamed(seed, sz, ctx, clock)


class MeshCtx:
    def __init__(self, mesh, devices, reports_hbm: bool):
        self.mesh, self.devices, self.reports_hbm = mesh, devices, reports_hbm
        self.n_dev = len(devices)

    def spans_all(self, x, what: str) -> None:
        check(len(x.sharding.device_set) == self.n_dev,
              f"mesh: {what} does not span the mesh",
              devices=len(x.sharding.device_set))

    def lived_on_all(self, watch: HbmWatch, floor: int, what: str) -> list:
        """Every device gained at least ``floor`` bytes during the block:
        a quarter of its share of the sub-phase's largest operand."""
        gained = watch.gained()
        check(not self.reports_hbm or min(gained) >= floor,
              f"mesh: {what} did not occupy every device",
              gained_bytes=gained, floor_bytes=floor)
        return gained


def mesh_glm(seed: int, sz: dict, ctx: MeshCtx, clock: CompileClock) -> None:
    """(i) the glm problem, row-sharded blocked-ELL, against one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from photon_tpu.data.dataset import (cast_features, make_batch,
                                         shard_blocked_ell_batch)
    from photon_tpu.data.matrix import SparseRows
    from photon_tpu.models.training import _sharded_prep, train_glm
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    task = TaskType.LOGISTIC_REGRESSION
    report: dict = {"devices": ctx.n_dev}
    with phase("mesh_glm", clock, report):
        rows = sz["glm_rows"]
        ind, va, y = bench.sparse_coo(seed, rows)
        cfg = OptimizerConfig(max_iters=5, tolerance=0.0, reg=l2(),
                              reg_weight=1e-3, history=5)
        one, _ = bench.sparse_batch(ind, va, y)
        _, ref = train_glm(one, task, cfg)
        ref_w, ref_f = np.asarray(ref.w), float(ref.value)
        del one
        sharded = cast_features(shard_blocked_ell_batch(
            make_batch(SparseRows(ind, va, bench.S_FEATURES), y), ctx.n_dev,
            d_dense=bench.S_DENSE, device_dense_dtype=jnp.bfloat16,
            mesh=ctx.mesh))
        placed, _, _ = _sharded_prep(
            sharded, jnp.zeros((bench.S_FEATURES,), jnp.float32), ctx.mesh)
        jax.block_until_ready(placed)
        ctx.spans_all(placed.X.dense, "the hot block")
        for leaf in placed.X.ell_vals + placed.X.bucket_vals:
            ctx.spans_all(leaf, "a tail bucket")
        in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                  for d in ctx.devices]
        share = int(placed.X.dense.nbytes) // ctx.n_dev
        check(not ctx.reports_hbm or min(in_use) >= share,
              "mesh: the sharded batch is not resident on every device",
              bytes_in_use=in_use, hot_block_share=share)
        hlo = all_reduces_per_evaluation(placed, ctx.mesh)
        check(hlo["compiled_all_reduce_ops"] == 1,
              "mesh: value-and-gradient is not ONE all-reduce", **hlo)
        _, res = train_glm(placed, task, cfg, mesh=ctx.mesh)
        f = float(res.value)
        dw = float(np.max(np.abs(np.asarray(res.w) - ref_w)))
        # same objective, another reduction order (four partial sums +
        # all-reduce) over bf16-rounded operands: one bf16 ulp, relative
        check(abs(f - ref_f) <= LOSS_RTOL * ref_f and np.isfinite(dw),
              "mesh (i): sharded solve != one-device solve",
              sharded=f, one_device=ref_f, w_max_abs_diff=dw)
        report.update({
            "rows": rows, "features": bench.S_FEATURES,
            "loss_sharded": f, "loss_one_device": ref_f,
            "loss_rel": abs(f - ref_f) / ref_f, "w_max_abs_diff": dw,
            "hot_block_devices": len(placed.X.dense.sharding.device_set),
            "bytes_in_use_per_device": in_use,
            "all_reduces_per_evaluation": hlo})


def mesh_game(seed: int, sz: dict, out_dir: str, ctx: MeshCtx,
              clock: CompileClock) -> None:
    """(ii) GameEstimator on the game data — fixed effect row-sharded,
    random-effect buckets entity-sharded — against one device."""
    import numpy as np

    from photon_tpu.data.ingest import read_game_data
    from photon_tpu.drivers.train import CoordinateSpec
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.game.scoring import score_game
    from photon_tpu.ops.losses import TaskType

    report: dict = {"devices": ctx.n_dev}
    with phase("mesh_game", clock, report):
        fd, train, val = flagship_files(out_dir, seed, sz)
        dcfg = flagship_data_config(fd)
        data, imaps = read_game_data(train, dcfg)
        vdata, _ = read_game_data(val, dcfg, index_maps=imaps)
        coords = {name: CoordinateSpec(**spec).coordinate_config()
                  for name, spec in fd.COORDINATES.items()}
        fits = {}
        for label, m in (("one_device", None), ("mesh", ctx.mesh)):
            est = GameEstimator(TaskType.LOGISTIC_REGRESSION, coords,
                                n_sweeps=2, mesh=m)
            with HbmWatch(ctx.devices) as watch:
                fit = est.fit(data, validation=vdata)[0]
                margins = np.asarray(score_game(fit.model, vdata),
                                     np.float64)
            fits[label] = (fit, margins, watch)
        fixed_bytes = int(np.asarray(data.shards["fixed"]).nbytes)
        gained = ctx.lived_on_all(fits["mesh"][2],
                                  fixed_bytes // ctx.n_dev // 4,
                                  "the mesh GAME fit")
        m1, m4 = fits["one_device"][1], fits["mesh"][1]
        auc1, auc4 = rank_auc(m1, vdata.y), rank_auc(m4, vdata.y)
        cdiff = {}
        for name, cm in fits["mesh"][0].model.coordinates.items():
            a = coefficient_array(cm)
            b = coefficient_array(
                fits["one_device"][0].model.coordinates[name])
            cdiff[name] = {"max_abs": float(np.max(np.abs(a - b))),
                           "rms": float(np.sqrt(np.mean((a - b) ** 2))),
                           "coef_rms": float(np.sqrt(np.mean(b ** 2)))}
        margin_diff = float(np.max(np.abs(m1 - m4)))
        check(abs(auc1 - auc4) <= 1e-3
              and all(v["max_abs"] <= GAME_COEF_ATOL for v in cdiff.values())
              and all(v["rms"] <= 1e-2 * v["coef_rms"]
                      for v in cdiff.values()),
              "mesh (ii): mesh GAME fit != one-device fit",
              auc_one=auc1, auc_mesh=auc4, coef_diff=cdiff,
              margin_max_abs_diff=margin_diff)
        report.update({
            "train_rows": data.n, "val_rows": vdata.n,
            "auc_one_device": auc1, "auc_mesh": auc4,
            "coef_diff": cdiff, "coef_atol": GAME_COEF_ATOL,
            "val_margin_max_abs_diff": margin_diff,
            "hbm_gained_per_device": gained})


def mesh_streamed(seed: int, sz: dict, ctx: MeshCtx,
                  clock: CompileClock) -> None:
    """(iii) the streamed mesh solve against the resident one."""
    import jax
    import numpy as np

    import bench
    from photon_tpu.data.dataset import chunk_batch, make_batch
    from photon_tpu.models.training import train_glm
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    task = TaskType.LOGISTIC_REGRESSION
    report: dict = {"devices": ctx.n_dev}
    with phase("mesh_streamed", clock, report):
        drows = sz["dense_rows"]
        X, yd = bench.dense_arrays(seed, drows)
        scfg = OptimizerConfig(max_iters=5, tolerance=0.0, reg=l2(),
                               reg_weight=1e-3, history=5)
        _, resident = train_glm(jax.device_put(make_batch(X, yd)), task,
                                scfg)
        chunked = chunk_batch(make_batch(X, yd), sz["stream_chunk_rows"])
        with HbmWatch(ctx.devices) as watch:
            _, streamed = train_glm(chunked, task, scfg, mesh=ctx.mesh)
        chunk_bytes = sz["stream_chunk_rows"] * bench.D_FEATURES * 4
        gained = ctx.lived_on_all(watch, chunk_bytes // ctx.n_dev // 4,
                                  "the streamed mesh solve")
        fr, fs = float(resident.value), float(streamed.value)
        dw = float(np.max(np.abs(np.asarray(streamed.w)
                                 - np.asarray(resident.w))))
        check(abs(fs - fr) <= LOSS_RTOL * fr and np.isfinite(dw),
              "mesh (iii): streamed mesh solve != resident solve",
              streamed=fs, resident=fr, w_max_abs_diff=dw)
        report.update({
            "rows": drows, "features": bench.D_FEATURES,
            "chunk_rows": sz["stream_chunk_rows"],
            "loss_streamed": fs, "loss_resident": fr,
            "loss_rel": abs(fs - fr) / fr, "w_max_abs_diff": dw,
            "iterations": int(streamed.iterations),
            "hbm_gained_per_device": gained})


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 runs ONLY the mesh phase, one process driving "
                        "four devices")
    p.add_argument("--out-dir", default=os.path.join(REPO, "chip_smoke_out"),
                   help="the only directory this script writes to")
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal: tiny sizes, same control flow, "
                        "never prints the success line")
    args = p.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.chips}").strip()
    import jax

    from photon_tpu import native
    from photon_tpu.utils import compile_cache

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    cache_dir = compile_cache.enable_compilation_cache()
    emit({"jax": jax.__version__, "backend": jax.default_backend(),
          **device, "rehearsal": bool(args.rehearse),
          "compile_cache_dir": cache_dir,
          "compile_cache_dir_from_env": bool(
              os.environ.get(compile_cache.ENV_VAR)),
          "seed": args.seed})
    if not args.rehearse:
        if dev.platform != "tpu":
            raise SystemExit(
                f"chip_smoke: no TPU visible (jax found {dev.platform!r} "
                f"devices); this script has no CPU mode except --rehearse")
        from photon_tpu.profiling.ledger import device_peaks

        device_peaks(dev)  # an unknown device kind is an error, here too
    if device["count"] != args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but jax sees "
                         f"{device['count']} device(s)")

    os.makedirs(args.out_dir, exist_ok=True)
    # built from what git would commit: no .so this run did not build
    native.rebuild()
    emit({"native_available": native.available()})
    if not native.available():
        raise SystemExit("chip_smoke: the native library did not build")

    clock = CompileClock()
    sz = sizes(args.rehearse)
    if args.chips == 4:
        run_mesh(args.seed, sz, args.out_dir, args.rehearse, clock)
    else:
        run_glm(args.seed, sz, clock)
        run_parity(args.seed, clock)
        game = run_game(args.seed, sz, args.out_dir, clock)
        run_serve(args.seed, sz, game, clock)
    if args.rehearse:
        emit({"rehearsal": True, "device": device})
        return 0
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
