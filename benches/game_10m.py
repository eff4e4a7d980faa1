"""GAME at BASELINE-config-4 shape: per-user + per-item random effects at
≥10M rows on one chip (VERDICT r3 item 4 — the 100M-row ads-CTR config,
scaled to what one v5e's HBM holds comfortably).

bf16 storage for the (wide) fixed shard — half the host→device bytes and
HBM, f32 accumulation in the matvec — and f32 for the narrow per-entity
shards. Measures host bucketing, data placement, cold fit (compile +
sweeps), warm refit, scoring, and AUC vs the fixed effect alone.

Run: python benches/game_10m.py [--rows 10000000]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import numpy as np

def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=10_000_000)
    p.add_argument("--users", type=int, default=100_000)
    p.add_argument("--items", type=int, default=50_000)
    p.add_argument("--d-fixed", type=int, default=32)
    p.add_argument("--d-re", type=int, default=4)
    p.add_argument("--sweeps", type=int, default=2)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from photon_tpu.evaluation.metrics import auc
    from photon_tpu.game.dataset import GameData
    from photon_tpu.game.estimator import (
        FixedEffectConfig,
        GameEstimator,
        RandomEffectConfig,
    )
    from photon_tpu.game.scoring import score_game
    from photon_tpu.data.dataset import make_batch
    from photon_tpu.models.training import train_glm
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    n, U, I = args.rows, args.users, args.items
    df, dr = args.d_fixed, args.d_re
    rng = np.random.default_rng(0)

    t0 = time.perf_counter()
    w_true = (rng.normal(size=df) * 0.3).astype(np.float32)
    u_true = rng.normal(size=(U, dr)).astype(np.float32)
    i_true = rng.normal(size=(I, dr)).astype(np.float32)
    Xf = rng.normal(size=(n, df)).astype(np.float32)
    Xu = rng.normal(size=(n, dr)).astype(np.float32)
    Xi = rng.normal(size=(n, dr)).astype(np.float32)
    uid = rng.integers(0, U, size=n)
    iid = rng.integers(0, I, size=n)
    margin = (Xf @ w_true + np.einsum("nd,nd->n", Xu, u_true[uid])
              + np.einsum("nd,nd->n", Xi, i_true[iid]))
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    print(f"host data gen: {time.perf_counter() - t0:.1f}s "
          f"({n} rows, {U} users + {I} items, d_fixed={df} bf16, "
          f"d_re={dr} f32)")

    # bf16 on HOST first (half the transfer bytes), then ONE device_put; the
    # per-entity shards stay host numpy — entity bucketing gathers them on
    # host anyway (stream_to_device's feature_dtype does the same cast for
    # the Avro-file road; synthetic data skips the ingest pass).
    t0 = time.perf_counter()
    Xf_dev = jax.device_put(Xf.astype(jnp.bfloat16))
    jax.block_until_ready(Xf_dev)
    print(f"fixed shard -> device (bf16, "
          f"{Xf_dev.nbytes / 1e9:.2f} GB): {time.perf_counter() - t0:.1f}s")
    del Xf

    data = GameData.build(
        y, shards={"fixed": Xf_dev, "u_re": Xu, "i_re": Xi},
        entity_ids={"user": uid, "item": iid})

    cfg_f = OptimizerConfig(max_iters=30, reg=l2(), reg_weight=1.0)
    cfg_r = OptimizerConfig(max_iters=15, reg=l2(), reg_weight=5.0)
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "fixed": FixedEffectConfig("fixed", cfg_f),
            "per_user": RandomEffectConfig("user", "u_re", cfg_r),
            "per_item": RandomEffectConfig("item", "i_re", cfg_r),
        },
        n_sweeps=args.sweeps)

    t0 = time.perf_counter()
    out = est.fit(data)[0]
    jax.block_until_ready(out.model.coordinates["fixed"].model.weights)
    cold = time.perf_counter() - t0
    print(f"cold fit ({args.sweeps} sweeps, 3 coordinates, incl. XLA "
          f"compile + entity bucketing + RE transfers): {cold:.1f}s")

    t0 = time.perf_counter()
    out = est.fit(data)[0]
    jax.block_until_ready(out.model.coordinates["fixed"].model.weights)
    warm = time.perf_counter() - t0
    print(f"warm refit ({args.sweeps} sweeps): {warm:.1f}s "
          f"({n * args.sweeps / warm:.2e} row-sweeps/sec)")

    t0 = time.perf_counter()
    margin_hat = score_game(out.model, data)
    mh = np.asarray(margin_hat)
    t_score = time.perf_counter() - t0
    game_auc = float(auc(mh, y))

    fixed_only, _ = train_glm(
        make_batch(Xf_dev, y), TaskType.LOGISTIC_REGRESSION, cfg_f)
    f_auc = float(auc(np.asarray(fixed_only.score(Xf_dev)), y))
    print(f"scoring {n} rows: {t_score:.1f}s")
    print(f"AUC: GAME {game_auc:.3f} vs fixed-only {f_auc:.3f}")


if __name__ == "__main__":
    main()
