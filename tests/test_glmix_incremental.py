"""GLMix's incremental refresh (PR 38): yesterday's model and its FULL
variances as every coordinate's Gaussian prior, on the one-dispatch
update — against the float64 reference the benchmark's
`glmix-incremental.refresh` cell decides `correct` with
(`benchmark/gen/glmix_incremental_reference.py`), at small sizes on the
CPU.
"""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.gen import glmix_incremental_reference as iref
from benchmark.gen import glmix_reference as ref
from photon_tpu import telemetry
from photon_tpu.data.matrix import SparseRows
from photon_tpu.game import dataset as game_dataset
from photon_tpu.game import random_effect
from photon_tpu.game.coordinate_descent import coordinate_descent
from photon_tpu.game.dataset import GameData, RandomEffectDataset, plan_buckets
from photon_tpu.game.model import RandomEffectModel
from photon_tpu.game.projector import (ProjectionConfig, ProjectorType,
                                       gather_rows)
from photon_tpu.game.random_effect import (RandomEffectCoordinate,
                                           align_entity_priors,
                                           bucket_priors, initial_table)
from photon_tpu.models.variance import (VarianceComputationType,
                                        compute_variances, inverse_diagonal)
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim import regularization as reg
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.prior import PriorDistribution

pytestmark = pytest.mark.release_programs

TASK = TaskType.LOGISTIC_REGRESSION
INDEX_MAP = ProjectionConfig(ProjectorType.INDEX_MAP)
FULL = VarianceComputationType.FULL
L2 = 2.0
FEATURES, NNZ, CAP = 30, 4, 12


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """This module's programs are compiled, not read back from the
    suite's persistent cache: reading an XLA:CPU executable back into a
    worker that already holds many segfaulted here under xdist (the
    planted faults trace programs another test of the module wrote)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _problem(seed=0, n_entities=30, n=800):
    """Sparse rows with a zipf entity skew (the cap bites, buckets differ),
    the intercept last; labels from planted per-entity effects."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, n_entities + 1, dtype=np.float64) ** -1.0
    ent = rng.choice(n_entities, size=n, p=p / p.sum()).astype(np.int32)
    ind = rng.integers(0, FEATURES, size=(n, NNZ)).astype(np.int32)
    val = rng.normal(size=(n, NNZ)).astype(np.float32)
    ind = np.concatenate([ind, np.full((n, 1), FEATURES, np.int32)], axis=1)
    val = np.concatenate([val, np.ones((n, 1), np.float32)], axis=1)
    truth = rng.normal(size=(n_entities, FEATURES + 1))
    dense = np.zeros((n, FEATURES + 1))
    np.add.at(dense, (np.arange(n)[:, None], ind), val)
    margin = np.einsum("nd,nd->n", dense, truth[ent]) * 0.5
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    offsets = (rng.normal(size=n) * 0.3).astype(np.float32)
    return {"ent": ent, "ind": ind, "val": val, "y": y, "offsets": offsets}


def _data(prob) -> GameData:
    return GameData.build(
        prob["y"], {"s": SparseRows(prob["ind"], prob["val"], FEATURES + 1)},
        {"e": prob["ent"]}, offsets=prob["offsets"])


def _prior(seed, keys, drop=(3, 7, 11)):
    """A previous run's model over ``keys`` less ``drop`` (entities new
    since then): means ~N(0, 0.5), variances in [0.05, 0.5), and 0 (never
    estimated) on a few columns."""
    rng = np.random.default_rng(seed)
    keys = np.setdiff1d(keys, drop)
    d = FEATURES + 1
    var = rng.uniform(0.05, 0.5, size=(keys.size, d)).astype(np.float32)
    var[rng.uniform(size=var.shape) < 0.1] = 0.0
    return RandomEffectModel(
        entity_name="e", feature_shard="s", task=TASK,
        coefficients=jnp.asarray(
            (rng.normal(size=(keys.size, d)) * 0.5).astype(np.float32)),
        entity_keys=keys,
        key_to_index={k: i for i, k in enumerate(keys.tolist())},
        variances=jnp.asarray(var))


def _dataset(data):
    return RandomEffectDataset.build(data, "e", "s", active_cap=CAP,
                                     projection=INDEX_MAP)


def _entity(prob, ds, e):
    """(columns, float64 rows over them, y, offsets) of dense entity e."""
    for block in ds.blocks:
        at = np.nonzero(np.asarray(block.entity_index) == e)[0]
        if at.size:
            real = np.asarray(block.weights)[at[0]] != 0.0
            r = np.asarray(block.row_index)[at[0]][real]
            cols, X = ref.entity_problem(prob["ind"][r], prob["val"][r])
            return (cols, X, prob["y"][r].astype(np.float64),
                    prob["offsets"][r].astype(np.float64))
    raise AssertionError(f"entity {e} is in no block")


def _prior_of(prior, key, cols):
    at = np.searchsorted(prior.entity_keys, key)
    if at >= prior.entity_keys.size or prior.entity_keys[at] != key:
        return np.zeros(len(cols)), np.zeros(len(cols)), False
    var = np.asarray(prior.variances, np.float64)[at, cols]
    return (np.asarray(prior.coefficients, np.float64)[at, cols],
            iref.prior_precision(var), True)


# ----------------------------------------------- (i) the fused prior update
def test_fused_prior_update_is_at_the_prior_optimum():
    """An incremental descent takes the one-dispatch update (no host block
    loop, every update counted with its prior) and lands every entity —
    seen: on the float64 optimum of its prior objective; new since the
    prior: on its plain L2 optimum — with variances float64 diag(H⁻¹)
    there."""
    prob = _problem()
    data = _data(prob)
    ds = _dataset(data)
    prior = _prior(1, ds.entity_keys)
    coord = RandomEffectCoordinate(ds, TASK, OptimizerConfig(
        max_iters=200, tolerance=1e-9, reg=reg.l2(), reg_weight=L2),
        variance=FULL)
    with telemetry.run("prior") as run:
        out = coordinate_descent({"re": coord}, data.y, data.weights,
                                 data.offsets, TASK, n_sweeps=2,
                                 initial_models={"re": prior},
                                 incremental=frozenset({"re"}))
        counters = run.report_compact()["counters"]
    assert counters["game_re.fused_prior_updates"] == 2
    assert counters["game_re.warm_adopted"] == 1
    assert counters["game_re.warm_carried"] == 1
    assert counters["game_re.variance_lanes"] == 2 * ds.n_entities
    assert counters["game_re.prior_unseen"] == 3
    assert counters["game_re.prior_seen"] == ds.n_entities - 3
    assert "game_re.readback_wait_ns" not in counters  # no host block loop
    model = out.model["re"]
    table = np.asarray(model.coefficients, np.float64)
    var_table = np.asarray(model.variances, np.float64)
    unseen = 0
    for e in range(ds.n_entities):
        cols, X, y, offs = _entity(prob, ds, e)
        mu, tau, seen = _prior_of(prior, ds.entity_keys[e], cols)
        unseen += not seen
        w_star, best = iref.newton(X, y, offs, L2, mu, tau)
        np.testing.assert_allclose(table[e, cols], w_star, atol=2e-3)
        at_fit = iref.objective(X, y, offs, table[e, cols], L2, mu, tau)
        assert -1e-9 <= (at_fit - best) / best <= 1e-5
        want = iref.full_variances(iref.hessian(X, y, offs, table[e, cols],
                                                L2, tau))
        np.testing.assert_allclose(var_table[e, cols], want, rtol=1e-4)
        # zero outside the columns the entity's rows touch
        outside = np.setdiff1d(np.arange(FEATURES + 1), cols)
        assert not np.any(var_table[e, outside])
        assert not np.any(table[e, outside])
    assert unseen == 3


@pytest.mark.parametrize("variance", [VarianceComputationType.NONE, FULL],
                         ids=["no_variances", "full"])
def test_fused_prior_update_matches_the_host_block_loop(variance):
    """The one-dispatch update with its on-device bucket priors and the
    host block loop with `align_entity_priors` solve the same problems:
    coefficients to a few f32 ulps carried through 25 iterations,
    variances alike, iteration counts exactly."""
    prob = _problem(seed=2)
    data = _data(prob)
    ds = _dataset(data)
    prior = _prior(3, ds.entity_keys)
    coord = RandomEffectCoordinate(ds, TASK, OptimizerConfig(
        max_iters=25, tolerance=0.0, reg=reg.l2(), reg_weight=L2),
        variance=variance)
    out = coordinate_descent({"re": coord}, data.y, data.weights,
                             data.offsets, TASK, n_sweeps=1,
                             initial_models={"re": prior},
                             incremental=frozenset({"re"}))
    warm = RandomEffectModel(
        "e", "s", TASK, initial_table(prior, ds.entity_keys),
        ds.entity_keys, ds.key_to_index)
    model, stats = coord.train(jnp.asarray(data.offsets), warm_start=warm,
                               prior=prior)
    np.testing.assert_allclose(np.asarray(out.model["re"].coefficients),
                               np.asarray(model.coefficients),
                               rtol=1e-4, atol=1e-4)
    assert out.coordinate_stats["re"][0].total_iterations \
        == stats.total_iterations
    if variance is FULL:
        np.testing.assert_allclose(np.asarray(out.model["re"].variances),
                                   np.asarray(model.variances), rtol=1e-4,
                                   atol=1e-7)
    else:
        assert out.model["re"].variances is None


# --------------------------------------- (ii) FULL variances by factorization
def test_inverse_diagonal_is_the_inverse_diagonal():
    rng = np.random.default_rng(4)
    A = rng.normal(size=(40, 17))
    H = A.T @ A + np.diag(rng.uniform(0.5, 3.0, 17))
    got = np.asarray(inverse_diagonal(jnp.asarray(H, jnp.float32)))
    np.testing.assert_allclose(got, np.diag(np.linalg.inv(H)), rtol=1e-5)
    # batched, as the vmapped lanes call it
    Hs = np.stack([H, H + np.eye(17)])
    got = np.asarray(jax.vmap(inverse_diagonal)(jnp.asarray(Hs, jnp.float32)))
    np.testing.assert_allclose(got[1], np.diag(np.linalg.inv(Hs[1])),
                               rtol=1e-5)


def test_full_variances_carry_the_prior_precision():
    """compute_variances FULL = diag((XᵀDX + diag(l2 + τ))⁻¹): the L2
    weight and the prior's precision are in H; no jitter."""
    from photon_tpu.data.dataset import make_batch
    from photon_tpu.ops.objective import Objective

    rng = np.random.default_rng(5)
    X = rng.normal(size=(60, 9)).astype(np.float32)
    y = (rng.uniform(size=60) < 0.5).astype(np.float32)
    tau = rng.uniform(0.0, 4.0, size=9).astype(np.float32)
    w = (rng.normal(size=9) * 0.3).astype(np.float32)
    obj = Objective(task=TASK, l2=0.7, prior_mean=jnp.zeros(9),
                    prior_precision=jnp.asarray(tau))
    got = np.asarray(compute_variances(obj, jnp.asarray(w),
                                       make_batch(X, y), FULL))
    want = iref.full_variances(iref.hessian(
        X.astype(np.float64), y.astype(np.float64), np.zeros(60),
        w.astype(np.float64), 0.7, tau))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_chunked_variance_lanes_agree_with_one_chunk(monkeypatch):
    """A bucket whose variances are computed a few lanes at a time (the
    plan's `variance_lanes` under a small device share) returns what one
    chunk returns, and both are float64 diag(H⁻¹)."""
    prob = _problem(seed=6, n_entities=40, n=1200)
    data = _data(prob)
    cfg = OptimizerConfig(max_iters=15, tolerance=0.0, reg=reg.l2(),
                          reg_weight=L2)

    def fit():
        ds = _dataset(data)
        coord = RandomEffectCoordinate(ds, TASK, cfg, variance=FULL)
        out = coordinate_descent({"re": coord}, data.y, data.weights,
                                 data.offsets, TASK, n_sweeps=1)
        return ds, out.model["re"]

    ds_one, one = fit()
    assert all(b.variance_lanes == b.n_entities for b in ds_one.blocks)
    # a share that leaves room for three lanes of the widest bucket
    widest = max(b.dim for b in ds_one.blocks)
    share = (16 << 30) // (3 * game_dataset._VARIANCE_MATRICES
                           * widest ** 2 * 4)
    monkeypatch.setattr(game_dataset, "_VARIANCE_SHARE", share)
    ds_few, few = fit()
    assert any(b.variance_lanes < b.n_entities for b in ds_few.blocks)
    assert all(b.variance_lanes <= 4 for b in ds_few.blocks
               if b.dim == widest)
    np.testing.assert_allclose(np.asarray(few.variances),
                               np.asarray(one.variances), rtol=1e-6,
                               atol=1e-9)
    table = np.asarray(few.coefficients, np.float64)
    for e in range(0, ds_few.n_entities, 3):
        cols, X, y, offs = _entity(prob, ds_few, e)
        want = iref.full_variances(iref.hessian(X, y, offs, table[e, cols],
                                                L2, 0.0))
        np.testing.assert_allclose(np.asarray(few.variances)[e, cols], want,
                                   rtol=1e-4)


def test_bucket_plan_sizes_the_variance_workspace():
    """The plan's variance chunks are powers of two, at most a bucket's
    entities, and their width² workspace holds at most the device's
    share; a sparse unprojected plan sizes them at the solve width."""
    rng = np.random.default_rng(7)
    rows = np.minimum(rng.zipf(1.6, size=5000), 128)
    widths = np.minimum(8 + 6 * rows + rng.integers(0, 40, 5000), 1200)
    dev = 16 << 30
    plan = plan_buckets(rows, widths, 4, width_classes=True, device_bytes=dev)
    share = dev // game_dataset._VARIANCE_SHARE
    for (m, w, g), lanes in zip(plan.buckets, plan.variance_lanes):
        assert 1 <= lanes <= len(g)
        assert lanes == len(g) or lanes & (lanes - 1) == 0
        assert lanes * game_dataset._VARIANCE_MATRICES * w * w * 4 <= share \
            or lanes == 1
    sparse = plan_buckets(rows, np.full(5000, 5), 8, device_bytes=dev,
                          solve_width=3000)
    assert sparse.variance_lanes == tuple(
        min(game_dataset.variance_lanes(3000, dev), len(g))
        for _, _, g in sparse.buckets)
    assert max(sparse.variance_lanes) \
        * game_dataset._VARIANCE_MATRICES * 3000 ** 2 * 4 <= share


# ------------------------------------------- (iii) day 0's model as day 1's prior
def test_day0_variances_become_day1_bucket_priors():
    """Day 0 fitted with FULL variances; its model, less a few entities,
    is day 1's prior. `bucket_priors` (on the device, in each bucket's
    space) holds what `PriorDistribution.from_variances` makes of the
    model's means and variances, aligned by key and projected through the
    bucket's index map — `align_entity_priors` + `gather_rows`, the host
    block loop's two (E, d) arrays — and each entity new since day 0 gets
    precision 0."""
    prob0, prob1 = _problem(seed=8), _problem(seed=8)
    prob1["val"] = (prob0["val"] * np.random.default_rng(9).uniform(
        0.5, 1.5, prob0["val"].shape)).astype(np.float32)
    prob1["val"][:, -1] = 1.0
    cfg = OptimizerConfig(max_iters=20, tolerance=0.0, reg=reg.l2(),
                          reg_weight=L2)
    data0 = _data(prob0)
    ds0 = _dataset(data0)
    day0 = coordinate_descent(
        {"re": RandomEffectCoordinate(ds0, TASK, cfg, variance=FULL)},
        data0.y, data0.weights, data0.offsets, TASK).model["re"]
    keep = np.nonzero(~np.isin(day0.entity_keys, [2, 5]))[0]
    prior = RandomEffectModel(
        "e", "s", TASK, day0.coefficients[keep], day0.entity_keys[keep],
        {k: i for i, k in enumerate(day0.entity_keys[keep].tolist())},
        variances=day0.variances[keep])
    ds1 = _dataset(_data(prob1))
    coord1 = RandomEffectCoordinate(ds1, TASK, cfg, variance=FULL)
    _, blocks_args, *_ = coord1.fused_update_program()
    with telemetry.run("prior") as run:
        got = bucket_priors(prior, ds1.entity_keys, blocks_args)
        counters = run.report_compact()["counters"]
    assert counters["game_re.prior_unseen"] == 2
    d = FEATURES + 1
    means, precs = align_entity_priors(prior, ds1.entity_keys, d)
    dist = PriorDistribution.from_variances(
        np.asarray(day0.coefficients), np.asarray(day0.variances))
    for block, (mu, tau) in zip(ds1.blocks, got):
        ents = block.entity_index
        np.testing.assert_array_equal(
            np.asarray(mu), gather_rows(means[ents], block.proj))
        np.testing.assert_allclose(
            np.asarray(tau), gather_rows(precs[ents], block.proj), rtol=1e-6)
        for i, e in enumerate(ents):
            key = ds1.entity_keys[e]
            real = block.proj.proj_mask[i] > 0
            cols = block.proj.proj_idx[i][real]
            if key in (2, 5):
                assert not np.any(np.asarray(tau)[i])
                continue
            row = int(np.searchsorted(day0.entity_keys, key))
            np.testing.assert_allclose(np.asarray(tau)[i][real],
                                       dist.precision_diag[row, cols],
                                       rtol=1e-6)
            assert np.all(np.asarray(tau)[i][real] > 0)
            assert not np.any(np.asarray(tau)[i][~real])  # padding


def test_initial_table_finds_rows_by_key():
    keys = np.array([1, 4, 6, 9])
    model = RandomEffectModel(
        "e", "s", TASK, jnp.arange(8, dtype=jnp.float32).reshape(4, 2),
        keys, {k: i for i, k in enumerate(keys.tolist())})
    same = initial_table(model, keys)
    np.testing.assert_array_equal(np.asarray(same),
                                  np.asarray(model.coefficients))
    assert same is not model.coefficients  # a fresh buffer to donate
    moved = np.asarray(initial_table(model, np.array([0, 4, 9, 12])))
    np.testing.assert_array_equal(moved, [[0, 0], [2, 3], [6, 7], [0, 0]])


def test_fixed_effect_prior_takes_the_fused_update():
    """A fixed-effect coordinate with an incremental prior is fused: one
    program, its objective carrying `PriorDistribution.from_coefficients`
    of the prior, its variances FULL at the solution under the scope
    `game_fixed.variance`."""
    import re

    cd = importlib.import_module("photon_tpu.game.coordinate_descent")
    from photon_tpu.game.estimator import FixedEffectConfig, GameEstimator

    rng = np.random.default_rng(10)
    X = rng.normal(size=(500, 6)).astype(np.float32)
    y = (rng.uniform(size=500) < 1 / (1 + np.exp(-X @ rng.normal(size=6)))
         ).astype(np.float32)
    data = GameData.build(y, {"f": X}, {})
    cfg = OptimizerConfig(max_iters=60, tolerance=1e-9, reg=reg.l2(),
                          reg_weight=L2)
    est = GameEstimator(TASK, {"fixed": FixedEffectConfig("f", cfg)},
                        n_sweeps=1, variance=FULL)
    (day0,) = est.fit(data)
    inc = GameEstimator(TASK, {"fixed": FixedEffectConfig("f", cfg)},
                        n_sweeps=1, variance=FULL,
                        incremental=frozenset({"fixed"}))
    calls = []
    real = cd._fused_fixed_update

    def counting(*a, **k):
        calls.append(a[4])  # the objective
        return real(*a, **k)

    mp = pytest.MonkeyPatch()
    mp.setattr(cd, "_fused_fixed_update", counting)
    try:
        (day1,) = inc.fit(data, initial_models=dict(day0.model.coordinates))
    finally:
        mp.undo()
    assert len(calls) == 1 and calls[0].prior_precision is not None
    prior = day0.model["fixed"].model.coefficients
    mu = np.asarray(prior.means, np.float64)
    tau = 1.0 / np.asarray(prior.variances, np.float64)
    w = np.asarray(day1.model["fixed"].model.coefficients.means, np.float64)
    Xd, yd = X.astype(np.float64), y.astype(np.float64)
    g = iref.gradient(Xd, yd, np.zeros(500), w, L2, mu, tau)
    assert np.linalg.norm(g) < 1e-3
    want = iref.full_variances(iref.hessian(Xd, yd, np.zeros(500), w, L2,
                                            tau))
    np.testing.assert_allclose(
        np.asarray(day1.model["fixed"].model.coefficients.variances), want,
        rtol=1e-4)
    fn, args = cd._contract_game_fixed_update()
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "game_fixed.solve" in text
    from photon_tpu.models.training import _static_config

    text = jax.jit(lambda *a: cd._fused_fixed_update(
        *a[:5], None, *a[5:], _static_config(cfg), TASK, FULL)).lower(
        *args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("game_fixed.variance" in n.split("/") for n in names)


def test_prior_and_variance_scopes_reach_the_compiled_update():
    import re

    prob = _problem(seed=11)
    data = _data(prob)
    ds = _dataset(data)
    prior = _prior(12, ds.entity_keys)
    coord = RandomEffectCoordinate(ds, TASK, OptimizerConfig(
        max_iters=3, tolerance=0.0, reg=reg.l2(), reg_weight=L2),
        variance=FULL)
    fn, blocks_args, plan, objs, lam = coord.fused_update_program()
    priors = bucket_priors(prior, ds.entity_keys, blocks_args)
    n = data.n
    zeros = jnp.zeros((n,), jnp.float32)
    text = fn.lower(jnp.zeros((ds.n_entities, ds.dim), jnp.float32),
                    random_effect.cold_warm_starts(blocks_args, ds.dim),
                    zeros, (zeros,), objs, lam, blocks_args, plan, zeros,
                    zeros, priors).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("game_re.variance" in n.split("/") for n in names)
    assert any("game_re.solve" in n.split("/") for n in names)
    text = random_effect._bucket_priors.lower(
        jnp.asarray(prior.coefficients), jnp.asarray(prior.variances),
        jnp.zeros((ds.n_entities,), jnp.int32),
        tuple((e, c) for _, e, c, _ in blocks_args)).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("game_re.prior" in n.split("/") for n in names)


def test_benchmark_json_lists_the_cell():
    """The cell, its configuration and its metrics are declared, each
    metric with its reader, and the configuration keeps `glmix-wide`'s
    widths: only the three scale keys it lists in `reduced` differ."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = "glmix-incremental.refresh"
    entry = next(w for w in spec["workloads"] if w["name"] == cell)
    assert entry["chips"] == 1 and entry["config"] == "glmix-incremental"
    config_entry = next(c for c in spec["configs"]
                        if c["name"] == "glmix-incremental")
    mine = {m["name"] for m in spec["per_layer"]
            if cell in m.get("workloads", ())}
    assert {"re_variance_ms", "re_variance_mxu_share", "re_solve_ms",
            "fit_dispatches"} <= mine
    for name in mine:
        assert os.path.exists(os.path.join(root, "benchmark", "layer_metrics",
                                           f"{name}.py"))
    with open(os.path.join(root, config_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "configs",
                           "glmix-wide.json")) as f:
        wide = json.load(f)
    assert config["architecture"] is None
    assert config["incremental"] == list(config["update_sequence"])
    assert config["variance"] == "full"
    changed = {k for k, v in wide.items()
               if isinstance(v, (int, float)) and config.get(k) != v}
    assert changed == set(config_entry["reduced"]) == {
        "n_train_rows", "n_users", "n_items"}
    assert config["coordinates"] == wide["coordinates"]
    for key in ("n_train_rows", "n_users", "n_items"):
        assert wide[key] == 2 * config[key]


# -------------------------------------- (iv) the cell's comparison and its faults
@pytest.fixture(scope="module")
def refresh_cell(tmp_path_factory):
    """`glmix-incremental.refresh` at its rehearse sizes: (traffic module,
    state, the warm-up fit's evidence), as `benchmark/run.py` builds
    them."""
    from benchmark.traffic import game_incremental

    # what this module's earlier tests compiled is not needed again: drop
    # it before the cell's programs, so the process holds fewer live
    # executables (tests/conftest.py, `release_programs`)
    for cache in (random_effect._FUSED_RE, random_effect._RE_SOLVERS,
                  random_effect._SCAN_DISPATCH, random_effect._RE_VARIANCES):
        cache.clear()
    jax.clear_caches()
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", "glmix-incremental.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "workloads",
                           "glmix-incremental.refresh.json")) as f:
        params = json.load(f)["params"]
    config = {**config, **config["rehearse"]}
    state = game_incremental.setup(
        config, params, 2147483659,
        {"shared": str(tmp_path_factory.mktemp("pattern"))})
    evidence = game_incremental.unit(state, keep=True)["evidence"]
    return game_incremental, state, evidence


def _forget_programs(state):
    """Drop the compiled one-dispatch updates the cell's coordinates hold,
    so that a planted fault is traced into a program of its own."""
    for entry in state.estimator.estimator._caches.values():
        for coord in entry[2].values():
            coord.__dict__.pop("_fused_cache", None)


def _plant_prior_dropped(mp):
    cd = importlib.import_module("photon_tpu.game.coordinate_descent")

    real = random_effect._bucket_priors

    def dropped(means, variances, pid, maps):
        return tuple((mu, jnp.zeros_like(tau))
                     for mu, tau in real(means, variances, pid, maps))

    mp.setattr(random_effect, "_bucket_priors", dropped)
    mp.setattr(cd, "_fixed_prior_objective", lambda obj, coord, prior: obj)


def _plant_simple_variances(mp):
    real = random_effect._re_variances
    mp.setattr(random_effect, "_re_variances",
               lambda with_prior, variance: real(
                   with_prior, VarianceComputationType.SIMPLE))


def _plant_bf16_gram(mp):
    from photon_tpu.ops import objective

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    mp.setattr(objective, "weighted_gram",
               lambda X, r: bf16(X * r[:, None]).T @ bf16(X))


def _plant_unseen_unit_precision(mp):
    real = random_effect._bucket_priors

    def unit(means, variances, pid, maps):
        out = []
        for (mu, tau), (ents, cols) in zip(
                real(means, variances, pid, maps), maps):
            new = (pid[ents] >= means.shape[0])[:, None] & (
                cols < means.shape[1])
            out.append((mu, jnp.where(new, 1.0, tau)))
        return tuple(out)

    mp.setattr(random_effect, "_bucket_priors", unit)


# (plant, retraced, the limit that refuses it): a fault in what the
# one-dispatch update is HANDED (its priors) runs the compiled program; a
# fault in the program itself is traced into a program of its own
@pytest.mark.parametrize("plant,retraced,refused_by", [
    (None, False, None),
    (_plant_prior_dropped, False, "per_item.gap"),
    (_plant_simple_variances, True, "per_item.var_rel"),
    (_plant_bf16_gram, True, "per_item.var_rel"),
    (_plant_unseen_unit_precision, False, "per_item.gap"),
], ids=["sound", "prior_dropped", "simple_variances", "bf16_gram",
        "unseen_unit_precision"])
def test_cell_comparison_refuses_faults_planted_in_the_program(
        refresh_cell, plant, retraced, refused_by):
    """`game_incremental.check` passes the sound refresh and refuses each
    of its four controls when the PROGRAM makes that fault, by the limit
    that is there for it; in every run its own controls are refused too."""
    traffic, state, evidence = refresh_cell
    if plant is not None:
        mp = pytest.MonkeyPatch()
        try:
            if retraced:
                mp.setattr(random_effect, "_FUSED_RE", {})
                _forget_programs(state)
            plant(mp)
            evidence = traffic.unit(state, keep=True)["evidence"]
        finally:
            mp.undo()
            _forget_programs(state)
    verdict = traffic.check(state, evidence)
    assert verdict["controls_refused"]
    for control in ("prior_dropped", "simple_variances", "bf16_gram",
                    "unseen_unit_precision"):
        assert verdict["controls"][control]["refused_by"], control
    if refused_by is None:
        assert verdict["ok"] and not verdict["refused_by"], verdict[
            "refused_by"]
        assert verdict["entities"]["per_item"]["unseen"] > 0
    else:
        assert not verdict["ok"]
        assert refused_by in verdict["refused_by"], verdict["refused_by"]


def test_probe_refuses_a_prior_on_the_host_block_loop(refresh_cell,
                                                      tmp_path, monkeypatch):
    """The cell's set-up probe passes this program and fails, naming the
    host path, a program whose prior-carrying update is not fused."""
    traffic, state, _ = refresh_cell
    said = traffic.probe(state.config, str(tmp_path))
    assert said["game_re.fused_prior_updates"] == 2
    assert said["game_re.variance_lanes"] > 0
    monkeypatch.setattr(RandomEffectCoordinate, "fused_update_program",
                        lambda self: None)
    with pytest.raises(SystemExit, match="host path"):
        traffic.probe(state.config, str(tmp_path))
