"""GLMix's regularization grid as lanes of one coordinate descent
(`glmix-grid.sweep8`): an INDEX_MAP GLMix grid from the training driver's
JSON takes the lane path, its 8 models agree with 8 sequential cold fits
and with the plain float64 reference, the cell's comparison refuses faults
planted in the PROGRAM, no lane program builds an (n, d) per-row copy of
the coefficients, and at G = 1 the one-dispatch update is the program it
was before the lane axis, text for text."""
from __future__ import annotations

import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.game import grid as game_grid
from photon_tpu.game import random_effect

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "glmix-grid.sweep8"
SEED = 2147483659


def _config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        config = json.load(f)
    return {**config, **config["rehearse"]}


@pytest.fixture(scope="module")
def grid_cell(tmp_path_factory):
    """`glmix-grid.sweep8` at its rehearse sizes: (traffic module, state,
    the warm-up grid's evidence and telemetry report), as
    `benchmark/run.py` builds them."""
    from benchmark.traffic import game_grid as traffic

    for cache in (random_effect._FUSED_RE, random_effect._RE_SOLVERS,
                  random_effect._SCAN_DISPATCH):
        cache.clear()
    jax.clear_caches()
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        params = json.load(f)["params"]
    state = traffic.setup(_config("glmix-grid"), params, SEED,
                          {"shared": str(tmp_path_factory.mktemp("pat"))})
    evidence = traffic.unit(state, keep=True)["evidence"]
    return traffic, state, evidence, state.facts["warmup_report"]


# ------------------------------------------------------------ the lane path
def test_driver_json_grid_takes_the_lane_path(grid_cell):
    """The cell's grid — the driver's `_config_grid` of its `reg_weights`
    lists — is 8 lanes of ONE descent: `game_grid.lanes` 8,
    `game.grid_points` 8, no `game.fit_point` span; every descent counter
    a sequential grid reports, summed over the lanes."""
    _, state, evidence, report = grid_cell
    counters, spans = report["counters"], report["span_totals"]
    assert len(state.grid) == 8 and len(evidence["lanes"]) == 8
    assert counters["game_grid.lanes"] == 8
    assert counters["game.grid_points"] == 8
    assert not any(k.split("/")[-1] == "game.fit_point" for k in spans)
    updates = int(state.config["n_sweeps"]) * 3
    assert counters["game.coordinate_updates"] == 8 * updates
    blocks = sum(len(b) for b in state.facts["blocks"].values())
    sweeps = int(state.config["n_sweeps"])
    assert counters["game_re.blocks"] == 8 * sweeps * blocks
    assert counters["game_re.warm_cold"] == counters["game_re.warm_carried"] \
        == 8 * 2 * (sweeps - 1)
    assert evidence["device_best"] == evidence["best"]
    assert state.estimator.would_vectorize(state.grid, data=state.data)


def test_lane_grid_matches_sequential_cold_fits(grid_cell):
    """The 8 lanes are the 8 grid points fitted one by one from zeros
    through the same estimator (`vectorized_grid=False`): the same
    coefficients, objective histories and validation AUCs to f32 noise —
    which a fixed-depth solve stalled at its objective's resolution carries
    into its last digits (a lane's line search does not repeat a scalar
    solve's bit for bit)."""
    import dataclasses

    _, state, evidence, _ = grid_cell
    sequential = dataclasses.replace(state.estimator, vectorized_grid=False)
    results = sequential.fit(state.data, state.validation,
                             config_grid=state.grid)
    for lane, r in zip(evidence["lanes"], results):
        assert lane["weights"] == {
            n: float(c.optimizer.reg_weight) for n, c in r.configs.items()}
        np.testing.assert_allclose(
            lane["fixed"]["fixed"],
            np.asarray(r.model["fixed"].model.coefficients.means),
            rtol=1e-3, atol=1e-3)
        for name in ("per_user", "per_item"):
            np.testing.assert_allclose(
                lane["tables"][name], np.asarray(r.model[name].coefficients),
                rtol=1e-3, atol=2e-3)
        np.testing.assert_allclose(lane["history"],
                                   r.descent.objective_history, rtol=1e-5)
        assert abs(lane["auc"] - r.validation_score) < 1e-5


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_entity_lane_solve_is_each_lanes_own_solve(sparse):
    """`random_effect._re_lane_solve` — a bucket's (entity × lane) solves as
    ONE lane-minor solve over `EntityBlocks` — gives each (entity, lane)
    what that entity's own solve under the lane's L2 weight and offsets
    gives — value, coefficients and iterations to f32 noise, its first
    line search's evaluations exactly — over dense blocks and over an
    unprojected bucket's sparse slots."""
    import dataclasses

    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.data.matrix import SparseRows
    from photon_tpu.models.training import _static_config, solve
    from photon_tpu.ops.objective import Objective
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim import regularization as reg
    from photon_tpu.optim.config import OptimizerConfig

    rng = np.random.default_rng(7)
    E, m, w, k, G = 3, 9, 6, 3, 2
    cfg = _static_config(OptimizerConfig(max_iters=6, tolerance=0.0,
                                         history=4, reg=reg.l2(),
                                         reg_weight=1.0))
    obj = Objective(TaskType.LOGISTIC_REGRESSION)
    if sparse:
        X = SparseRows(jnp.asarray(rng.integers(0, w, (E, m, k)), jnp.int32),
                       jnp.asarray(rng.normal(size=(E, m, k)), jnp.float32), w)
    else:
        X = jnp.asarray(rng.normal(size=(E, m, w)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, (E, m)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.5, 2.0, (E, m)), jnp.float32)
    offsets = jnp.asarray(rng.normal(size=(E, m, G)), jnp.float32)
    w0 = jnp.asarray(0.1 * rng.normal(size=(E, w, G)), jnp.float32)
    l2s = jnp.asarray([0.3, 3.0], jnp.float32)
    batch = GLMBatch(X, y, weights, offsets)
    res, _ = random_effect._re_lane_solve(cfg, obj, l2s, None, batch, w0)
    res1, _ = random_effect._re_lane_solve(
        dataclasses.replace(cfg, max_iters=1), obj, l2s, None, batch, w0)
    for e in range(E):
        Xe = (SparseRows(X.indices[e], X.values[e], w) if sparse else X[e])
        for g in range(G):
            one = solve(dataclasses.replace(obj, l2=l2s[g]),
                        GLMBatch(Xe, y[e], weights[e], offsets[e, :, g]),
                        w0[e, :, g], cfg)
            np.testing.assert_allclose(res.value[e, g], one.value,
                                       rtol=1e-6)
            np.testing.assert_allclose(res.w[e, :, g], one.w, atol=1e-3)
            assert int(res.iterations[e, g]) == int(one.iterations)
            # past its stall a lane's search does not repeat the scalar
            # one trial for trial; the first search does
            first = solve(dataclasses.replace(obj, l2=l2s[g]),
                          GLMBatch(Xe, y[e], weights[e], offsets[e, :, g]),
                          w0[e, :, g], dataclasses.replace(cfg, max_iters=1))
            assert int(res1.evaluations[e, g]) == int(first.evaluations)


# ------------------------------------------- the cell's comparison and faults
def _plant_swapped_l2(mp):
    """Every lane solved under another lane's L2 weights (the lanes'
    weight arrays reversed)."""
    real = game_grid.lane_weight_arrays

    def swapped(config, weights):
        l2s, l1s, cfg = real(config, weights)
        return l2s[::-1], l1s, cfg

    mp.setattr(game_grid, "lane_weight_arrays", swapped)


def _plant_bf16_blocks(mp):
    """bf16 products over the blocks: every block the lane solves and the
    block scoring read rounded to bf16."""
    from photon_tpu.game.dataset import RandomEffectDataset

    real = RandomEffectDataset.block_batch

    def rounded(self, block, offsets_full=None):
        batch = real(self, block, offsets_full)
        return batch._replace(X=batch.X.astype(jnp.bfloat16)
                              .astype(jnp.float32))

    mp.setattr(RandomEffectDataset, "block_batch", rounded)


def _plant_zero_lane(mp):
    """One lane's tables left at zero when the lanes' models are made."""
    real = game_grid._lane_models_tables

    def zeroed(solutions, maps, shape):
        tables = list(real(solutions, maps, shape))
        tables[3] = jnp.zeros_like(tables[3])
        return tuple(tables)

    mp.setattr(game_grid, "_lane_models_tables", zeroed)


def _forget_programs(state):
    for entry in state.estimator._caches.values():
        for coord in entry[2].values():
            coord.__dict__.pop("_fused_lanes_cache", None)


@pytest.mark.parametrize("plant,refused_by", [
    (None, None),
    (_plant_swapped_l2, "per_item.gap"),
    (_plant_bf16_blocks, "per_item.value_rel"),
    (_plant_zero_lane, "lane3"),
], ids=["sound", "swapped_l2", "bf16_blocks", "zero_lane"])
def test_cell_comparison_refuses_faults_planted_in_the_program(
        grid_cell, plant, refused_by):
    """`game_grid.check` passes the sound grid — every lane against the
    float64 reference under its own weights — and refuses each fault
    planted in the PROGRAM by the limit that is there for it; in every run
    its own controls are refused too."""
    traffic, state, evidence, _ = grid_cell
    if plant is not None:
        mp = pytest.MonkeyPatch()
        try:
            mp.setattr(random_effect, "_FUSED_RE", {})
            _forget_programs(state)
            plant(mp)
            evidence = traffic.unit(state, keep=True)["evidence"]
        finally:
            mp.undo()
            _forget_programs(state)
    verdict = traffic.check(state, evidence)
    assert verdict["controls_refused"]
    for control in ("bf16", "lanes_swapped", "zero_table.per_user",
                    "zero_table.per_item", "zero_fixed"):
        assert not verdict["controls"][control]["ok"], control
    if refused_by is None:
        assert verdict["ok"] and not verdict["refused_by"], \
            verdict["refused_by"]
    else:
        assert not verdict["ok"]
        assert refused_by in verdict["refused_by"], verdict["refused_by"]


def test_reference_lanes_are_the_driver_grid():
    """The reference's lanes are the driver's grid, point for point."""
    from benchmark.gen.glmix_grid_reference import lane_weights
    from photon_tpu.drivers.train import CoordinateSpec, _config_grid

    config = _config("glmix-grid")
    specs = {n: CoordinateSpec(**s) for n, s in config["coordinates"].items()}
    driver = [{n: float(c.optimizer.reg_weight) for n, c in point.items()}
              for point in _config_grid(specs)]
    assert [{n: w[n] for n in p} for w, p in zip(lane_weights(config),
                                                 driver)] == driver
    assert len(driver) == 8


# ------------------------------------------- no (n, d) per-row coefficients
def _shapes(text: str) -> set:
    return {tuple(int(x) for x in dims.split("x"))
            for dims in re.findall(r"tensor<((?:\d+x)+)f32>", text)
            for dims in [dims.rstrip("x")]}


def test_lane_programs_build_no_per_row_coefficient_copy(grid_cell):
    """The lane update and the lanes' validation program read k
    coefficients a row out of the buckets' lane solutions: no f32 array of
    the lowered programs has a row count of the training, passive or
    validation rows beside the shard's width d, and the update takes and
    gives no (E, d) table."""
    _, state, _, _ = grid_cell
    from photon_tpu.game.estimator import _grid_select

    coords = [c for entry in state.estimator._caches.values()
              for c in entry[2].values()
              if isinstance(c, random_effect.RandomEffectCoordinate)]
    assert coords
    n = state.data.n
    n_val = int(state.validation.y.shape[0])
    G = 8
    for coord in coords:
        ds = coord.dataset
        d = ds.dim
        _, _, cfg = game_grid.lane_weight_arrays(coord.config, [1.0] * G)
        fn, blocks_args, plan, objs, _ = coord.fused_update_program(
            lanes=G, config=cfg)
        zeros = jnp.zeros((n, G), jnp.float32)
        warm = random_effect.cold_warm_starts(blocks_args, d, G)
        lowered = fn.lower(None, warm, jnp.zeros((n,), jnp.float32),
                           (zeros, zeros), objs,
                           (jnp.ones((G,), jnp.float32), None), blocks_args,
                           plan, state.data.y, state.data.weights)
        text = lowered.as_text()
        rows = {n, plan.n_table_rows}
        assert not [s for s in _shapes(text) if d in s and rows & set(s)]
        assert (ds.n_entities, d) not in _shapes(text)
        Xv = state.validation.shards[ds.shard_name]
        text = _grid_select.lower(
            jnp.zeros((n_val,), jnp.float32), (),
            ((warm, Xv.values, jnp.zeros(Xv.indices.shape, jnp.int32)),),
            state.validation.y, state.validation.weights,
            None, True).as_text()
        assert not [s for s in _shapes(text) if d in s and n_val in s]


def test_lane_positions_find_each_coefficient(grid_cell):
    """`RandomEffectDataset.lane_positions`: every column of every entity's
    index map is found where its bucket's solutions lie end to end, and a
    feature outside an entity's map, or an entity no bucket holds, reads
    past the end."""
    _, state, _, _ = grid_cell
    ds = state.estimator.datasets(state.data)["per_user"]
    total = sum(b.n_entities * b.proj.proj_idx.shape[1] for b in ds.blocks)
    base = 0
    for block in ds.blocks:
        width = block.proj.proj_idx.shape[1]
        at = np.asarray(ds.lane_positions(block.entity_index,
                                          block.proj.proj_idx))
        want = base + np.arange(block.n_entities * width).reshape(-1, width)
        mask = block.proj.proj_mask > 0
        np.testing.assert_array_equal(at[mask], want[mask])
        base += block.n_entities * width
    assert base == total
    block = ds.blocks[0]
    outside = np.setdiff1d(np.arange(ds.dim),
                           block.proj.proj_idx[0][block.proj.proj_mask[0] > 0])
    assert (np.asarray(ds.lane_positions(block.entity_index[:1],
                                         outside[None, :3])) == total).all()
    assert (np.asarray(ds.lane_positions([ds.n_entities], [[0]]))
            == total).all()


# ------------------------------------- G = 1: the parent's program, text for text
def _update_program_hashes(cell: str) -> list:
    """SHA-256 of the lowered one-dispatch random-effect updates of
    ``cell``'s coordinates at its rehearse sizes, built from the datasets
    as a cold first update would call them (an incremental cell's with a
    prior of zeros and unit variances)."""
    from benchmark.gen import glmix_wide
    from photon_tpu.drivers.train import CoordinateSpec
    from photon_tpu.game.dataset import GameData, RandomEffectDataset
    from photon_tpu.game.model import RandomEffectModel
    from photon_tpu.game.random_effect import (RandomEffectCoordinate,
                                               bucket_priors,
                                               cold_warm_starts)
    from photon_tpu.data.matrix import SparseRows
    from photon_tpu.models.variance import VarianceComputationType
    from photon_tpu.ops.losses import TaskType
    import tempfile

    config = _config(cell.split(".")[0])
    arrays = glmix_wide.draw(config, SEED, glmix_wide.pattern(
        config, tempfile.mkdtemp()))["train"]
    p = int(config["re_features"]) + 1
    data = GameData.build(
        arrays["y"], {"job_f": SparseRows(*arrays["job_f"], p),
                      "member_f": SparseRows(*arrays["member_f"], p)},
        {"memberId": arrays["member"], "jobId": arrays["job"]})
    variance = VarianceComputationType[config.get("variance", "none").upper()]
    out = []
    for name, spec in config["coordinates"].items():
        if "entity_name" not in spec:
            continue
        cfg = CoordinateSpec(**spec).coordinate_config()
        ds = RandomEffectDataset.build(
            data, cfg.entity_name, cfg.feature_shard,
            active_cap=cfg.active_cap, projection=cfg.projection)
        coord = RandomEffectCoordinate(ds, TaskType[config["task"]],
                                       cfg.optimizer, variance=variance)
        fn, blocks_args, plan, objs, lam = coord.fused_update_program()
        n = data.n
        zeros = jnp.zeros((n,), jnp.float32)
        priors = None
        if config.get("incremental"):
            E, d = ds.n_entities, ds.dim
            prior = RandomEffectModel(
                ds.entity_name, ds.shard_name, TaskType[config["task"]],
                jnp.zeros((E, d), jnp.float32), ds.entity_keys,
                ds.key_to_index, jnp.ones((E, d), jnp.float32))
            priors = bucket_priors(prior, ds.entity_keys, blocks_args)
        text = fn.lower(jnp.zeros((ds.n_entities, ds.dim), jnp.float32),
                        cold_warm_starts(blocks_args, ds.dim), zeros,
                        (zeros, zeros), objs, lam, blocks_args, plan,
                        jnp.asarray(data.y), jnp.asarray(data.weights),
                        priors).as_text()
        out.append(hashlib.sha256(text.encode()).hexdigest())
    return out


# the parent commit's lowered programs (per-member, per-job), hashed by
# `_update_program_hashes` on that tree with this file
PARENT_PROGRAMS = {
    "glmix-wide.descent": [
        "06741d05dbb5380cfab0ac8a12e5efd1cf8c2b672fb9535de76a686956fbc04e",
        "0112ee63759dca93af2c740543fbfbba92d3e2c9f7ce4e32d2cd2b3b17c1b6f5"],
    "glmix-incremental.refresh": [
        "46d0ad0ea870c1c3f042f5469092d24844a145a1781c19d29e29c3bf8d7d848e",
        "4ec6671efed20c676169d431efa97589bd2034c23b742b03149dc1effc60d12f"],
}


@pytest.mark.parametrize("cell", sorted(PARENT_PROGRAMS))
def test_single_lane_update_is_the_parent_program(cell):
    """At G = 1 the one-dispatch update of `glmix-wide.descent` and of
    `glmix-incremental.refresh` lowers to the program it was before the
    lane axis, text for text."""
    assert _update_program_hashes(cell) == PARENT_PROGRAMS[cell]
