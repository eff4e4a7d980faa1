"""GLMix at real widths (PR 27): sparse per-entity random effects with an
active-row cap and INDEX_MAP projection, through the estimator and the
training driver — against the plain per-entity Newton reference the
benchmark's `glmix-wide.descent` cell decides `correct` with, at tiny
sizes on the CPU.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.gen import glmix_reference as ref
from photon_tpu import telemetry
from photon_tpu.data.matrix import SparseRows
from photon_tpu.game import dataset as game_dataset
from photon_tpu.game.coordinate_descent import coordinate_descent
from photon_tpu.game.dataset import GameData, RandomEffectDataset, plan_buckets
from photon_tpu.game.estimator import GameEstimator, RandomEffectConfig
from photon_tpu.game.projector import ProjectionConfig, ProjectorType
from photon_tpu.game.random_effect import (RandomEffectCoordinate,
                                           cold_warm_starts)
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim import regularization as reg
from photon_tpu.optim.config import OptimizerConfig

TASK = TaskType.LOGISTIC_REGRESSION
INDEX_MAP = ProjectionConfig(ProjectorType.INDEX_MAP)
L2 = 2.0
FEATURES, NNZ, CAP = 40, 5, 12


def _problem(seed=0, n_entities=30, n=900):
    """Sparse rows with a zipf entity skew (so the cap bites and buckets
    differ), one slot of every row naming a feature twice (duplicates
    accumulate), the intercept last; labels from planted effects."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, n_entities + 1, dtype=np.float64) ** -1.0
    ent = rng.choice(n_entities, size=n, p=p / p.sum()).astype(np.int32)
    ind = rng.integers(0, FEATURES, size=(n, NNZ)).astype(np.int32)
    ind[:, 1] = ind[:, 0]  # a feature named twice
    val = rng.normal(size=(n, NNZ)).astype(np.float32)
    ind = np.concatenate([ind, np.full((n, 1), FEATURES, np.int32)], axis=1)
    val = np.concatenate([val, np.ones((n, 1), np.float32)], axis=1)
    truth = rng.normal(size=(n_entities, FEATURES + 1))
    dense = np.zeros((n, FEATURES + 1), np.float32)
    np.add.at(dense, (np.arange(n)[:, None], ind), val)
    margin = np.einsum("nd,nd->n", dense, truth[ent]) * 0.5
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    offsets = rng.normal(size=n).astype(np.float32) * 0.3
    return {"ent": ent, "ind": ind, "val": val, "dense": dense, "y": y,
            "offsets": offsets}


def _game_data(prob, sparse: bool) -> GameData:
    X = (SparseRows(prob["ind"], prob["val"], FEATURES + 1) if sparse
         else prob["dense"])
    return GameData.build(prob["y"], {"s": X}, {"e": prob["ent"]},
                          offsets=prob["offsets"])


def _active_rows(ds, e):
    """The rows the dataset trains entity ``e`` on (what a cap kept)."""
    for block in ds.blocks:
        at = np.nonzero(np.asarray(block.entity_index) == e)[0]
        if at.size:
            real = np.asarray(block.weights)[at[0]] != 0.0
            return np.asarray(block.row_index)[at[0]][real]
    raise AssertionError(f"entity {e} is in no block")


# ------------------------------------------------------------ (i) estimator
@pytest.mark.parametrize("cap", [None, CAP], ids=["nocap", "cap"])
@pytest.mark.parametrize("projection", [None, INDEX_MAP],
                         ids=["full", "index_map"])
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_fit_agrees_with_plain_reference(sparse, projection, cap):
    """`GameEstimator.fit` run to convergence lands every entity on the
    plain Newton optimum of ITS rows (capped where a cap is set) and
    columns, and reports the float64 loss of the coefficients it returns."""
    prob = _problem()
    data = _game_data(prob, sparse)
    cfg = RandomEffectConfig(
        "e", "s", OptimizerConfig(max_iters=300, tolerance=1e-9, reg=reg.l2(),
                                  reg_weight=L2),
        active_cap=cap, projection=projection)
    est = GameEstimator(TASK, {"re": cfg}, n_sweeps=1)
    (result,) = est.fit(data)
    ds = est.datasets(data)["re"]
    table = np.asarray(result.model["re"].coefficients, np.float64)
    counts = np.bincount(prob["ent"])
    for e in range(ds.n_entities):
        key = int(ds.entity_keys[e])
        rows = _active_rows(ds, e)
        assert len(rows) == (min(counts[key], cap) if cap else counts[key])
        assert np.all(prob["ent"][rows] == key)
        cols, X = ref.entity_problem(prob["ind"][rows], prob["val"][rows])
        y = prob["y"][rows].astype(np.float64)
        offs = prob["offsets"][rows].astype(np.float64)
        w_star, best, _ = ref.newton(X, y, offs, L2)
        # f32 L-BFGS at tolerance 1e-9 stops on f32 line-search resolution:
        # coefficients to 1e-3 of their scale (~1), the objective to 1e-5
        np.testing.assert_allclose(table[e, cols], w_star, atol=2e-3)
        assert np.count_nonzero(table[e]) <= len(cols)
        at_fit = ref.objective(X, y, offs, table[e, cols], L2)
        assert -1e-9 <= (at_fit - best) / best <= 1e-5
    margin = prob["offsets"] + ref.sparse_margins(
        prob["ind"], prob["val"], table,
        ref.table_rows(prob["ent"], prob["ent"]))
    loss = ref.log_loss(margin, prob["y"])
    # an f32 sum of 900 f32 per-row losses against float64
    assert result.descent.objective_history[-1] == pytest.approx(loss,
                                                                 rel=1e-5)


# ------------------------------------------- (ii) one dispatch vs block loop
@pytest.mark.parametrize("lanes", [None, 5], ids=["one_chunk", "scanned"])
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_projected_one_dispatch_update_matches_block_loop(sparse, lanes,
                                                          monkeypatch):
    """The projected one-dispatch update and the host block loop run the
    same vmapped solver on the same blocks; they differ in how many lanes a
    call holds (the loop pads a bucket's entities to a power of two; the
    update scans a bucket a chunk of lanes at a time, the last chunk laid
    back over the end of the bucket), so XLA may order a lane's f32
    reductions differently: coefficients agree to a few f32 ulps of their
    scale carried through 25 iterations (1e-4), iteration counts exactly."""
    from photon_tpu.game import random_effect

    if lanes is not None:  # buckets of 7-20 entities in chunks of 5 lanes
        monkeypatch.setattr(random_effect, "_MAX_SOLVE_LANES", lanes)
    prob = _problem(seed=1)
    data = _game_data(prob, sparse)
    ds = RandomEffectDataset.build(data, "e", "s", active_cap=CAP,
                                   projection=INDEX_MAP)
    cfg = OptimizerConfig(max_iters=25, tolerance=0.0, reg=reg.l2(),
                          reg_weight=L2)
    coord = RandomEffectCoordinate(ds, TASK, cfg)
    assert coord.fused_update_program() is not None
    out = coordinate_descent({"re": coord}, data.y, data.weights,
                             data.offsets, TASK, n_sweeps=2)
    model, stats = coord.train(jnp.asarray(data.offsets))
    model2, stats2 = coord.train(jnp.asarray(data.offsets), warm_start=model)
    fused = out.coordinate_stats["re"]
    np.testing.assert_allclose(np.asarray(out.model["re"].coefficients),
                               np.asarray(model2.coefficients),
                               rtol=1e-4, atol=1e-4)
    assert [s.total_iterations for s in fused] == [stats.total_iterations,
                                                   stats2.total_iterations]
    assert fused[0].row_iterations == pytest.approx(stats.row_iterations)
    assert fused[0].row_iterations > 0


def test_descent_keeps_the_table_off_the_host(monkeypatch):
    """During a projected descent no (E, d) table is built on, or copied
    from, the host: after the first update has placed what it reads, a
    whole sweep makes no host→device transfer of a table's size and the
    block loop's host gather / scatter never run."""
    from photon_tpu.game import projector

    prob = _problem(seed=2)
    data = _game_data(prob, sparse=True)
    cfg = RandomEffectConfig(
        "e", "s", OptimizerConfig(max_iters=5, tolerance=0.0, reg=reg.l2(),
                                  reg_weight=L2),
        active_cap=CAP, projection=INDEX_MAP)
    est = GameEstimator(TASK, {"re": cfg}, n_sweeps=2)
    est.fit(data)  # builds the dataset, compiles
    table_bytes = est.datasets(data)["re"].n_entities * (FEATURES + 1) * 4

    def boom(*a, **k):
        raise AssertionError("the host block loop ran")

    monkeypatch.setattr(projector, "gather_rows", boom)
    monkeypatch.setattr(projector, "scatter_rows_into", boom)
    uploads = []
    real_put = jax.device_put

    def counting_put(x, *a, **k):
        for leaf in jax.tree_util.tree_leaves(x):
            if isinstance(leaf, np.ndarray):
                uploads.append(leaf.nbytes)
        return real_put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", counting_put)
    real_asarray = jnp.asarray

    def counting_asarray(x, *a, **k):
        if isinstance(x, np.ndarray):
            uploads.append(x.nbytes)
        return real_asarray(x, *a, **k)

    monkeypatch.setattr(jnp, "asarray", counting_asarray)
    (result,) = est.fit(data)
    # the only arrays that cross are the (n,) response / weight / offset
    # columns the descent is handed anew each fit
    assert uploads and max(uploads) <= prob["y"].nbytes
    assert max(uploads) < table_bytes
    assert isinstance(result.model["re"].coefficients, jax.Array)


# ------------------------------------------------------------ (iii) the plan
def _zipf_entities(rng, n_entities, cap):
    rows = np.minimum(rng.zipf(1.6, size=n_entities), cap)
    widths = np.minimum(8 + 6 * rows + rng.integers(0, 40, n_entities), 1200)
    return rows, widths


def test_bucket_plan_bytes_and_target():
    """On a zipf draw the planned blocks hold under 1.75 × the bytes the
    entities' own (rows × width) hold, once a shape must save 1/128 of the
    device to be kept; every entity is in exactly one bucket that fits it."""
    rng = np.random.default_rng(3)
    rows, widths = _zipf_entities(rng, 40000, 128)
    plan = plan_buckets(rows, widths, 4, width_classes=True,
                        device_bytes=4 << 30)
    assert plan.bytes_real == int((rows * widths).sum()) * 4
    assert plan.bytes_padded == sum(m * w * len(g) * 4
                                    for m, w, g in plan.buckets)
    assert plan.bytes_padded / plan.bytes_real <= 1.75
    assert len(plan.buckets) > 3  # bytes, not a fixed count, set the shapes
    seen = np.concatenate([g for _, _, g in plan.buckets])
    assert np.array_equal(np.sort(seen), np.arange(len(rows)))
    for m, w, g in plan.buckets:
        assert rows[g].max() <= m and widths[g].max() <= w
    # a caller's max_blocks is an upper limit that is met whatever it costs
    assert len(plan_buckets(rows, widths, 4, width_classes=True,
                            max_blocks=2, device_bytes=None).buckets) == 2
    # small problems keep the three shapes they always had
    small = plan_buckets(rows[:200], widths[:200], 4, width_classes=True,
                         device_bytes=16 << 30)
    assert len(small.buckets) <= 3


def test_built_blocks_hold_the_planned_bytes():
    prob = _problem(seed=4, n_entities=60, n=2500)
    data = _game_data(prob, sparse=True)
    with telemetry.run("plan") as run:
        ds = RandomEffectDataset.build(data, "e", "s", active_cap=64,
                                       projection=INDEX_MAP)
        counters = run.report_compact()["counters"]
    allocated = sum(int(np.prod(b.X.shape)) * 4 for b in ds.blocks)
    assert allocated == ds.block_bytes_padded
    assert counters["game_re.block_bytes_padded"] == ds.block_bytes_padded
    assert counters["game_re.block_bytes_real"] == ds.block_bytes_real
    assert ds.block_bytes_real <= ds.block_bytes_padded
    for b in ds.blocks:  # a bucket's width is its plan's, not a power of two
        assert b.X.shape == (b.n_entities, b.m, b.dim)
        assert b.proj.proj_mask.sum(axis=1).max() <= b.dim


def test_over_budget_plan_raises_before_allocating(monkeypatch):
    prob = _problem(seed=5)
    data = _game_data(prob, sparse=True)
    monkeypatch.setattr(game_dataset, "_device_memory_bytes", lambda: 4096)
    made = []
    monkeypatch.setattr(game_dataset, "_project_sparse",
                        lambda *a, **k: made.append(a))
    with pytest.raises(ValueError, match="largest bucket is .* entities x"):
        RandomEffectDataset.build(data, "e", "s", projection=INDEX_MAP)
    assert not made  # no block was built


# -------------------------------------------------- (iv) the training driver
def _write_avro(path, n, seed):
    from photon_tpu.data.avro_io import write_avro
    from photon_tpu.data.ingest import training_example_schema

    rng = np.random.default_rng(seed)
    users = 12
    user = rng.integers(0, users, n)
    x = rng.normal(size=(n, 2))
    jobf = rng.integers(0, 20, size=(n, 3))
    jobv = rng.normal(size=(n, 3))
    truth = np.random.default_rng(99).normal(size=(users, 20))
    margin = 0.8 * x[:, 0] - 0.5 * x[:, 1] + np.einsum(
        "nk,nk->n", jobv, truth[user[:, None], jobf])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    schema = training_example_schema(feature_bags=("global", "jobf"),
                                     entity_fields=("userId",))
    write_avro(path, [{
        "response": float(y[i]), "offset": None, "weight": None,
        "uid": f"row{i}", "userId": f"u{user[i]:02d}",
        "global": [{"name": f"x{j}", "term": "", "value": float(x[i, j])}
                   for j in range(2)],
        "jobf": [{"name": f"f{jobf[i, j]:02d}", "term": "",
                  "value": float(jobv[i, j])} for j in range(3)],
    } for i in range(n)], schema)


def test_driver_json_expresses_index_map_projection(tmp_path, capsys):
    """The driver's JSON carries `projection` per coordinate, trains, saves
    and reloads a model whose validation AUC is the library call's."""
    from photon_tpu.data.feature_bags import FeatureShardConfig
    from photon_tpu.data.ingest import GameDataConfig, read_game_data
    from photon_tpu.drivers import ScoringParams, run_scoring
    from photon_tpu.drivers.train import CoordinateSpec, main
    from photon_tpu.game.estimator import FixedEffectConfig

    _write_avro(tmp_path / "train.avro", 700, seed=1)
    _write_avro(tmp_path / "val.avro", 300, seed=2)
    shards = {"fixedShard": {"bags": ["global"], "has_intercept": True},
              "jobShard": {"bags": ["jobf"], "has_intercept": True}}
    coordinates = {
        "fixed": {"feature_shard": "fixedShard", "reg_type": "l2",
                  "reg_weight": 0.5, "max_iters": 40},
        "perUser": {"feature_shard": "jobShard", "entity_name": "userId",
                    "reg_type": "l2", "reg_weight": 2.0, "max_iters": 30,
                    "active_cap": 48, "projection": "index_map"}}
    job = {"train_path": str(tmp_path / "train.avro"),
           "validation_path": str(tmp_path / "val.avro"),
           "output_dir": str(tmp_path / "out"), "feature_shards": shards,
           "coordinates": coordinates, "entity_fields": ["userId"],
           "n_sweeps": 2, "sparse_k": 4}
    (tmp_path / "job.json").write_text(json.dumps(job))
    main(["--config", str(tmp_path / "job.json")])
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["validation_score"] > 0.6

    spec = CoordinateSpec(**coordinates["perUser"]).coordinate_config()
    assert spec.projection == INDEX_MAP and spec.active_cap == 48
    with pytest.raises(ValueError, match="unknown projection"):
        CoordinateSpec(feature_shard="s", entity_name="e",
                       projection="hashing").coordinate_config()
    assert CoordinateSpec(
        feature_shard="s", entity_name="e", projection={"random": 8}
    ).coordinate_config().projection == ProjectionConfig(
        ProjectorType.RANDOM, projected_dim=8)

    # the saved model, reloaded by the scoring driver
    scored = run_scoring(ScoringParams(
        model_dir=said["model_dir"], data_path=str(tmp_path / "val.avro"),
        output_dir=str(tmp_path / "scored"), feature_shards=shards,
        entity_fields=["userId"], sparse_k=4))
    assert scored.metric == pytest.approx(said["validation_score"], abs=1e-6)

    # the library call on the same files
    typed = {k: FeatureShardConfig(bags=tuple(v["bags"]),
                                   has_intercept=v["has_intercept"])
             for k, v in shards.items()}
    conf = GameDataConfig(shards=typed, entity_fields=("userId",))
    train, maps = read_game_data(str(tmp_path / "train.avro"), conf,
                                 sparse_k=4)
    val, _ = read_game_data(str(tmp_path / "val.avro"), conf,
                            index_maps=maps, sparse_k=4)
    est = GameEstimator(TASK, {
        "fixed": FixedEffectConfig("fixedShard", CoordinateSpec(
            **coordinates["fixed"]).optimizer_config()),
        "perUser": spec}, n_sweeps=2)
    (lib,) = est.fit(train, val)
    assert lib.validation_score == pytest.approx(said["validation_score"],
                                                 abs=1e-5)
    assert est.datasets(train)["perUser"].blocks[0].proj is not None


# ------------------------------------------------------- scopes and counters
def test_descent_scopes_reach_the_compiled_updates():
    """The phases of a coordinate update are named in the compiled
    programs' op metadata, the solver's own scopes nested under the solve."""
    import re

    from photon_tpu.game.coordinate_descent import _contract_game_fixed_update

    prob = _problem(seed=6)
    data = _game_data(prob, sparse=True)
    ds = RandomEffectDataset.build(data, "e", "s", active_cap=CAP,
                                   projection=INDEX_MAP)
    coord = RandomEffectCoordinate(ds, TASK, OptimizerConfig(
        max_iters=3, tolerance=0.0, reg=reg.l2(), reg_weight=L2))
    fn, blocks_args, plan, objs, lam = coord.fused_update_program()
    n = data.n
    zeros = jnp.zeros((n,), jnp.float32)
    text = fn.lower(jnp.zeros((ds.n_entities, ds.dim), jnp.float32),
                    cold_warm_starts(blocks_args, ds.dim), zeros,
                    (zeros,), objs, lam, blocks_args, plan, zeros,
                    zeros).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("game_re.gather", "game_re.solve", "game_re.scatter",
                  "game_re.score", "game.objective"):
        assert any(scope in name.split("/") for name in names), scope
    nested = [name for name in names if "lbfgs.two_loop" in name]
    assert nested and all("game_re.solve" in name for name in nested)
    fixed_fn, fixed_args = _contract_game_fixed_update()
    text = jax.jit(fixed_fn).lower(*fixed_args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("game_fixed.solve" in name.split("/") for name in names)


def test_descent_reports_rows_times_iterations():
    """`game_re.row_iterations` / `game_fixed.row_iterations` count rows ×
    iterations TAKEN, as device values read with the report."""
    from photon_tpu.game.estimator import FixedEffectConfig

    prob = _problem(seed=7)
    X = SparseRows(prob["ind"], prob["val"], FEATURES + 1)
    data = GameData.build(prob["y"], {"s": X, "f": prob["dense"][:, :4]},
                          {"e": prob["ent"]})
    opt = OptimizerConfig(max_iters=4, tolerance=0.0, reg=reg.l2(),
                          reg_weight=L2)
    est = GameEstimator(TASK, {
        "fixed": FixedEffectConfig("f", opt),
        "re": RandomEffectConfig("e", "s", opt, active_cap=CAP,
                                 projection=INDEX_MAP)}, n_sweeps=2)
    with telemetry.run("work") as run:
        (result,) = est.fit(data)
        counters = run.report_compact()["counters"]
    stats = result.descent.coordinate_stats
    fixed_iters = sum(int(s.iterations) for s in stats["fixed"])
    assert counters["game_fixed.row_iterations"] == data.n * fixed_iters
    assert counters["game_re.row_iterations"] == pytest.approx(
        sum(s.row_iterations for s in stats["re"]))
    ds = est.datasets(data)["re"]
    rows = np.minimum(np.bincount(prob["ent"]), CAP)
    assert 0 < stats["re"][0].row_iterations <= rows.sum() * 4
    assert counters["game_re.block_steps"] <= 4 * 2 * len(ds.blocks)


# ------------------------------------------ the cell's comparison and its faults
@pytest.fixture(scope="module")
def descent_cell(tmp_path_factory):
    """`glmix-wide.descent` at its rehearse sizes: (traffic module, state,
    the warm-up fit's evidence), as `benchmark/run.py` builds them."""
    import os

    from benchmark.traffic import game_descent

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", "glmix-wide.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "workloads",
                           "glmix-wide.descent.json")) as f:
        params = json.load(f)["params"]
    config = {**config, **config["rehearse"]}
    state = game_descent.setup(
        config, params, 2147483659,
        {"shared": str(tmp_path_factory.mktemp("pattern"))})
    evidence = game_descent.unit(state, keep=True)["evidence"]
    return game_descent, state, evidence


def _fault_none(state, evidence):
    return state, evidence


def _fault_zero_member_table(state, evidence):
    """The per-member coordinate never written: its table left at zero."""
    tables = dict(evidence["tables"])
    tables["per_user"] = np.zeros_like(tables["per_user"])
    return state, {**evidence, "tables": tables}


def _fault_skipped_sweep(state, evidence):
    """One sweep instead of two: half the updates, half the history."""
    return state, {**evidence, "history": evidence["history"][:3]}


def _fault_skipped_cap(state, evidence):
    """The fit kept more rows than the configuration's bound allows (the
    configuration asks for 16 a member; the fit was built with 128)."""
    import copy
    import dataclasses

    config = copy.deepcopy(state.config)
    config["coordinates"]["per_user"]["active_cap"] = 16
    return dataclasses.replace(state, config=config), evidence


def _fault_low_precision_values(state, evidence):
    """Solves whose own objectives are off by a bf16 product's error."""
    values = {n: v * np.float32(1.0 + 2.0 ** -9)
              for n, v in evidence["values"].items()}
    return state, {**evidence, "values": values}


@pytest.mark.parametrize("fault,refused_by", [
    (_fault_none, None),
    (_fault_zero_member_table, "per_user.gap"),
    (_fault_skipped_sweep, "objective"),
    (_fault_skipped_cap, "per_user.cap_errors"),
    (_fault_low_precision_values, "per_item.value_rel"),
], ids=["sound", "zero_table", "skipped_sweep", "skipped_cap",
        "low_precision"])
def test_cell_comparison_refuses_planted_faults(descent_cell, fault,
                                                refused_by):
    """`game_descent.check` passes the sound fit and refuses each planted
    fault by the limit that is there for it; in every run its own controls
    — the reference at bf16 in the fit's place, each table left at zero —
    are refused too."""
    traffic, state, evidence = descent_cell
    verdict = traffic.check(*fault(state, evidence))
    assert verdict["controls_refused"]
    assert "per_item.value_rel" in verdict["controls"]["bf16"]["refused_by"]
    assert "per_user.gap" in verdict["controls"][
        "zero_table.per_user"]["refused_by"]
    if refused_by is None:
        assert verdict["ok"] and not verdict["refused_by"]
    else:
        assert not verdict["ok"]
        assert refused_by in verdict["refused_by"]


@pytest.mark.parametrize("start", ["cold", "warm_started"])
def test_cell_fit_hands_every_update_what_its_table_holds(descent_cell, start,
                                                          monkeypatch):
    """At the cell's rehearse sizes: every one-dispatch update of a fit is
    handed, as its buckets' warm starts, bit for bit what a gather out of
    the table it is handed beside them reads through the index maps (the
    rule every update followed until the solutions were carried), and a
    table that is 0 outside those maps — so the carried solve is that
    solve. A cold fit repeats the warm-up fit's bits."""
    _, state, evidence = descent_cell
    plain = RandomEffectCoordinate.fused_update_program
    handed = []

    def checking(self):
        fn, blocks_args, *rest = plain(self)

        def call(table, warm, *args):
            left = table
            for (_, ents, cols, _), w0 in zip(blocks_args, warm):
                np.testing.assert_array_equal(
                    np.asarray(table.at[ents[:, None], cols].get(
                        mode="fill", fill_value=0)), np.asarray(w0))
                left = left.at[ents[:, None], cols].set(0.0, mode="drop")
            assert not np.any(np.asarray(left))  # nothing outside a map
            handed.append(bool(np.any(np.asarray(table))))
            return fn(table, warm, *args)

        return (call, blocks_args, *rest)

    monkeypatch.setattr(RandomEffectCoordinate, "fused_update_program",
                        checking)
    initial = None
    if start == "warm_started":
        (first,) = state.estimator.fit(state.data)
        initial = dict(first.model.coordinates)
        handed.clear()
    (result,) = state.estimator.fit(state.data, initial_models=initial)
    # 2 sweeps x 2 random-effect coordinates; a cold fit's first two
    # updates start from tables of zeros
    assert handed == ([False, False, True, True] if start == "cold"
                      else [True] * 4)
    if start == "cold":
        for name, table in evidence["tables"].items():
            np.testing.assert_array_equal(
                np.asarray(result.model[name].coefficients), table)
        assert [float(v) for v in result.descent.objective_history] \
            == evidence["history"]
