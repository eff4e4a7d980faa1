"""The one-dispatch random-effect update scores a row from where its
features lie (PR 30): a row a bucket holds from the bucket's block times the
bucket's fresh solution, every other row from the updated (E, d) table, one
(n,) gather laying both over the rows — against a float64 per-row product
with the returned table, the table scorer, and the block-loop path.
"""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import telemetry
from photon_tpu.data.matrix import SparseRows
from photon_tpu.game.coordinate_descent import (_objective_at,
                                                coordinate_descent)
from photon_tpu.game.dataset import GameData, RandomEffectDataset
from photon_tpu.game.estimator import GameEstimator, RandomEffectConfig
from photon_tpu.game.model import score_entities
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
N_ENTITIES, N = 30, 900


def _problem(seed=0):
    """Sparse rows with a zipf entity skew (the cap bites, buckets differ),
    one slot of every row naming a feature twice, the intercept last."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, N_ENTITIES + 1, dtype=np.float64) ** -1.0
    ent = rng.choice(N_ENTITIES, size=N, p=p / p.sum()).astype(np.int32)
    ind = rng.integers(0, FEATURES, size=(N, NNZ)).astype(np.int32)
    ind[:, 1] = ind[:, 0]
    val = rng.normal(size=(N, NNZ)).astype(np.float32)
    ind = np.concatenate([ind, np.full((N, 1), FEATURES, np.int32)], axis=1)
    val = np.concatenate([val, np.ones((N, 1), np.float32)], axis=1)
    dense = np.zeros((N, FEATURES + 1), np.float32)
    np.add.at(dense, (np.arange(N)[:, None], ind), val)
    truth = rng.normal(size=(N_ENTITIES, FEATURES + 1))
    margin = np.einsum("nd,nd->n", dense, truth[ent]) * 0.5
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return {"ent": ent, "ind": ind, "val": val, "dense": dense, "y": y,
            "offsets": rng.normal(size=N).astype(np.float32) * 0.3,
            "weights": np.ones(N, np.float32)}


def _zero_weights_under_the_cap(prob):
    """Every third row of the entities that stay under the cap loses its
    weight: still an active row, held and scored by its block."""
    counts = np.bincount(prob["ent"], minlength=N_ENTITIES)
    small = np.nonzero((counts[prob["ent"]] < CAP))[0]
    prob["weights"][small[::3]] = 0.0
    assert small[::3].size > 5


def _weightless_entity(prob):
    """One entity whose rows all carry weight 0: dropped from training, its
    rows keep dense id E and score 0."""
    prob["weights"][prob["ent"] == 3] = 0.0


# name -> (sparse shard, projection, cap, what it does to the problem)
CASES = {
    "sparse_index_map_cap": (True, INDEX_MAP, CAP, None),
    "dense_unprojected_cap": (False, None, CAP, None),
    "sparse_unprojected_cap": (True, None, CAP, None),
    "no_cap": (True, INDEX_MAP, None, None),
    "zero_weight_rows_under_cap": (True, INDEX_MAP, CAP,
                                   _zero_weights_under_the_cap),
    "weightless_entity": (True, INDEX_MAP, CAP, _weightless_entity),
}


def _case(name, seed=0):
    sparse, projection, cap, fault = CASES[name]
    prob = _problem(seed)
    if fault is not None:
        fault(prob)
    X = (SparseRows(prob["ind"], prob["val"], FEATURES + 1) if sparse
         else prob["dense"])
    data = GameData.build(prob["y"], {"s": X}, {"e": prob["ent"]},
                          weights=prob["weights"], offsets=prob["offsets"])
    ds = RandomEffectDataset.build(data, "e", "s", active_cap=cap,
                                   projection=projection)
    return prob, data, ds


def _held_slots(ds):
    """The real (non-padding) slots of the concatenated flattened blocks,
    the original row each holds, and the concatenation's length."""
    slots, rows, base = [], [], 0
    for block in ds.blocks:
        held = (np.arange(block.m)[None, :]
                < block.active_rows[:, None]).reshape(-1)
        assert np.array_equal(block.held.reshape(-1), held)
        slots.append(base + np.nonzero(held)[0])
        rows.append(np.asarray(block.row_index).reshape(-1)[held])
        base += held.shape[0]
    return np.concatenate(slots), np.concatenate(rows), base


def _update(ds, data, iters=6):
    """One one-dispatch update from a zero table → (table, margins)."""
    coord = RandomEffectCoordinate(ds, TASK, OptimizerConfig(
        max_iters=iters, tolerance=0.0, reg=reg.l2(), reg_weight=L2))
    fn, blocks_args, plan, objs, lam = coord.fused_update_program()
    zeros = jnp.zeros((data.n,), jnp.float32)
    out = fn(jnp.zeros((ds.n_entities, ds.dim), jnp.float32),
             cold_warm_starts(blocks_args, ds.dim),
             jnp.asarray(data.offsets), (zeros,), objs, lam, blocks_args,
             plan, jnp.asarray(data.y), jnp.asarray(data.weights))
    return out[0], out[2]


# ------------------------------------------------- (a) the margins themselves
@pytest.mark.parametrize("name", list(CASES))
def test_update_margins_against_float64_and_the_table_scorer(name):
    prob, data, ds = _case(name)
    table, margins = _update(ds, data)
    table64 = np.concatenate([np.asarray(table, np.float64),
                              np.zeros((1, ds.dim))])
    assert np.abs(table64).max() > 0.1  # the solves moved
    ids = ds.entity_dense
    want = np.einsum("nd,nd->n", prob["dense"].astype(np.float64),
                     table64[ids])
    # an f32 sum of at most FEATURES + 1 products a row, in either order:
    # 1e-6 of the row's Σ|x·w| (a margin near 0 is a cancellation)
    scale = np.einsum("nd,nd->n", np.abs(prob["dense"]).astype(np.float64),
                      np.abs(table64[ids]))
    got = np.asarray(margins, np.float64)
    assert np.all(np.abs(got - want) <= 1e-6 * scale)
    scored = np.asarray(score_entities(ds.X, table, jnp.asarray(ids),
                                       exact=True), np.float64)
    assert np.all(np.abs(got - scored) <= 1e-6 * scale)
    # rows of a dropped entity (dense id E) score exactly 0
    assert np.all(got[ids == ds.n_entities] == 0.0)
    if name == "weightless_entity":
        assert np.count_nonzero(ids == ds.n_entities) > 5


# ------------------------------------------------------------- (b) the plan
@pytest.mark.parametrize("name", list(CASES))
def test_slot_of_row_is_a_bijection_onto_real_slots_and_passive_ranks(name):
    prob, data, ds = _case(name)
    plan = ds.scoring_plan
    slot = np.asarray(plan.slot_of_row)
    slots, rows, base = _held_slots(ds)
    assert slot.shape == (data.n,) and slot.dtype == np.int32
    assert plan.n_table_rows == ds.n_passive
    assert plan.n_block_rows == ds.n_active == slots.size
    # onto: every real slot and every passive rank is named exactly once,
    # and nothing else — no padding slot — is
    targets = np.concatenate([slots, base + np.arange(ds.n_passive)])
    assert np.array_equal(np.sort(slot), np.sort(targets))
    # a held row points at the slot that holds IT
    assert np.array_equal(slot[rows], slots)
    # the passive sub-shard is those rows of the flat shard, in rank order
    passive = np.nonzero(slot >= base)[0]
    assert np.array_equal(slot[passive], base + np.arange(passive.size))
    assert np.array_equal(np.asarray(plan.ids_passive),
                          ds.entity_dense[passive])
    if isinstance(ds.X, SparseRows):
        assert np.array_equal(np.asarray(plan.X_passive.indices),
                              prob["ind"][passive])
        assert np.array_equal(np.asarray(plan.X_passive.values),
                              prob["val"][passive])
    else:
        assert np.array_equal(np.asarray(plan.X_passive),
                              prob["dense"][passive])
    cap = CASES[name][2]
    counts = np.bincount(prob["ent"], minlength=N_ENTITIES)
    if cap is None:
        assert ds.n_passive == 0 and plan.X_passive.shape[0] == 0
    elif name == "weightless_entity":
        live = np.delete(counts, 3)
        assert ds.n_passive == (np.maximum(live - cap, 0).sum() + counts[3])
    else:
        assert ds.n_passive == np.maximum(counts - cap, 0).sum() > 0
    # a weight-0 row under its entity's cap is held by a block
    if name == "zero_weight_rows_under_cap":
        weightless = np.nonzero(prob["weights"] == 0.0)[0]
        assert weightless.size and np.all(slot[weightless] < base)


# --------------------------------------------------- (c) the compiled update
@pytest.mark.parametrize("name", ["sparse_index_map_cap", "no_cap"])
def test_compiled_update_gathers_the_table_for_passive_rows_only(name):
    """Under `game_re.score` the table gather produces n_passive × k
    elements (none with no passive row), the reassembly one (n,) gather,
    and the buckets' forward passes nest there."""
    _, data, ds = _case(name)
    coord = RandomEffectCoordinate(ds, TASK, OptimizerConfig(
        max_iters=3, tolerance=0.0, reg=reg.l2(), reg_weight=L2))
    fn, blocks_args, plan, objs, lam = coord.fused_update_program()
    zeros = jnp.zeros((data.n,), jnp.float32)
    text = fn.lower(jnp.zeros((ds.n_entities, ds.dim), jnp.float32),
                    cold_warm_starts(blocks_args, ds.dim), zeros,
                    (zeros,), objs, lam, blocks_args, plan, zeros,
                    zeros).compile().as_text()
    k = NNZ + 1
    gathers = set()
    for line in text.splitlines():
        shape = re.search(r"= f32\[([\d,]*)\]\S* gather\(", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if shape and op_name and "game_re.score" in op_name.group(1):
            gathers.add(int(np.prod([int(s) for s in
                                     shape.group(1).split(",")])))
    # elements gathered: the reassembly's n, the passive rows' k each — and
    # never the whole shard's n × k
    assert gathers == ({data.n, ds.n_passive * k} if ds.n_passive
                       else {data.n})
    names = re.findall(r'op_name="([^"]*)"', text)
    fwd = [nm for nm in names if "xpass.fwd" in nm and "game_re.score" in nm]
    assert fwd and all(nm.index("game_re.score") < nm.index("xpass.fwd")
                       for nm in fwd)
    # and the solves' own passes stay under the solve, not under the score
    solve = [nm for nm in names if "game_re.solve" in nm and "xpass." in nm]
    assert solve and not any("game_re.score" in nm for nm in solve)


# ------------------------------------------------------------ (d) the counters
@pytest.mark.parametrize("cap", [None, CAP], ids=["nocap", "cap"])
def test_scored_row_counters_sum_to_rows_times_updates(cap):
    prob = _problem(seed=3)
    X = SparseRows(prob["ind"], prob["val"], FEATURES + 1)
    data = GameData.build(prob["y"], {"s": X},
                          {"e": prob["ent"], "g": prob["ent"] % 7})
    opt = OptimizerConfig(max_iters=3, tolerance=0.0, reg=reg.l2(),
                          reg_weight=L2)
    est = GameEstimator(TASK, {
        "a": RandomEffectConfig("e", "s", opt, active_cap=cap,
                                projection=INDEX_MAP),
        "b": RandomEffectConfig("g", "s", opt, active_cap=cap)}, n_sweeps=2)
    with telemetry.run("scored") as run:
        est.fit(data)
        counters = run.report_compact()["counters"]
    datasets = est.datasets(data)
    held = sum(ds.n_active for ds in datasets.values())
    block = counters.get("game_re.block_scored_rows", 0)
    table = counters.get("game_re.table_scored_rows", 0)
    assert block + table == data.n * 4  # 2 coordinates × 2 sweeps
    assert block == 2 * held
    assert (table == 0) == (cap is None)


# ------------------------------- (e) a descent trained on block-scored margins
def _two_coordinates(sparse=True, iters=6, seed=4):
    """Two random-effect coordinates over one shard, each with passive
    rows: "a" through INDEX_MAP buckets, "b" unprojected."""
    prob = _problem(seed=seed)
    X = (SparseRows(prob["ind"], prob["val"], FEATURES + 1) if sparse
         else prob["dense"])
    data = GameData.build(prob["y"], {"s": X},
                          {"e": prob["ent"], "g": (prob["ent"] * 5) % 11},
                          offsets=prob["offsets"])
    cfg = OptimizerConfig(max_iters=iters, tolerance=0.0, reg=reg.l2(),
                          reg_weight=L2)
    coords = {
        "a": RandomEffectCoordinate(RandomEffectDataset.build(
            data, "e", "s", active_cap=CAP, projection=INDEX_MAP), TASK, cfg),
        "b": RandomEffectCoordinate(RandomEffectDataset.build(
            data, "g", "s", active_cap=4 * CAP), TASK, cfg)}
    assert all(c.dataset.n_passive > 0 for c in coords.values())
    return prob, data, coords


def _recorded(coords):
    """Wrap every coordinate's one-dispatch program: the list this returns
    fills with what each update of a descent returned, in order, copied to
    the host before the next update is handed (and overwrites) the same
    buffers."""
    seen = []

    def recording(name, fn):
        def call(*args):
            out = fn(*args)
            seen.append({
                "name": name, "table": np.array(out[0]),
                "margins": np.array(out[2]), "objective": np.array(out[3]),
                "values": np.array(out[5]),
                "carried": [np.array(w) for w in out[6]]})
            return out
        return call

    for name, coord in coords.items():
        fn, *rest = coord.fused_update_program()
        coord._fused_cache = (recording(name, fn), *rest)
    return seen


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_two_coordinate_descent_matches_the_block_loop(sparse):
    """Each coordinate trains against the other's margins as offsets: the
    one-dispatch descent (block-scored) and the block loop's `train` +
    `score` (table-scored) agree on every update's margins and objective
    within what `test_projected_one_dispatch_update_matches_block_loop`
    grants the pair of solvers."""
    prob, data, coords = _two_coordinates(sparse, iters=25)
    updates = _recorded(coords)
    out = coordinate_descent(coords, data.y, data.weights, data.offsets,
                             TASK, n_sweeps=2)
    # (table, margins) each one-dispatch update returned, in order
    seen = [(u["table"], u["margins"]) for u in updates]

    y, weights = jnp.asarray(data.y), jnp.asarray(data.weights)
    base = jnp.asarray(data.offsets)
    scores = {name: jnp.zeros((data.n,), jnp.float32) for name in coords}
    models = {name: None for name in coords}
    loop, history = [], []
    for _ in range(2):
        for name, coord in coords.items():
            offsets = base + sum(s for other, s in scores.items()
                                 if other != name)
            models[name], _ = coord.train(offsets, warm_start=models[name])
            scores[name] = coord.score(models[name])
            loop.append((np.asarray(models[name].coefficients),
                         np.asarray(scores[name])))
            history.append(float(_objective_at(TASK, y, weights, offsets,
                                               scores[name])))
    assert len(seen) == len(loop) == 4
    dense64 = prob["dense"].astype(np.float64)
    for at, ((table, got), (table_loop, want), name) in enumerate(
            zip(seen, loop, 2 * list(coords))):
        assert np.abs(want).max() > 0.5
        # every update's margins are its own returned table's, warm-started
        # tables (zero outside a bucket's map) included
        ids = coords[name].dataset.entity_dense
        rows = np.concatenate([table.astype(np.float64),
                               np.zeros((1, table.shape[1]))])[ids]
        exact = np.einsum("nd,nd->n", dense64, rows)
        scale = np.einsum("nd,nd->n", np.abs(dense64), np.abs(rows))
        assert np.all(np.abs(got - exact) <= 1e-6 * scale)
        # The first update solves the same problems on both paths: 1e-4, as
        # granted. A later one trains against margins that differ in their
        # last bit, and an f32 L-BFGS solve run past its stall answers that
        # at its own resolution — the 2e-3 of a coefficient that
        # `test_fit_agrees_with_plain_reference` grants it (the table-scored
        # parent reads 7e-4 between its two paths on this very problem).
        tol = 1e-4 if at == 0 else 2e-3
        np.testing.assert_allclose(table, table_loop, rtol=tol, atol=tol)
        grant = tol * np.einsum("nd,nd->n", np.abs(dense64), 1.0 + np.abs(rows))
        assert np.all(np.abs(got - want) <= grant)
    # the tracked loss moves 1e-5 with the solver's wander
    np.testing.assert_allclose(
        [float(h) for h in out.objective_history], history, rtol=1e-4)


# ----------- (f) a bucket's solution is carried to its next update (PR 37)
def _parents_rule(table, blocks_args):
    """What every update did with the table it was given until the buckets'
    solutions were carried: each bucket's warm starts gathered through its
    index map (a padding column reads 0), the rows of a projected bucket
    cleared before its solution is written over them."""
    warm = []
    for _, ents, cols, _ in blocks_args:
        at = (ents,) if cols is None else (ents[:, None], cols)
        warm.append(table.at[at].get(mode="fill", fill_value=0))
        if cols is not None:
            table = table.at[ents].set(0.0)
    return table, tuple(warm)


def _descent_by_the_parents_rule(data, coords, programs, initial=None,
                                 n_sweeps=2):
    """The descent's updates, each through the coordinate's own one-dispatch
    program, with every update's warm starts read back out of the (E, d)
    table → what each update returned, in order."""
    y, weights = jnp.asarray(data.y), jnp.asarray(data.weights)
    base = jnp.asarray(data.offsets)
    zeros = jnp.zeros((data.n,), jnp.float32)
    tables, scores = {}, {}
    for name, coord in coords.items():
        ds = coord.dataset
        tables[name] = jnp.zeros((ds.n_entities, ds.dim), jnp.float32)
        if initial is not None:
            tables[name] = jnp.array(initial[name].coefficients, jnp.float32)
            scores[name] = coord.score(initial[name])
    updates = []
    for _ in range(n_sweeps):
        for name in coords:
            fn, blocks_args, plan, objs, lam = programs[name]
            (other,) = [scores.get(o, zeros) for o in coords if o != name]
            table, warm = _parents_rule(tables[name], blocks_args)
            out = fn(table, warm, base, (other,), objs, lam, blocks_args,
                     plan, y, weights)
            tables[name], scores[name] = out[0], out[2]
            updates.append({"name": name, "table": np.array(out[0]),
                            "margins": np.array(out[2]),
                            "objective": np.array(out[3]),
                            "values": np.array(out[5])})
    return updates


def _assert_same_bits(got, want):
    assert [u["name"] for u in got] == [u["name"] for u in want]
    for at, (g, w) in enumerate(zip(got, want)):
        for key in ("table", "margins", "objective", "values"):
            np.testing.assert_array_equal(
                g[key], w[key], err_msg=f"update {at} ({g['name']}): {key}")


def _random_models(coords, seed=11):
    """A caller's model a coordinate: nonzeros in EVERY column, so also
    outside the entities' index maps."""
    from photon_tpu.game.model import RandomEffectModel

    rng = np.random.default_rng(seed)
    models = {}
    for name, coord in coords.items():
        ds = coord.dataset
        table = rng.normal(size=(ds.n_entities, ds.dim)).astype(np.float32)
        assert np.all(table != 0.0)
        models[name] = RandomEffectModel(
            entity_name=ds.entity_name, feature_shard=ds.shard_name,
            task=TASK, coefficients=jnp.asarray(table),
            entity_keys=ds.entity_keys, key_to_index=ds.key_to_index)
    return models


def _inside_the_maps(ds):
    """(E, d) bool: the columns an entity's index map names."""
    inside = np.zeros((ds.n_entities, ds.dim), bool)
    for block in ds.blocks:
        ents = np.asarray(block.entity_index)
        real = np.asarray(block.proj.proj_mask) > 0
        idx = np.asarray(block.proj.proj_idx)
        rows = np.broadcast_to(ents[:, None], idx.shape)
        inside[rows[real], idx[real]] = True
    return inside


@pytest.mark.parametrize("start", ["cold", "warm_started"])
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_carried_descent_has_the_bits_of_warm_starts_read_from_the_table(
        sparse, start):
    """A 2-sweep descent whose updates are handed the previous update's
    solutions returns, update by update, the tables, margins, objectives and
    per-entity values of one that gathers every warm start out of the table
    — from zeros, and from the model of an earlier fit (a grid's previous
    point)."""
    _, data, coords = _two_coordinates(sparse)
    programs = {name: c.fused_update_program() for name, c in coords.items()}
    initial = None
    if start == "warm_started":
        initial = coordinate_descent(
            coords, data.y, data.weights, data.offsets, TASK,
            n_sweeps=1).model.coordinates
    seen = _recorded(coords)
    out = coordinate_descent(coords, data.y, data.weights, data.offsets,
                             TASK, n_sweeps=2, initial_models=initial)
    want = _descent_by_the_parents_rule(data, coords, programs, initial)
    assert len(seen) == 4 and np.abs(want[-1]["table"]).max() > 0.1
    _assert_same_bits(seen, want)
    # and what the descent hands back is what its updates returned
    for name in coords:
        last = [u for u in want if u["name"] == name]
        np.testing.assert_array_equal(
            np.asarray(out.model[name].coefficients), last[-1]["table"])
        for stats, u in zip(out.coordinate_stats[name], last):
            np.testing.assert_array_equal(
                np.asarray(stats.entity_values), u["values"])
    assert out.objective_history == [float(u["objective"]) for u in want]


def test_callers_table_is_adopted_with_zeros_outside_the_maps():
    """A caller's table with nonzeros outside the index maps: the descent
    solves from the mapped columns only, hands back 0 in every other
    column, the same bits as the parent's gather-and-clear gives, and
    leaves the caller's own arrays as they were."""
    _, data, coords = _two_coordinates()
    programs = {name: c.fused_update_program() for name, c in coords.items()}
    initial = _random_models(coords)
    kept = {name: np.array(m.coefficients) for name, m in initial.items()}
    seen = _recorded(coords)
    out = coordinate_descent(coords, data.y, data.weights, data.offsets,
                             TASK, n_sweeps=2, initial_models=initial)
    _assert_same_bits(seen, _descent_by_the_parents_rule(
        data, coords, programs, initial))
    inside = _inside_the_maps(coords["a"].dataset)
    table = np.asarray(out.model["a"].coefficients)
    assert 0 < inside.sum() < inside.size
    assert np.all(table[~inside] == 0.0) and np.all(table[inside] != 0.0)
    for name, m in initial.items():  # the copy was donated, not the model
        np.testing.assert_array_equal(np.asarray(m.coefficients), kept[name])


def test_adopt_table_reads_the_maps_and_clears_the_rest():
    from photon_tpu.game.random_effect import adopt_table

    _, _, coords = _two_coordinates()
    initial = _random_models(coords)
    for name, coord in coords.items():
        _, blocks_args, *_ = coord.fused_update_program()
        given = np.array(initial[name].coefficients)
        _, want = _parents_rule(jnp.asarray(given), blocks_args)
        table, warm = adopt_table(jnp.asarray(given), blocks_args)
        for got, w in zip(warm, want):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
        keep = (_inside_the_maps(coord.dataset) if name == "a"
                else np.ones(given.shape, bool))
        np.testing.assert_array_equal(np.asarray(table),
                                      np.where(keep, given, 0.0))


@pytest.mark.parametrize("start", ["cold", "adopted"])
def test_padding_columns_of_a_carried_solution_are_exactly_zero(start):
    """A bucket's padding column has no feature and an L2 gradient of 0 at
    0: the solver never moves it, so a carried solution reads there what
    the table gather filled in."""
    _, data, coords = _two_coordinates()
    initial = _random_models(coords) if start == "adopted" else None
    seen = _recorded(coords)
    coordinate_descent(coords, data.y, data.weights, data.offsets, TASK,
                       n_sweeps=2, initial_models=initial)
    blocks = coords["a"].dataset.blocks
    padded = 0
    for update in (u for u in seen if u["name"] == "a"):
        assert len(update["carried"]) == len(blocks)
        for block, w in zip(blocks, update["carried"]):
            pad = np.asarray(block.proj.proj_mask) == 0
            assert w.shape == pad.shape and np.abs(w[~pad]).max() > 0.0
            assert np.all(w[pad] == 0.0)
            padded += int(pad.sum())
    assert padded > 0


@pytest.mark.parametrize("start, want", [("cold", (2, 2, 0)),
                                         ("warm_started", (2, 0, 2))])
def test_warm_start_counters_say_where_the_warm_starts_came_from(start,
                                                                 want):
    _, data, coords = _two_coordinates(iters=3)
    initial = None
    if start == "warm_started":
        initial = coordinate_descent(
            coords, data.y, data.weights, data.offsets, TASK,
            n_sweeps=1).model.coordinates
    with telemetry.run("warm") as run:
        coordinate_descent(coords, data.y, data.weights, data.offsets, TASK,
                           n_sweeps=2, initial_models=initial)
        counters = run.report_compact()["counters"]
    assert tuple(counters.get(f"game_re.warm_{kind}", 0)
                 for kind in ("carried", "cold", "adopted")) == want
    assert counters["game.coordinate_updates"] == 4


def test_update_program_is_traced_once_across_sweeps_and_starts():
    """Cold zeros, the carried solutions and an adopted table's warm starts
    reach the update as the same avals: ONE compiled program a coordinate
    for both sweeps of a cold descent and of a warm-started one."""
    _, data, coords = _two_coordinates(iters=7)  # a solver no other test has
    fns = {name: c.fused_update_program()[0] for name, c in coords.items()}
    assert all(fn._cache_size() == 0 for fn in fns.values())
    out = coordinate_descent(coords, data.y, data.weights, data.offsets,
                             TASK, n_sweeps=2)
    assert all(fn._cache_size() == 1 for fn in fns.values())
    coordinate_descent(coords, data.y, data.weights, data.offsets, TASK,
                       n_sweeps=2, initial_models=out.model.coordinates)
    assert all(fn._cache_size() == 1 for fn in fns.values())


def test_restored_descent_finishes_with_the_uninterrupted_bits(tmp_path):
    """Killed after its third update and restored, the descent adopts the
    restored tables where the uninterrupted run carried its solutions: the
    same tables and objective history, bit for bit."""
    from photon_tpu import checkpoint

    _, data, coords = _two_coordinates()

    def run():
        return coordinate_descent(coords, data.y, data.weights,
                                  data.offsets, TASK, n_sweeps=2)

    def session(path):
        return checkpoint.session(str(path), every_evals=1, every_s=None,
                                  async_writer=False)

    ref = run()
    with session(tmp_path / "rec"), checkpoint.record_sites() as rec:
        run()
    assert dict(rec.hits)["commit"] == 4  # one progress cut an update
    with pytest.raises(checkpoint.InjectedFault):
        with session(tmp_path / "kill"), checkpoint.fault_plan(
                checkpoint.FaultPlan.kill_at("commit", 4)):
            run()
    with session(tmp_path / "kill"), telemetry.run("restored") as trun:
        out = run()
        counters = trun.report_compact()["counters"]
    assert counters["checkpoint.descent_restores"] == 1
    assert counters["game.coordinate_updates"] == 1
    assert counters["game_re.warm_adopted"] == 1
    for name in coords:
        np.testing.assert_array_equal(
            np.asarray(ref.model[name].coefficients),
            np.asarray(out.model[name].coefficients))
    assert ref.objective_history == out.objective_history
