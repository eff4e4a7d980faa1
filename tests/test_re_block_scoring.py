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
from photon_tpu.game.random_effect import RandomEffectCoordinate
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
    text = fn.lower(jnp.zeros((ds.n_entities, ds.dim), jnp.float32), zeros,
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
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_two_coordinate_descent_matches_the_block_loop(sparse):
    """Each coordinate trains against the other's margins as offsets: the
    one-dispatch descent (block-scored) and the block loop's `train` +
    `score` (table-scored) agree on every update's margins and objective
    within what `test_projected_one_dispatch_update_matches_block_loop`
    grants the pair of solvers."""
    prob = _problem(seed=4)
    X = (SparseRows(prob["ind"], prob["val"], FEATURES + 1) if sparse
         else prob["dense"])
    data = GameData.build(prob["y"], {"s": X},
                          {"e": prob["ent"], "g": (prob["ent"] * 5) % 11},
                          offsets=prob["offsets"])
    cfg = OptimizerConfig(max_iters=25, tolerance=0.0, reg=reg.l2(),
                          reg_weight=L2)
    coords = {
        "a": RandomEffectCoordinate(RandomEffectDataset.build(
            data, "e", "s", active_cap=CAP, projection=INDEX_MAP), TASK, cfg),
        "b": RandomEffectCoordinate(RandomEffectDataset.build(
            data, "g", "s", active_cap=4 * CAP), TASK, cfg)}
    assert all(c.dataset.n_passive > 0 for c in coords.values())
    seen = []  # (table, margins) each one-dispatch update returned, in order

    def recording(fn):
        def call(*args):
            out = fn(*args)
            seen.append((np.asarray(out[0]), np.asarray(out[2])))
            return out
        return call

    for coord in coords.values():
        fn, *rest = coord.fused_update_program()
        coord._fused_cache = (recording(fn), *rest)
    out = coordinate_descent(coords, data.y, data.weights, data.offsets,
                             TASK, n_sweeps=2)

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
