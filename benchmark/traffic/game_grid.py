"""Whole GLMix regularization grids by coordinate descent on resident data,
back to back.

A unit is one `GameEstimator.fit(data, validation, config_grid=grid)`: the
grid is the training driver's own (`drivers.train._config_grid`: the cross
product of the coordinates' ``reg_weights`` lists, 8 points), the estimator
is built from the driver's JSON (`CoordinateSpec`) with ``warm_start``
false, so every point starts cold; each point runs fixed → per-member →
per-job for ``n_sweeps`` sweeps at a fixed depth, then the points' validation
AUC, and the unit closes with the point the driver keeps (`best_model`).
Whichever path the program takes — lanes of one descent or one fit a point —
the unit is the same grid. Avro ingest and the model save are NOT in the
unit (as in `game_descent`).

``work`` is `game_descent`'s, summed over the grid's points: rows × solver
iterations TAKEN, n × iterations for a fixed-effect update,
`RETrainStats.row_iterations` for a random-effect update.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark.gen import glmix_grid_reference as gref
from benchmark.gen import glmix_reference as ref
from benchmark.gen import glmix_wide
from benchmark.gen.reference import rank_auc
from benchmark.traffic import game_descent as gd
from benchmark.traffic.game_descent import metrics  # noqa: F401

GAP_LABELS = {**gd.GAP_LABELS, "game.grid_vectorized": "fit.grid"}
SAMPLE_SEED = gd.SAMPLE_SEED

# ---- limits of `check`: `game_descent`'s, for the same reasons, each now
# read lane by lane — a lane's returned model against the float64 reference
# under THAT lane's weights (`gen/glmix_grid_reference.py`), at the offsets
# its own other coordinates give. The controls go through the same
# comparisons and each must be refused: the reference at bf16 in the fit's
# place (the last coordinate's solves), each random-effect coordinate's
# tables left at zero in every lane, and every lane handed the last
# coordinate's table of a lane with another weight on it.
OBJECTIVE_RTOL = gd.OBJECTIVE_RTOL
BLOCK_RTOL = gd.BLOCK_RTOL
VALUE_RTOL = gd.VALUE_RTOL
GRAD_REL_LAST = gd.GRAD_REL_LAST
GAP_LAST = gd.GAP_LAST
NOT_BELOW = gd.NOT_BELOW
# (b) the EARLIER coordinate (per-member), solved before the per-job
# coordinate's last sweep moved its offsets: its drift is larger the
# weaker its L2 weight, and the grid's lanes reach down to 1 where
# `glmix-wide`'s is 10. Readings of this grid on a TPU v5e, as lanes and
# as sequential fits, over six seeds: fit 0.024 to 0.063, the table at
# zero 3.8 to 3.9; `game_descent`'s 5e-2 sits under the fit. The limit
# is about the geometric middle of 0.063 and 3.8.
GAP_EARLIER = 0.4
AUC_ATOL = gd.AUC_ATOL
AUC_LIFT = gd.AUC_LIFT
# (d) the fixed effect of every lane against the float64 optimum of its
# lane's objective over all rows, at the offsets the lane's random effects
# finally give. It is the FIRST coordinate of the sequence: its last update
# saw the offsets of the first sweep, so the reading is the drift of the
# random effects' last sweep, as GAP_EARLIER's; its zero control (the fixed
# effect left at 0) reads the whole gap.
FIXED_GAP = 5e-2


@dataclasses.dataclass
class State(gd.State):
    grid: list = None     # the driver's config grid: {name: config} a point
    lanes: list = None    # the reference's {coordinate: L2 weight} a lane


def setup(config: dict, params: dict, seed: int, dirs: dict) -> State:
    from photon_tpu.drivers.train import CoordinateSpec, _config_grid
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.ops.losses import TaskType

    specs = {name: CoordinateSpec(**spec)
             for name, spec in config["coordinates"].items()}
    estimator = GameEstimator(
        TaskType[config["task"]],
        {name: spec.coordinate_config() for name, spec in specs.items()},
        update_sequence=list(config["update_sequence"]),
        n_sweeps=int(config["n_sweeps"]),
        warm_start=bool(config["warm_start"]))
    grid = _config_grid(specs)
    t0 = time.perf_counter()
    pat = glmix_wide.pattern(config, dirs["shared"])
    t1 = time.perf_counter()
    arrays = glmix_wide.draw(config, seed, pat)
    t2 = time.perf_counter()
    features = int(config["re_features"])
    data = gd._game_data(arrays["train"], features)
    validation = gd._game_data(arrays["validation"], features).to_device()
    return State(config=config, params=params, estimator=estimator,
                 data=data, validation=validation, arrays=arrays,
                 rows=int(config["n_train_rows"]),
                 clocks={"pattern_s": t1 - t0, "generate_s": t2 - t1},
                 facts={}, grid=grid, lanes=gref.lane_weights(config))


def _fit(state: State):
    import jax

    from photon_tpu.game.random_effect import RETrainStats

    with jax.profiler.TraceAnnotation("bench.fit"):
        results = state.estimator.fit(state.data, state.validation,
                                      config_grid=state.grid)
        best = state.estimator.best_model(results)
    fixed, re_stats = [], []
    for r in results:
        for per in r.descent.coordinate_stats.values():
            for s in per:
                (re_stats if isinstance(s, RETrainStats) else fixed).append(s)
    with jax.profiler.TraceAnnotation("bench.readback"):
        fixed_iters = jax.device_get([s.iterations for s in fixed])
    work = (float(state.rows) * float(np.sum(fixed_iters))
            + sum(s.row_iterations for s in re_stats))
    aucs = [r.validation_score for r in results]
    histories = [np.asarray(r.descent.objective_history) for r in results]
    out = {"work": work, "lanes": len(results),
           "updates": sum(len(h) for h in histories),
           "re_updates": len(re_stats),
           "fixed_iterations": int(np.sum(fixed_iters)),
           "re_iterations": int(sum(s.total_iterations for s in re_stats)),
           "failed": any(a is None or not bool(np.isfinite(a)) for a in aucs)
           or not all(bool(np.all(np.isfinite(h))) for h in histories)}
    return results, next(i for i, r in enumerate(results) if r is best), out


def unit(state: State, keep: bool = False) -> dict:
    """One whole grid. The warm-up (``keep``) runs with the program's
    telemetry attached, as the traced units do, and keeps every lane's
    model on the host for `check`."""
    if not keep:
        return _fit(state)[2]
    from photon_tpu import telemetry

    t0 = time.perf_counter()
    with telemetry.run("game_grid.warmup") as run:
        results, best, out = _fit(state)
        report = run.report_compact()
    state.clocks["first_fit_s"] = time.perf_counter() - t0
    state.facts["warmup_report"] = report
    builds = [v for k, v in report["span_totals"].items()
              if k.split("/")[-1] == "game_re.build"]
    if builds:
        state.clocks["re_dataset_build_s"] = sum(builds)
    state.facts["build_counters"] = {
        k: v for k, v in report["counters"].items()
        if k.startswith("game_re.block_")}
    datasets = state.estimator.datasets(state.data)
    state.facts["blocks"] = {
        name: [(b.n_entities, b.m, b.dim) for b in ds.blocks]
        for name, ds in datasets.items() if hasattr(ds, "blocks")}
    lanes = []
    for r in results:
        model = r.model
        lanes.append({
            "weights": {n: float(c.optimizer.reg_weight)
                        for n, c in r.configs.items()},
            "history": [float(v) for v in r.descent.objective_history],
            "auc": r.validation_score,
            "fixed": {n: np.asarray(m.model.coefficients.means)
                      for n, m in model.coordinates.items()
                      if hasattr(m, "model")},
            "tables": {n: np.asarray(m.coefficients)
                       for n, m in model.coordinates.items()
                       if hasattr(m, "coefficients")},
            "values": {n: np.asarray(per[-1].entity_values)
                       for n, per in r.descent.coordinate_stats.items()
                       if getattr(per[-1], "entity_values", None)
                       is not None}})
    out["evidence"] = {"lanes": lanes, "best": best,
                       "device_best": report.get("gauges", {}).get(
                           "game_grid.best_lane")}
    return out


# ------------------------------------------------------------------- check
def _lane_state(state: State, weights: dict) -> State:
    """``state`` with the configuration's L2 weights set to one lane's."""
    config = {**state.config, "coordinates": {
        n: {**spec, "reg_weight": weights[n]}
        for n, spec in state.config["coordinates"].items()}}
    return dataclasses.replace(state, config=config)


def _entities_refused_by(r: dict, last: bool) -> list:
    """`game_descent._entities_refused_by` under this cell's limits."""
    broke = [key for key, limit in (
        ("cap_errors", 0), ("outside", 0), ("block_diff", BLOCK_RTOL),
        ("below", NOT_BELOW), ("gap", GAP_LAST if last else GAP_EARLIER))
        if r.get(key, 0) > limit]
    if last:
        broke += [key for key, limit in (("value_rel", VALUE_RTOL),
                                         ("grad_rel", GRAD_REL_LAST))
                  if not r.get(key, np.inf) <= limit]
    return broke


def check_entities(state: State, evidence: dict, margins: list) -> tuple:
    """`game_descent.check_entities`, lane by lane: in every bucket of every
    random-effect coordinate `sample_per_bucket` entities are drawn, and
    the i-th of them is held to the float64 optimum of lane i mod G's
    objective (its L2 weight, the offsets lane i mod G's other coordinates
    give), so every lane is read in every bucket. Beside each reading those
    of the controls: the row LEFT AT ZERO, the row of the lane with the
    other weight on the last coordinate, the reference at bf16."""
    rows = state.arrays["train"]
    sequence = list(state.config["update_sequence"])
    datasets = state.estimator.datasets(state.data)
    rng = np.random.default_rng(SAMPLE_SEED)
    per_bucket = int(state.params["sample_per_bucket"])
    lanes = evidence["lanes"]
    G = len(lanes)
    totals = [sum(m.values()) for m in margins]
    out, controls = {}, {"bf16": {}, "lanes_swapped": {}}
    for name, spec in state.config["coordinates"].items():
        if "entity_name" not in spec:
            continue
        ds = datasets[name]
        ind, val = rows[spec["feature_shard"]]
        ids = gd._ids(rows, spec)
        counts = np.bincount(ids, minlength=ds.n_entities)
        cap = spec.get("active_cap")
        last = name == sequence[-1]
        # the lane each lane is swapped with: its last coordinate's other
        # weight, the rest of its weights the same
        partner = [next(h for h in range(G) if h != g and all(
            lanes[h]["weights"][n] == lanes[g]["weights"][n]
            for n in lanes[g]["weights"] if n != sequence[-1]))
                   for g in range(G)]
        worst = {"outside": 0, "cap_errors": 0, "entities": 0}
        zero, low, swapped = {}, {}, {}
        for block in ds.blocks:
            row_index = np.asarray(block.row_index)
            real = np.asarray(block.weights) != 0.0
            picks = gd._sampled(block, per_bucket, rng)
            stored = (np.asarray(block.X[picks]) if block.proj is not None
                      else None)
            for at, pos in enumerate(picks):
                g = at % G
                lane = lanes[g]
                e = int(block.entity_index[pos])
                r = row_index[pos][real[pos]]
                want = counts[ds.entity_keys[e]]
                want = min(want, cap) if cap is not None else want
                if (len(r) != want or len(set(r.tolist())) != len(r)
                        or np.any(ids[r] != ds.entity_keys[e])):
                    worst["cap_errors"] += 1
                cols, X = ref.entity_problem(ind[r], val[r])
                if stored is not None:
                    scale = np.abs(X).max()
                    for into, kept in ((worst, stored[at]),
                                       (low, ref.bf16(stored[at]))):
                        gd._worse(into, block_diff=np.abs(
                            kept - gd._block_as_stored(
                                cols, X, block.m, block.dim, ds.dim - 1)
                        ).max() / scale)
                offsets = totals[g] - margins[g][name]
                y, offs = rows["y"][r].astype(np.float64), offsets[r]
                l2 = lane["weights"][name]
                table = lane["tables"][name]
                w_fit = table[e, cols].astype(np.float64)
                worst["outside"] += int(np.count_nonzero(table[e])
                                        - np.count_nonzero(w_fit))
                worst["entities"] += 1
                _, best = gref.entity_optimum(X, y, offs, l2)
                g0 = np.linalg.norm(ref.gradient(
                    X, y, offs, np.zeros_like(w_fit), l2))
                values = lane["values"].get(name)
                said = None if values is None else float(values[e])
                cases = [(worst, w_fit, said),
                         (zero, np.zeros_like(w_fit), said)]
                if last:
                    w_low, said_low = gref.entity_optimum(X, y, offs, l2,
                                                          ref.bf16)
                    cases.append((low, w_low, said_low))
                    cases.append((swapped, lanes[partner[g]]["tables"][name][
                        e, cols].astype(np.float64), None))
                for into, w, reported in cases:
                    at_w = ref.objective(X, y, offs, w, l2)
                    gd._worse(into, gap=(at_w - best) / best,
                              below=(best - at_w) / best,
                              grad_rel=np.linalg.norm(
                                  ref.gradient(X, y, offs, w, l2)) / g0)
                    if reported is not None:
                        gd._worse(into, value_rel=abs(reported - at_w) / at_w)
        out[name] = worst
        controls[f"zero_table.{name}"] = {name: zero}
        if last:
            controls["bf16"][name] = low
            controls["lanes_swapped"][name] = swapped
    return out, controls


def _fixed_gaps(state: State, lane: dict, margins: dict) -> tuple:
    """(d): (the lane's fixed effect, 0 in its place) against the float64
    optimum of its lane's objective at the offsets its random effects
    give, less 1, the largest over the fixed-effect coordinates."""
    rows = state.arrays["train"]
    y = rows["y"].astype(np.float64)
    fit = zero = 0.0
    for name, spec in state.config["coordinates"].items():
        if "entity_name" in spec:
            continue
        X = rows[spec["feature_shard"]]
        offsets = sum(v for n, v in margins.items() if n != name)
        l2 = lane["weights"][name]
        _, best = gref.fixed_optimum(X, y, offsets, l2)
        w = lane["fixed"][name].astype(np.float64)
        fit = max(fit, (gref.fixed_objective(X, y, offsets, w, l2) - best)
                  / best)
        zero = max(zero, (gref.fixed_objective(
            X, y, offsets, np.zeros_like(w), l2) - best) / best)
    return fit, zero


def check(state: State, evidence: dict) -> dict:
    """Lane by lane, `game_descent.check`'s comparisons under the lane's
    own weights: (a) the objective history, its last entry the float64
    loss of the lane's returned coefficients; (b) `check_entities`; (c)
    the lane's AUC against a float64 rank-AUC of the same coefficients, and
    its lift over the fixed effect alone; (d) the fixed effect at its
    lane's optimum within FIXED_GAP. And of the grid: one lane a point of
    the reference's grid, in its order; the point the program keeps is the
    one whose reference AUC is highest, to AUC_ATOL. Then the CONTROLS,
    each through the same comparisons; the run is correct only if every
    one of them is refused."""
    lanes = evidence["lanes"]
    y, y_val = state.arrays["train"]["y"], state.arrays["validation"]["y"]
    updates = int(state.config["n_sweeps"]) * len(
        state.config["update_sequence"])
    last = state.config["update_sequence"][-1]
    grid_ok = [lane["weights"] for lane in lanes] == state.lanes
    train, val = [], []
    for lane in lanes:
        lane_state = _lane_state(state, lane["weights"])
        train.append(gd._margins(lane_state, "train", lane))
        val.append(gd._margins(lane_state, "validation", lane))
    entities, control_entities = check_entities(state, evidence, train)
    for name, r in entities.items():
        r["refused_by"] = _entities_refused_by(r, name == last)
    fixed_names = [n for n, s in state.config["coordinates"].items()
                   if "entity_name" not in s]

    gaps = [_fixed_gaps(state, lane, train[g])
            for g, lane in enumerate(lanes)]

    def lane_verdict(g: int, margins: dict, margins_val: dict) -> dict:
        lane = lanes[g]
        objective = gd._objective(np.asarray(lane["history"], np.float64),
                                  gref.lane_loss(margins, y), state.rows,
                                  updates)
        auc = rank_auc(sum(margins_val.values()), y_val)
        fixed_auc = rank_auc(sum(val[g][n] for n in fixed_names), y_val)
        return {"objective": objective, "reference_auc": auc,
                "fit_auc": lane["auc"], "fixed_gap": gaps[g][0],
                "ok": bool(objective["ok"] and gaps[g][0] <= FIXED_GAP
                           and abs(auc - lane["auc"]) <= AUC_ATOL
                           and auc - fixed_auc >= AUC_LIFT)}

    per_lane = [lane_verdict(g, train[g], val[g]) for g in range(len(lanes))]
    ref_aucs = [v["reference_auc"] for v in per_lane]
    best_ok = (ref_aucs[evidence["best"]] >= max(ref_aucs) - AUC_ATOL
               and evidence["device_best"] in (None, evidence["best"]))

    def refused_by(entities: dict, verdicts: list) -> list:
        return [f"{name}.{key}" for name, r in entities.items()
                for key in r["refused_by"]] + [
            f"lane{g}" for g, v in enumerate(verdicts) if not v["ok"]]

    refused = refused_by(entities, per_lane) + (
        [] if grid_ok else ["grid"]) + ([] if best_ok else ["best_lane"])
    controls = {}
    for control, per in control_entities.items():
        for name, r in per.items():
            r["refused_by"] = _entities_refused_by(r, name == last)
        verdicts = []
        if control.startswith("zero_table."):
            (name,) = per
            verdicts = [lane_verdict(
                g, {n: v for n, v in train[g].items() if n != name},
                {n: v for n, v in val[g].items() if n != name})
                for g in range(len(lanes))]
        c_refused = refused_by({**entities, **per}, verdicts)
        controls[control] = {"ok": not c_refused, "refused_by": c_refused,
                             "entities": per}
    controls["zero_fixed"] = {
        "ok": all(zero <= FIXED_GAP for _, zero in gaps),
        "fixed_gap": [zero for _, zero in gaps]}
    controls_refused = all(not c["ok"] for c in controls.values())
    return {"ok": not refused and controls_refused, "refused_by": refused,
            "controls_refused": controls_refused, "lanes": per_lane,
            "best": evidence["best"], "device_best": evidence["device_best"],
            "entities": entities, "controls": controls}
