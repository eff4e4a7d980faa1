"""Whole GLMix incremental refreshes on resident data, back to back.

A unit is one `GameEstimator.fit(day1, validation, initial_models=prior)`:
yesterday's model — day 0 fitted in set-up with FULL variances, the members
and jobs new since then taken out of it — is the Gaussian prior (mean: its
coefficients; precision: 1 / its variances) and the warm start of EVERY
coordinate, fixed → per-member → per-job for ``n_sweeps`` sweeps at a fixed
depth, and the returned model carries FULL variances for tomorrow. The
estimator is built from the training driver's own JSON (`CoordinateSpec`),
with the driver's ``variance_type`` and ``incremental_coordinates`` as the
configuration's ``variance`` and ``incremental``. Avro ingest, the model's
load and its save are NOT in the unit (as in `game_descent`).

``work`` is `game_descent`'s: rows × solver iterations TAKEN, n ×
iterations for a fixed-effect update, `RETrainStats.row_iterations` for a
random-effect update. The variances are work the count does not see: they
cost time at the same count (`re_variance_ms` says how much).

The FIRST thing `setup` does is `probe`: an incremental fit at rehearse
sizes, which fails in seconds unless every coordinate update of it takes
the one-dispatch update with its prior and computes variances there — a
program that sends a prior to the host block loop would otherwise spend
minutes a fit in it.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from benchmark.gen import glmix_incremental as days
from benchmark.gen import glmix_incremental_reference as iref
from benchmark.gen import glmix_reference as ref
from benchmark.gen import glmix_wide
from benchmark.gen.reference import rank_auc
from benchmark.traffic import game_descent as gd
from benchmark.traffic.game_descent import GAP_LABELS, metrics  # noqa: F401

SAMPLE_SEED = gd.SAMPLE_SEED

# ---- limits of `check`. Each lies between two readings taken in the SAME
# run and printed beside it: what the fit reads, and what a control reads.
# The controls are put through the comparison the fit goes through, and a
# run is correct only if each is refused: the prior DROPPED (each seen
# entity's plain L2 optimum in the fit's place, its variances without the
# prior's precision); SIMPLE variances (1 / H_jj) in FULL's place; the
# variances of a bf16 Gram (both operands of the product rounded to bf16:
# a product at the precision below the configuration's float32); an entity
# new since day 0 given UNIT precision toward 0 instead of none. Readings:
# my chip runs of PR 38, four seeds (PERF.md section 4), the extremes.
#
# (a) the objective history, its last entry against the float64 loss of
# the returned coefficients, and (c) the validation AUC: `game_descent`'s
# limits, for the same reasons (an f32 sum of the rows' losses; the f32
# evaluator).
# (b) an entity's row of the table against the float64 optimum of ITS
# objective — under its prior where the prior model has a row for it, the
# plain L2 objective where it is new — at the offsets the fit's other
# coordinates finally give: `game_descent`'s GAP_LAST / GAP_EARLIER /
# NOT_BELOW, VALUE_RTOL and GRAD_REL_LAST, the gradient's scale now taken
# at the prior mean (the solve's warm start) instead of at 0. Last
# coordinate: gap 1.0e-6 to 2.9e-6, unit precision for new jobs 1.47e-3 to
# 1.52e-3, the prior dropped 0.63 to 0.92; value_rel 5.0e-5 to 8.3e-5
# (the chip's `log`), the new jobs' control 1.47e-3 to 1.53e-3; grad_rel
# 3.6e-3 to 4.6e-3, that control 0.063 to 0.067. Earlier coordinate: gap
# 2.7e-3 to 4.4e-3, the prior dropped 1.3 to 3.5.
GAP_LAST = gd.GAP_LAST
GAP_EARLIER = gd.GAP_EARLIER
NOT_BELOW = gd.NOT_BELOW
VALUE_RTOL = gd.VALUE_RTOL
GRAD_REL_LAST = gd.GRAD_REL_LAST
# (b) an entity's returned variances against float64 diag(H⁻¹), H = Xᵀ·
# diag(p(1−p))·X + diag(l2 + τ) at its returned row of the table, the
# largest relative error over its columns. The LAST coordinate's update saw
# the offsets the check computes H at; an earlier coordinate's last update
# saw the offsets of the sweep before the later coordinates' last update,
# so its H differs by that drift, which the looser limit admits. Last
# coordinate: fit 1.25e-6 to 1.44e-6 (f32 at HIGHEST); a bf16 Gram 4.2e-4
# to 5.7e-4; SIMPLE 0.25 to 0.26; unit precision for new jobs 0.091; the
# prior dropped 1.26 to 1.30 — the limit is about the geometric middle of
# the fit and the bf16 Gram. Earlier coordinate: fit 6.4e-3 to 1.03e-2,
# unit precision for new members 0.091, SIMPLE 0.30 to 0.34.
VAR_LAST = 2.5e-5
VAR_EARLIER = 3e-2
# (b') the fixed effect's 65 variances against float64, at the final
# offsets: its last update is the first of the last sweep, so the drift of
# the random effects' last sweep is in the reading: fit 1.20e-2 to
# 1.34e-2; SIMPLE 0.92 (65 correlated match features); the prior dropped
# 2.56 to 2.59. A bf16 Gram over a million rows reads 1.4e-5 to 1.8e-5
# here, under the drift: the last coordinate's limit refuses it.
FIXED_VAR_RTOL = 5e-2


@dataclasses.dataclass
class _Refresh:
    """The estimator as the window calls it: every fit from yesterday's
    model. ``keep`` holds the last fit's results for `unit`'s evidence."""

    estimator: object
    prior: dict
    keep: bool = False
    last: object = None

    def fit(self, data, validation=None):
        results = self.estimator.fit(data, validation,
                                     initial_models=self.prior)
        self.last = results if self.keep else None
        return results

    def datasets(self, data) -> dict:
        return self.estimator.datasets(data)


@dataclasses.dataclass
class State(gd.State):
    # {coordinate: (entity keys or None, means, variances)} of the prior
    # model, on the host, for `check`
    prior_host: dict = None
    new: dict = None  # {"member", "job": the ids new since day 0}


def _estimator(config: dict):
    from photon_tpu.drivers.train import CoordinateSpec
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.models.variance import VarianceComputationType
    from photon_tpu.ops.losses import TaskType

    specs = {name: CoordinateSpec(**spec)
             for name, spec in config["coordinates"].items()}
    return GameEstimator(
        TaskType[config["task"]],
        {name: spec.coordinate_config() for name, spec in specs.items()},
        update_sequence=list(config["update_sequence"]),
        n_sweeps=int(config["n_sweeps"]),
        variance=VarianceComputationType[config["variance"].upper()],
        incremental=frozenset(config["incremental"]))


def _without_new(models: dict, config: dict, new: dict) -> dict:
    """Day 0's model as the prior: each random-effect model without the
    rows of the entities new since day 0."""
    import jax.numpy as jnp

    from photon_tpu.game.model import RandomEffectModel

    out = {}
    for name, model in models.items():
        spec = config["coordinates"][name]
        if not isinstance(model, RandomEffectModel):
            out[name] = model
            continue
        drop = new["member" if spec["entity_name"] == "memberId" else "job"]
        keys = np.asarray(model.entity_keys)
        keep = np.nonzero(~np.isin(keys, drop))[0]
        at = jnp.asarray(keep, jnp.int32)
        out[name] = dataclasses.replace(
            model, coefficients=model.coefficients[at],
            variances=model.variances[at], entity_keys=keys[keep],
            key_to_index={k: i for i, k in enumerate(keys[keep].tolist())})
    return out


def _prior(config: dict, arrays: dict, new: dict) -> dict:
    """Fit day 0 (FULL variances, cold) and return the prior: its model
    without the entities new since then. Day 0's data, datasets and
    estimator are let go here."""
    estimator = _estimator({**config, "incremental": []})
    data = gd._game_data(arrays, int(config["re_features"]))
    (result,) = estimator.fit(data)
    prior = _without_new(dict(result.model.coordinates), config, new)
    del estimator, data, result
    gc.collect()
    return prior


def probe(config: dict, cache_dir: str) -> dict:
    """Raise unless an incremental fit with FULL variances takes the
    one-dispatch update for EVERY coordinate update: at the rehearse sizes,
    one sweep of two iterations from a prior made of zeros and unit
    variances, with both coordinate kinds' host paths (`train`) made to
    fail at once. Returns the counters."""
    from photon_tpu import telemetry
    from photon_tpu.game.fixed_effect import FixedEffectCoordinate
    from photon_tpu.game.model import FixedEffectModel, RandomEffectModel
    from photon_tpu.game.random_effect import RandomEffectCoordinate
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel

    small = {**config, **config["rehearse"], "n_sweeps": 1}
    small["coordinates"] = {
        name: {**spec, "max_iters": 2}
        for name, spec in config["coordinates"].items()}
    estimator = _estimator(small)
    pat = glmix_wide.pattern(small, cache_dir)
    arrays = days.draw_days(small, 1, pat)["day1"]["train"]
    data = gd._game_data(arrays, int(small["re_features"]))
    task = estimator.task
    prior = {}
    for name, spec in small["coordinates"].items():
        if "entity_name" not in spec:
            d = arrays[spec["feature_shard"]].shape[1]
            prior[name] = FixedEffectModel(GeneralizedLinearModel(
                Coefficients(np.zeros(d, np.float32),
                             np.ones(d, np.float32)), task),
                spec["feature_shard"])
            continue
        keys = np.unique(gd._ids(arrays, spec))
        d = int(small["re_features"]) + 1
        prior[name] = RandomEffectModel(
            entity_name=spec["entity_name"],
            feature_shard=spec["feature_shard"], task=task,
            coefficients=np.zeros((keys.size, d), np.float32),
            entity_keys=keys,
            key_to_index={k: i for i, k in enumerate(keys.tolist())},
            variances=np.ones((keys.size, d), np.float32))

    def host_path(self, *args, **kwargs):
        raise SystemExit(
            "game_incremental: a coordinate update with an incremental "
            f"prior took the host path ({type(self).__name__}.train), not "
            "the one-dispatch update; at the configuration's sizes that is "
            "minutes a fit (probe at rehearse sizes)")

    saved = (FixedEffectCoordinate.train, RandomEffectCoordinate.train)
    FixedEffectCoordinate.train = RandomEffectCoordinate.train = host_path
    try:
        with telemetry.run("game_incremental.probe") as run:
            estimator.fit(data, initial_models=prior)
            counters = run.report_compact()["counters"]
    finally:
        FixedEffectCoordinate.train, RandomEffectCoordinate.train = saved
    re_updates = len(prior) - 1
    said = {k: counters.get(k, 0) for k in (
        "game_re.fused_prior_updates", "game_re.variance_lanes",
        "game_re.fused_gate_offs", "game_re.prior_seen")}
    if (said["game_re.fused_prior_updates"] != re_updates
            or said["game_re.variance_lanes"] <= 0
            or said["game_re.fused_gate_offs"] != 0):
        raise SystemExit(
            "game_incremental: an incremental fit with FULL variances did "
            f"not run every random-effect update ({re_updates}) as a "
            f"one-dispatch update with its prior and variances: {said}")
    return said


def setup(config: dict, params: dict, seed: int, dirs: dict) -> State:
    t0 = time.perf_counter()
    probed = probe(config, dirs["shared"])
    t1 = time.perf_counter()
    estimator = _estimator(config)
    pat = glmix_wide.pattern(config, dirs["shared"])
    drawn = days.draw_days(config, seed, pat)
    new = days.new_entities(config, pat)
    t2 = time.perf_counter()
    prior = _prior(config, drawn["day0"]["train"], new)
    t3 = time.perf_counter()
    arrays = drawn["day1"]
    features = int(config["re_features"])
    data = gd._game_data(arrays["train"], features)
    validation = gd._game_data(arrays["validation"], features).to_device()
    prior_host = {}
    for name, model in prior.items():
        if hasattr(model, "entity_keys"):
            prior_host[name] = (np.asarray(model.entity_keys),
                                np.asarray(model.coefficients, np.float64),
                                np.asarray(model.variances, np.float64))
        else:
            c = model.model.coefficients
            prior_host[name] = (None, np.asarray(c.means, np.float64),
                                np.asarray(c.variances, np.float64))
    return State(config=config, params=params,
                 estimator=_Refresh(estimator, prior), data=data,
                 validation=validation, arrays=arrays,
                 rows=int(config["n_train_rows"]),
                 clocks={"probe_s": t1 - t0, "generate_s": t2 - t1,
                         "day0_fit_s": t3 - t2},
                 facts={"probe": probed}, prior_host=prior_host, new=new)


def unit(state: State, keep: bool = False) -> dict:
    """One whole refresh (`game_descent.unit`, from the prior); with
    ``keep`` the evidence adds the returned variances."""
    if not keep:
        return gd.unit(state)
    state.estimator.keep = True
    try:
        out = gd.unit(state, keep=True)
        (result,) = state.estimator.last
    finally:
        state.estimator.keep, state.estimator.last = False, None
    model = result.model
    out["evidence"]["variances"] = {
        n: np.asarray(m.variances) for n, m in model.coordinates.items()
        if hasattr(m, "coefficients")}
    out["evidence"]["fixed_variances"] = {
        n: np.asarray(m.model.coefficients.variances)
        for n, m in model.coordinates.items() if hasattr(m, "model")}
    return out


# ------------------------------------------------------------------- check
def _prior_of(state: State, name: str, key, cols) -> tuple:
    """(μ, τ, seen) of one entity over its columns, from the prior model."""
    keys, means, var = state.prior_host[name]
    at = np.searchsorted(keys, key)
    if at >= len(keys) or keys[at] != key:
        return np.zeros(len(cols)), np.zeros(len(cols)), False
    return means[at, cols], iref.prior_precision(var[at, cols]), True


def _entity_readings(into: dict, X, y, offs, w, var, l2, mu, tau, best,
                     g0, reported=None) -> None:
    at_w = iref.objective(X, y, offs, w, l2, mu, tau)
    want = iref.full_variances(iref.hessian(X, y, offs, w, l2, tau))
    gd._worse(into, gap=(at_w - best) / best, below=(best - at_w) / best,
              grad_rel=np.linalg.norm(
                  iref.gradient(X, y, offs, w, l2, mu, tau)) / g0,
              var_rel=np.max(np.abs(var - want) / want))
    if reported is not None:
        gd._worse(into, value_rel=abs(reported - at_w) / at_w)


def _refused_by(r: dict, last: bool) -> list:
    broke = [key for key, limit in (
        ("cap_errors", 0), ("prior_errors", 0), ("outside", 0),
        ("block_diff", gd.BLOCK_RTOL),
        ("below", NOT_BELOW), ("gap", GAP_LAST if last else GAP_EARLIER),
        ("var_rel", VAR_LAST if last else VAR_EARLIER))
        if r.get(key, 0) > limit]
    if last:
        broke += [key for key, limit in (("value_rel", VALUE_RTOL),
                                         ("grad_rel", GRAD_REL_LAST))
                  if not r.get(key, np.inf) <= limit]
    return broke


def check_entities(state: State, evidence: dict, train_margins: dict) -> tuple:
    """(b) for a seeded sample of every bucket of every random-effect
    coordinate, and up to ``sample_unseen`` entities new since day 0: the
    rows an entity is trained on are its own, as many as the cap allows;
    the block holds exactly those rows' values; its row of the table is at
    the float64 optimum of its objective (under its prior, or the plain L2
    one where it is new), and its variances are float64 diag(H⁻¹) there.

    Returns ({coordinate: the fit's worst readings}, {control:
    {coordinate: the control's worst readings}})."""
    rows = state.arrays["train"]
    sequence = list(state.config["update_sequence"])
    datasets = state.estimator.datasets(state.data)
    rng = np.random.default_rng(SAMPLE_SEED)
    total = sum(train_margins.values())
    per_bucket = int(state.params["sample_per_bucket"])
    per_new = int(state.params["sample_unseen"])
    out, controls = {}, {"prior_dropped": {}, "simple_variances": {},
                         "bf16_gram": {}, "unseen_unit_precision": {}}
    for name, spec in state.config["coordinates"].items():
        if "entity_name" not in spec:
            continue
        ds = datasets[name]
        ind, val = rows[spec["feature_shard"]]
        ids = gd._ids(rows, spec)
        counts = np.bincount(ids, minlength=ds.n_entities)
        offsets = total - train_margins[name]
        table = evidence["tables"][name]
        var_table = evidence["variances"][name]
        values = evidence["values"].get(name)
        l2 = float(spec["reg_weight"])
        cap = spec.get("active_cap")
        last = name == sequence[-1]
        new = set(state.new["member" if spec["entity_name"] == "memberId"
                            else "job"].tolist())
        worst = {"outside": 0, "cap_errors": 0, "prior_errors": 0,
                 "entities": 0, "unseen": 0}
        ctl = {k: {} for k in controls}
        unseen_left = per_new
        for block in ds.blocks:
            row_index = np.asarray(block.row_index)
            real = np.asarray(block.weights) != 0.0
            keys = ds.entity_keys[block.entity_index]
            picks = gd._sampled(block, per_bucket, rng)
            fresh = np.nonzero(np.isin(keys, list(new)))[0][:unseen_left]
            unseen_left -= len(fresh)
            picks = np.unique(np.concatenate([picks, fresh]))
            stored = np.asarray(block.X[picks])  # one device read a bucket
            for at, pos in enumerate(picks):
                e = int(block.entity_index[pos])
                r = row_index[pos][real[pos]]
                want = counts[ds.entity_keys[e]]
                want = min(want, cap) if cap is not None else want
                if (len(r) != want or len(set(r.tolist())) != len(r)
                        or np.any(ids[r] != ds.entity_keys[e])):
                    worst["cap_errors"] += 1
                cols, X = ref.entity_problem(ind[r], val[r])
                _worse_block = np.abs(stored[at] - gd._block_as_stored(
                    cols, X, block.m, block.dim, ds.dim - 1)).max()
                gd._worse(worst, block_diff=_worse_block / np.abs(X).max())
                y, offs = rows["y"][r].astype(np.float64), offsets[r]
                w_fit = table[e, cols].astype(np.float64)
                v_fit = var_table[e, cols].astype(np.float64)
                worst["outside"] += int(np.count_nonzero(table[e])
                                        - np.count_nonzero(w_fit))
                worst["entities"] += 1
                mu, tau, seen = _prior_of(state, name, ds.entity_keys[e],
                                          cols)
                if seen == (ds.entity_keys[e] in new):
                    worst["prior_errors"] += 1  # a prior where none is, or
                    # none where one is
                worst["unseen"] += int(not seen)
                _, best = iref.newton(X, y, offs, l2, mu, tau)
                g0 = np.linalg.norm(iref.gradient(X, y, offs, mu, l2, mu,
                                                  tau))
                said = None if values is None else float(values[e])
                _entity_readings(worst, X, y, offs, w_fit, v_fit, l2, mu,
                                 tau, best, g0, said)
                H = iref.hessian(X, y, offs, w_fit, l2, tau)
                if seen:  # a program that dropped the prior
                    w_np, _ = iref.newton(X, y, offs, l2, 0 * mu, 0 * tau)
                    v_np = iref.full_variances(iref.hessian(
                        X, y, offs, w_np, l2, 0 * tau))
                    _entity_readings(ctl["prior_dropped"], X, y, offs, w_np,
                                     v_np, l2, mu, tau, best, g0, said)
                else:  # unit precision toward 0 for a new entity
                    one = np.ones_like(tau)
                    w_u, _ = iref.newton(X, y, offs, l2, mu, one)
                    v_u = iref.full_variances(iref.hessian(
                        X, y, offs, w_u, l2, one))
                    _entity_readings(ctl["unseen_unit_precision"], X, y,
                                     offs, w_u, v_u, l2, mu, tau, best, g0,
                                     said)
                _entity_readings(ctl["simple_variances"], X, y, offs, w_fit,
                                 iref.simple_variances(H), l2, mu, tau, best,
                                 g0, said)
                if last:
                    v_low = iref.full_variances(iref.hessian(
                        X, y, offs, w_fit, l2, tau, ref.bf16))
                    _entity_readings(ctl["bf16_gram"], X, y, offs, w_fit,
                                     v_low, l2, mu, tau, best, g0, said)
        out[name] = worst
        for control, readings in ctl.items():
            if readings:
                controls[control][name] = readings
    return out, controls


def check_fixed(state: State, evidence: dict, train_margins: dict) -> tuple:
    """(b') each fixed-effect coordinate's returned variances against
    float64 diag(H⁻¹) at its returned coefficients, the prior's precision
    in H (`PriorDistribution.from_coefficients`); beside them SIMPLE and
    bf16-Gram variances and those of H without the prior."""
    rows = state.arrays["train"]
    total = sum(train_margins.values())
    out, controls = {}, {"prior_dropped": {}, "simple_variances": {},
                         "bf16_gram": {}}
    for name, spec in state.config["coordinates"].items():
        if "entity_name" in spec:
            continue
        X = rows[spec["feature_shard"]]
        y = rows["y"].astype(np.float64)
        offs = total - train_margins[name]
        w = evidence["fixed"][name].astype(np.float64)
        got = evidence["fixed_variances"][name].astype(np.float64)
        _, _, prior_var = state.prior_host[name]
        tau = 1.0 / np.maximum(prior_var, 1e-12)
        l2 = float(spec["reg_weight"])
        H = iref.hessian(X, y, offs, w, l2, tau)
        want = iref.full_variances(H)

        def rel(v):
            return {"var_rel": float(np.max(np.abs(v - want) / want))}

        out[name] = rel(got)
        controls["simple_variances"][name] = rel(iref.simple_variances(H))
        controls["bf16_gram"][name] = rel(iref.full_variances(
            iref.hessian(X, y, offs, w, l2, tau, ref.bf16)))
        controls["prior_dropped"][name] = rel(iref.full_variances(
            iref.hessian(X, y, offs, w, l2, 0 * tau)))
    return out, controls


def check(state: State, evidence: dict) -> dict:
    """(a) the objective history, its last entry the float64 loss of the
    returned coefficients; (b) `check_entities`; (b') `check_fixed`; (c)
    the fit's AUC against a float64 rank-AUC of the same coefficients, and
    its lift over the fixed effect alone — `game_descent`'s (a) and (c).
    Then the four CONTROLS through the same comparisons; the run is correct
    only if every control is refused."""
    history = np.asarray(evidence["history"], np.float64)
    y, y_val = state.arrays["train"]["y"], state.arrays["validation"]["y"]
    updates = int(state.config["n_sweeps"]) * len(
        state.config["update_sequence"])
    train = gd._margins(state, "train", evidence)
    val = gd._margins(state, "validation", evidence)
    objective = gd._objective(history, ref.log_loss(sum(train.values()), y),
                              state.rows, updates)
    entities, control_entities = check_entities(state, evidence, train)
    fixed, control_fixed = check_fixed(state, evidence, train)
    last = state.config["update_sequence"][-1]
    for name, r in entities.items():
        r["refused_by"] = _refused_by(r, name == last)
    for r in fixed.values():
        r["refused_by"] = ([] if r["var_rel"] <= FIXED_VAR_RTOL
                           else ["var_rel"])
    fixed_auc = rank_auc(sum(v for n, v in val.items()
                             if n in evidence["fixed"]), y_val)
    auc = rank_auc(sum(val.values()), y_val)
    scored = {"fit_auc": evidence["auc"], "reference_auc": auc,
              "fixed_effect_only_auc": fixed_auc,
              "ok": bool(abs(auc - evidence["auc"]) <= gd.AUC_ATOL
                         and auc - fixed_auc >= gd.AUC_LIFT)}

    def refused_by(ents: dict, fx: dict) -> list:
        return ([] if objective["ok"] else ["objective"]) + [
            f"{name}.{key}" for per in (ents, fx)
            for name, r in per.items() for key in r["refused_by"]] + (
            [] if scored["ok"] else ["validation"])

    refused = refused_by(entities, fixed)
    controls = {}
    for control in control_entities:
        per = control_entities[control]
        per_fixed = control_fixed.get(control, {})
        for name, r in per.items():
            r["refused_by"] = _refused_by(r, name == last)
        for r in per_fixed.values():
            r["refused_by"] = ([] if r["var_rel"] <= FIXED_VAR_RTOL
                               else ["var_rel"])
        c_refused = refused_by({**entities, **per}, {**fixed, **per_fixed})
        controls[control] = {"ok": not c_refused, "refused_by": c_refused,
                             "entities": per, "fixed": per_fixed}
    controls_refused = all(not c["ok"] for c in controls.values())
    return {"ok": not refused and controls_refused, "refused_by": refused,
            "controls_refused": controls_refused, "objective": objective,
            "entities": entities, "fixed": fixed, "validation": scored,
            "controls": controls}
