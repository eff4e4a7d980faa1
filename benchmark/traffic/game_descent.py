"""Whole GLMix fits by coordinate descent on resident data, back to back.

A unit is one `GameEstimator.fit(data, validation)`: one grid point, cold
start, the update sequence fixed → per-member → per-job for ``n_sweeps``
sweeps, every coordinate `max_iters` L-BFGS iterations at tolerance 0, then
the validation AUC. The estimator is built from the training driver's own
JSON (`drivers.train.CoordinateSpec(**spec).coordinate_config()`), so the
config language is what is exercised; a program whose language cannot
express the configuration fails here, before any data is drawn. Avro ingest
and the model save are NOT in the unit (PERF.md section 7).

``work`` is rows × solver iterations TAKEN, as the program reports them:
n × iterations for a fixed-effect update, Σ over entities of
weight-carrying active rows × iterations for a random-effect update
(`RETrainStats.row_iterations`) — never the configured depth.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark.gen import glmix_reference as ref
from benchmark.gen import glmix_wide
from benchmark.gen.reference import rank_auc

GAP_LABELS = {"bench.fit": "fit", "bench.readback": "readback",
              "game.fit_point": "fit.descent",
              "game.validate_point": "fit.validate"}
SAMPLE_SEED = 20240927

# ---- limits of `check`. Each lies between two readings taken in the SAME
# run and printed beside it: what the fit reads, and what a control reads —
# the plain reference solved and evaluated with bfloat16 products in the
# fit's place (`ref.bf16`: the precision below the configuration's
# float32), or a coefficient table left at zero. `check` puts every control
# through the comparison the fit goes through, and a run is correct only if
# each control is refused. Readings: my chip runs of PR 27, the extremes
# over the seeds run (PERF.md section 4); "at zero": a table left at zero.
#
# (a) The last reported objective is an f32 sum of 2,097,152 per-row losses
# of f32 margins; the reference is float64 from the same returned
# coefficients. Fit 7.1e-6 to 8.3e-6 (the report is always the lower; 4e-9
# to 5e-8 at 16,384 rows); at zero 0.27 to 0.39. The bf16 control reads
# 5.3e-6 to 4.8e-5, row errors cancelling in the sum: this limit ties the
# report to the coefficients, the precision is VALUE_RTOL's to hold. The
# history has one entry per coordinate update, n_sweeps x the sequence: a
# skipped sweep is short.
OBJECTIVE_RTOL = 1e-4
# (b) The block the solves read against the reference's dense matrix of the
# same rows, as a share of the entity's largest value: f32 blocks store the
# generated f32 values as they are (a feature a row names twice is one f32
# addition, 6e-8). Fit 0 in every run; the control's bf16 blocks 2.6e-3 to
# 2.7e-3.
BLOCK_RTOL = 1e-5
# (b) An entity's own final objective AS ITS SOLVE COMPUTED IT
# (`RETrainStats.entity_values`) against the float64 objective of its row
# of the table on the reference's rows: first order in the precision of
# every product the solve consumed — its own and those of the margins it
# took as offsets — for the LAST coordinate of the sequence, the one solved
# at the offsets the returned model gives. Fit 5.5e-5 to 8.5e-5 over 9
# seeds; the reference solved and evaluated at bf16 4.7e-4 to 7.7e-4; at
# zero 0.37 to 0.43. The limit is the geometric middle of 8.5e-5 and
# 4.7e-4. The fit's reading is the chip's natural logarithm, not its
# products: `log` / `log1p` are good to 9e-5 rms, 2.6e-4 at the most, on
# this chip (`exp` and the sigmoid to 5e-6, the products at HIGHEST to
# 1e-7), so a logistic loss summed over 2 rows is off 4e-5 rms and over
# 128 rows 2e-6 — the worst of the sample is an entity of few rows. On
# the CPU the same shapes read 2.5e-7 (PERF.md section 6, PR 27).
VALUE_RTOL = 2e-4
# (b) The gradient left at the fit's coefficients over the gradient at 0,
# last coordinate: a solve that stalls on the objective it can compute
# leaves the square root of that objective's resolution, which on the chip
# is its logarithm's (above). Fit 2.2e-3 to 1.0e-2 (5e-4 to 1.1e-3 on the
# CPU, at these shapes and at the rehearsal's); at zero 1. The bf16 control
# reads 6.1e-3 to 2.2e-2, inside the fit's range: this limit holds the
# DEPTH of the solve.
GRAD_REL_LAST = 5e-2
# (b) An entity's objective at its row of the table, at the offsets the
# fit's other coordinates finally give, over the Newton optimum, less 1.
# The LAST coordinate was solved at exactly these offsets: fit 2.0e-6 to
# 4.1e-5, at zero 0.59 to 0.76. The EARLIER coordinates were solved before
# the later ones moved on: fit 3.2e-3 to 1.0e-2, at zero 0.67 to 0.97. No
# coordinate may read below the optimum. A table row must be zero outside
# the columns the entity's capped rows touch, and the rows it was trained
# on must be its own, as many as the cap allows: a skipped cap or a wrong
# index map fails one of those outright.
GAP_LAST = 2e-4
GAP_EARLIER = 5e-2
NOT_BELOW = 1e-9
# (c) as game_fit: the evaluator accumulates in f32 on the device, the
# reference ranks float64 margins of the same coefficients: fit 1.9e-7 at
# the most, a table at zero 0.033 to 0.052. The planted per-member and
# per-job effects carry most of the signal: the fit lifts the AUC 0.073 to
# 0.106 over the fixed effect alone; both tables at zero lift it 0.
AUC_ATOL = 1e-4
AUC_LIFT = 0.02


@dataclasses.dataclass
class State:
    config: dict
    params: dict
    estimator: object
    data: object          # GameData, shards resident
    validation: object
    arrays: dict          # the generated rows, on the host, for `check`
    rows: int
    clocks: dict
    facts: dict


def _game_data(part: dict, features: int):
    from photon_tpu.data.matrix import SparseRows
    from photon_tpu.game.dataset import GameData

    return GameData.build(
        part["y"],
        {"match": part["match"],
         "job_f": SparseRows(*part["job_f"], features + 1),
         "member_f": SparseRows(*part["member_f"], features + 1)},
        {"memberId": part["member"], "jobId": part["job"]})


def setup(config: dict, params: dict, seed: int, dirs: dict) -> State:
    from photon_tpu.drivers.train import CoordinateSpec
    from photon_tpu.game.estimator import GameEstimator
    from photon_tpu.ops.losses import TaskType

    # the driver's own config language first: a program that cannot
    # express the configuration stops here, in a second
    specs = {name: CoordinateSpec(**spec)
             for name, spec in config["coordinates"].items()}
    estimator = GameEstimator(
        TaskType[config["task"]],
        {name: spec.coordinate_config() for name, spec in specs.items()},
        update_sequence=list(config["update_sequence"]),
        n_sweeps=int(config["n_sweeps"]))
    t0 = time.perf_counter()
    pat = glmix_wide.pattern(config, dirs["shared"])
    t1 = time.perf_counter()
    arrays = glmix_wide.draw(config, seed, pat)
    t2 = time.perf_counter()
    features = int(config["re_features"])
    # training shards go in as host arrays: the datasets built from them in
    # the warm-up fit place what a fit reads on the device, once
    data = _game_data(arrays["train"], features)
    validation = _game_data(arrays["validation"], features).to_device()
    return State(config=config, params=params, estimator=estimator,
                 data=data, validation=validation, arrays=arrays,
                 rows=int(config["n_train_rows"]),
                 clocks={"pattern_s": t1 - t0, "generate_s": t2 - t1},
                 facts={})


def _fit(state: State):
    import jax

    from photon_tpu.game.random_effect import RETrainStats

    with jax.profiler.TraceAnnotation("bench.fit"):
        (result,) = state.estimator.fit(state.data, state.validation)
    stats = result.descent.coordinate_stats
    fixed = [s for per in stats.values() for s in per
             if not isinstance(s, RETrainStats)]
    with jax.profiler.TraceAnnotation("bench.readback"):
        fixed_iters = jax.device_get([s.iterations for s in fixed])
    re_stats = [s for per in stats.values() for s in per
                if isinstance(s, RETrainStats)]
    work = (float(state.rows) * float(np.sum(fixed_iters))
            + sum(s.row_iterations for s in re_stats))
    history = result.descent.objective_history
    auc = result.validation_score
    out = {"work": work, "updates": len(history),
           "re_updates": len(re_stats),
           "fixed_iterations": int(np.sum(fixed_iters)),
           "re_iterations": int(sum(s.total_iterations for s in re_stats)),
           "failed": auc is None or not bool(np.isfinite(auc))
           or not bool(np.all(np.isfinite(history)))}
    return result, out


def unit(state: State, keep: bool = False) -> dict:
    """One whole fit. The warm-up (``keep``) runs with the program's
    telemetry attached, as the traced units do, so that every small
    program a counter dispatches is built before the window; it also keeps
    the dataset builds' byte counters and, for `check`, the model on the
    host."""
    if not keep:
        return _fit(state)[1]
    from photon_tpu import telemetry

    t0 = time.perf_counter()
    with telemetry.run("game_descent.warmup") as run:
        result, out = _fit(state)
        report = run.report_compact()
        counters = report["counters"]
    state.clocks["first_fit_s"] = time.perf_counter() - t0
    builds = [v for k, v in report["span_totals"].items()
              if k.split("/")[-1] == "game_re.build"]
    if builds:  # a program from before the span reports none
        state.clocks["re_dataset_build_s"] = sum(builds)
    state.facts["build_counters"] = {
        k: v for k, v in counters.items() if k.startswith("game_re.block_")}
    datasets = state.estimator.datasets(state.data)
    state.facts["blocks"] = {
        name: [(b.n_entities, b.m, b.dim) for b in ds.blocks]
        for name, ds in datasets.items() if hasattr(ds, "blocks")}
    model = result.model
    out["evidence"] = {
        "history": [float(v) for v in result.descent.objective_history],
        "auc": result.validation_score,
        "fixed": {n: np.asarray(m.model.coefficients.means)
                  for n, m in model.coordinates.items()
                  if hasattr(m, "model")},
        "tables": {n: np.asarray(m.coefficients)
                   for n, m in model.coordinates.items()
                   if hasattr(m, "coefficients")},
        # each entity's final objective as its own last solve computed it
        "values": {n: np.asarray(per[-1].entity_values)
                   for n, per in result.descent.coordinate_stats.items()
                   if getattr(per[-1], "entity_values", None) is not None}}
    return out


def metrics(state: State, units: list, elapsed_s: float) -> dict:
    """rows·iterations per second over ALL the work and ALL the time of the
    window (host clock; every fit closed by its own readbacks)."""
    return {"rows_iters_per_s": sum(r["work"] for _, r in units) / elapsed_s}


# ------------------------------------------------------------------- check
def _ids(rows: dict, spec: dict) -> np.ndarray:
    """The generated entity ids a random-effect coordinate groups by."""
    return rows["member" if spec["entity_name"] == "memberId" else "job"]


def _margins(state: State, part: str, evidence: dict,
             rd=lambda a: a) -> dict:
    """{coordinate: (n,) float64 margin} of the returned coefficients on the
    generated rows of ``part``; ``rd`` rounds the factors of every product
    (`ref.bf16`: the lower-precision control)."""
    rows = state.arrays[part]
    out = {}
    for name, spec in state.config["coordinates"].items():
        if "entity_name" not in spec:
            out[name] = rd(rows[spec["feature_shard"]].astype(np.float64)) \
                @ rd(evidence["fixed"][name].astype(np.float64))
        else:
            out[name] = ref.sparse_margins(
                *rows[spec["feature_shard"]], evidence["tables"][name],
                ref.table_rows(_ids(state.arrays["train"], spec),
                               _ids(rows, spec)), rd)
    return out


def _sampled(block, per_bucket: int, rng) -> np.ndarray:
    """Positions in a block: `per_bucket` drawn, and the widest."""
    n = block.n_entities
    picks = rng.choice(n, size=min(per_bucket, n), replace=False)
    width = (block.proj.proj_mask.sum(axis=1) if block.proj is not None
             else np.zeros(n))
    return np.unique(np.append(picks, int(np.argmax(width))))


def _block_as_stored(cols, X, m: int, p: int, intercept: int):
    """The (m, p) projected block the reference's (r, c) matrix over sorted
    feature ids ``cols`` should be stored as: its columns first, in order,
    the intercept's pinned last, zero rows and columns for the padding."""
    out = np.zeros((m, p))
    feats = cols != intercept
    out[:X.shape[0], :int(feats.sum())] = X[:, feats]
    if not feats.all():
        out[:X.shape[0], -1] = X[:, ~feats][:, 0]
    return out


def _worse(worst: dict, **readings) -> None:
    for key, value in readings.items():
        worst[key] = max(worst.get(key, -np.inf), float(value))


def _entities_refused_by(r: dict, last: bool) -> list:
    """The limits of (b) that one coordinate's worst readings break."""
    broke = [key for key, limit in (
        ("cap_errors", 0), ("outside", 0), ("block_diff", BLOCK_RTOL),
        ("below", NOT_BELOW),
        ("gap", GAP_LAST if last else GAP_EARLIER))
        if r.get(key, 0) > limit]
    if last:
        broke += [key for key, limit in (("value_rel", VALUE_RTOL),
                                         ("grad_rel", GRAD_REL_LAST))
                  if not r.get(key, np.inf) <= limit]
    return broke


def check_entities(state: State, evidence: dict, train_margins: dict) -> tuple:
    """(b): for a seeded sample of every bucket of every random-effect
    coordinate — the rows an entity is trained on are its own, as many as
    the cap allows; the block the solves read holds exactly those rows'
    values, through the bucket's index map; the fit's row of the table
    against the plain Newton optimum on those rows and the columns they
    touch; for the last coordinate, the objective its own solve reported.

    Returns ({coordinate: the fit's worst readings}, {control: {coordinate:
    the control's worst readings}}): beside each entity's readings those of
    its row LEFT AT ZERO, and for the last coordinate those of the
    reference solved and evaluated with bf16 products, over bf16 blocks."""
    rows = state.arrays["train"]
    sequence = list(state.config["update_sequence"])
    datasets = state.estimator.datasets(state.data)
    rng = np.random.default_rng(SAMPLE_SEED)
    total = sum(train_margins.values())
    per_bucket = int(state.params["sample_per_bucket"])
    out, controls = {}, {"bf16": {}}
    for name, spec in state.config["coordinates"].items():
        if "entity_name" not in spec:
            continue
        ds = datasets[name]
        ind, val = rows[spec["feature_shard"]]
        ids = _ids(rows, spec)
        counts = np.bincount(ids, minlength=ds.n_entities)
        offsets = total - train_margins[name]
        table = evidence["tables"][name]
        values = evidence["values"].get(name)
        l2 = float(spec["reg_weight"])
        cap = spec.get("active_cap")
        last = name == sequence[-1]
        worst = {"outside": 0, "cap_errors": 0, "entities": 0}
        zero, low = {}, {}
        for block in ds.blocks:
            row_index = np.asarray(block.row_index)
            real = np.asarray(block.weights) != 0.0
            picks = _sampled(block, per_bucket, rng)
            stored = (np.asarray(block.X[picks]) if block.proj is not None
                      else None)  # one device read a bucket
            for at, pos in enumerate(picks):
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
                        _worse(into, block_diff=np.abs(
                            kept - _block_as_stored(
                                cols, X, block.m, block.dim, ds.dim - 1)
                        ).max() / scale)
                y, offs = rows["y"][r].astype(np.float64), offsets[r]
                w_fit = table[e, cols].astype(np.float64)
                worst["outside"] += int(np.count_nonzero(table[e])
                                        - np.count_nonzero(w_fit))
                worst["entities"] += 1
                _, best, _ = ref.newton(X, y, offs, l2)
                g0 = np.linalg.norm(ref.gradient(
                    X, y, offs, np.zeros_like(w_fit), l2))
                said = None if values is None else float(values[e])
                cases = [(worst, w_fit, said),
                         (zero, np.zeros_like(w_fit), said)]
                if last:  # the reference at the precision below, in place
                    w_low, said_low, _ = ref.newton(X, y, offs, l2, ref.bf16)
                    cases.append((low, w_low, said_low))
                for into, w, reported in cases:
                    at_w = ref.objective(X, y, offs, w, l2)
                    _worse(into, gap=(at_w - best) / best,
                           below=(best - at_w) / best,
                           grad_rel=np.linalg.norm(
                               ref.gradient(X, y, offs, w, l2)) / g0)
                    if reported is not None:
                        _worse(into, value_rel=abs(reported - at_w) / at_w)
        out[name] = worst
        controls[f"zero_table.{name}"] = {name: zero}
        if last:
            controls["bf16"][name] = low
    return out, controls


def _objective(history, loss: float, rows: int, updates: int) -> dict:
    """(a) for one set of returned coefficients, whose float64 loss is
    ``loss``."""
    rises = np.diff(history) / history[:-1]
    out = {"reported": float(history[-1]), "reference": loss,
           "rel": abs(history[-1] - loss) / loss, "updates": len(history),
           "largest_rise": float(rises.max(initial=0.0))}
    out["ok"] = bool(out["rel"] <= OBJECTIVE_RTOL and len(history) == updates
                     and history[-1] < history[0]
                     and history[0] < rows * np.log(2.0))
    return out


def check(state: State, evidence: dict) -> dict:
    """(a) one entry of the objective history per coordinate update, the
    last of them the float64 loss of the returned coefficients; (b)
    `check_entities`; (c) the fit's AUC against a float64 rank-AUC of the
    same coefficients, and its lift over the fixed effect alone. (d), no
    program built inside the window, is the harness's.

    Then the CONTROLS, through the same comparisons: the reference at bf16
    in the fit's place (its products in the loss, its solves, its blocks),
    and each random-effect table left at zero with everything the fit
    reported kept. The run is correct only if every control is refused."""
    history = np.asarray(evidence["history"], np.float64)
    y, y_val = state.arrays["train"]["y"], state.arrays["validation"]["y"]
    updates = int(state.config["n_sweeps"]) * len(
        state.config["update_sequence"])
    train = _margins(state, "train", evidence)
    val = _margins(state, "validation", evidence)
    objective = _objective(history, ref.log_loss(sum(train.values()), y),
                           state.rows, updates)
    entities, control_entities = check_entities(state, evidence, train)
    last = state.config["update_sequence"][-1]
    for name, r in entities.items():
        r["refused_by"] = _entities_refused_by(r, name == last)
        r["ok"] = not r["refused_by"]
    fixed_auc = rank_auc(sum(v for n, v in val.items()
                             if n in evidence["fixed"]), y_val)

    def validation(margins: dict) -> dict:
        auc = rank_auc(sum(margins.values()), y_val)
        return {"fit_auc": evidence["auc"], "reference_auc": auc,
                "fixed_effect_only_auc": fixed_auc,
                "ok": bool(abs(auc - evidence["auc"]) <= AUC_ATOL
                           and auc - fixed_auc >= AUC_LIFT)}

    def refused_by(objective: dict, entities: dict, validation: dict) -> list:
        return ([] if objective["ok"] else ["objective"]) + [
            f"{name}.{key}" for name, r in entities.items()
            for key in r["refused_by"]] + (
            [] if validation["ok"] else ["validation"])

    scored = validation(val)
    refused = refused_by(objective, entities, scored)
    controls = {}
    for control, per in control_entities.items():
        for name, r in per.items():
            r["refused_by"] = _entities_refused_by(r, name == last)
        if control == "bf16":
            low = _margins(state, "train", evidence, ref.bf16)
            c_objective = _objective(
                history, ref.log_loss(sum(low.values()), y), state.rows,
                updates)
            c_scored = scored
        else:  # that coordinate scores 0 on every row
            (name,) = per
            c_objective = _objective(history, ref.log_loss(
                sum(v for n, v in train.items() if n != name), y),
                state.rows, updates)
            c_scored = validation({n: v for n, v in val.items()
                                   if n != name})
        c_refused = refused_by(c_objective, {**entities, **per}, c_scored)
        controls[control] = {
            "ok": not c_refused, "refused_by": c_refused,
            "objective_rel": c_objective["rel"],
            "reference_auc": c_scored["reference_auc"], "entities": per}
    controls_refused = all(not c["ok"] for c in controls.values())
    return {"ok": not refused and controls_refused, "refused_by": refused,
            "controls_refused": controls_refused, "objective": objective,
            "entities": entities, "validation": scored, "controls": controls}
