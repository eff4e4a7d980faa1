"""Whole GLMix fits through the training driver, back to back.

A unit is one `photon_tpu.drivers.train.main(["--config", ...])` call: Avro
files on disk → ingest → coordinate descent (fixed effect, per-user and
per-item random effects) → validation AUC → `best_model` saved, each into a
fresh ``output_dir``. The driver's config is the one chip_smoke.run_game
wrote for its ``train_w0`` run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import os
import time

import numpy as np

from benchmark.gen import flagship, reference

# the driver's own phase spans (telemetry → TraceAnnotation), as labels
GAP_LABELS = {"train.read": "fit.read", "train.validate": "fit.validate",
              "train.train": "fit.train", "train.save": "fit.save"}
# validation AUC: the driver's evaluator accumulates in f32 on the device,
# the reference ranks float64 margins built from the same saved f32
# coefficients; PR 21 measured 5e-8 between three such computations
AUC_ATOL = 1e-4
# the planted per-user / per-item effects carry most of the signal: a fit
# that learned them beats its own fixed effect alone (PR 21: 0.119)
AUC_LIFT = 0.05


@dataclasses.dataclass
class State:
    config: dict
    driver_config: dict   # the training driver's JSON, less output_dir
    work_dir: str
    validation: tuple     # (Xf, Xu, Xi, uid, iid, y) of the validation file
    clocks: dict
    facts: dict
    fits: int = 0


def setup(config: dict, params: dict, seed: int, dirs: dict) -> State:
    work_dir = dirs["work"]
    users, items = int(config["n_users"]), int(config["n_items"])
    truth = flagship.planted_truth(
        users, items, int(config["fixed_features"]),
        int(config["random_effect_features"]), seed)
    train = os.path.join(work_dir, "train.avro")
    val = os.path.join(work_dir, "val.avro")
    t0 = time.perf_counter()
    flagship.write_flagship_avro(train, int(config["n_train_rows"]), users,
                                 items, truth, seed + 1)
    flagship.write_flagship_avro(val, int(config["n_validation_rows"]),
                                 users, items, truth, seed + 2)
    t1 = time.perf_counter()
    validation = flagship.flagship_arrays(
        int(config["n_validation_rows"]), users, items, truth, seed + 2)
    driver_config = {
        "train_path": train, "validation_path": val,
        "feature_shards": flagship.FEATURE_SHARDS,
        "coordinates": config["coordinates"],
        "entity_fields": ["userId", "itemId"],
        "n_sweeps": int(config["n_sweeps"]),
        "evaluators": list(config["evaluators"]),
        "streaming": bool(params["streaming"]),
        "ingest_workers": int(params["ingest_workers"])}
    return State(config=config, driver_config=driver_config,
                 work_dir=work_dir, validation=validation,
                 clocks={"write_avro_s": t1 - t0}, facts={})


def unit(state: State, keep: bool = False) -> dict:
    """One whole fit; ``work`` is 1. The driver's own last step (the model
    written to disk, its summary line printed) closes the timing."""
    from photon_tpu.drivers import train as train_driver

    state.fits += 1
    tag = f"fit-{state.fits}"
    path = os.path.join(state.work_dir, f"{tag}.json")
    with open(path, "w") as f:
        json.dump({**state.driver_config,
                   "output_dir": os.path.join(state.work_dir, tag)}, f)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        train_driver.main(["--config", path])
    said = json.loads(buf.getvalue().strip().splitlines()[-1])
    auc = said["validation_score"]
    out = {"work": 1.0,
           "failed": auc is None or not bool(np.isfinite(auc))}
    if keep:
        out["evidence"] = {"model_dir": said["model_dir"], "auc": auc}
    return out


def metrics(state: State, units: list, elapsed_s: float) -> dict:
    """Seconds per whole fit over ALL the fits and ALL the time of the
    window (host clock)."""
    return {"fit_wall_s": elapsed_s / len(units)}


def _columns(index_map, names) -> list:
    cols = [index_map.get(n) for n in names]
    if min(cols) < 0:
        raise KeyError("a written feature is missing from the saved "
                       "model's index map")
    return cols


def _entity_rows(model, key_of, n: int) -> np.ndarray:
    """Row of `model.coefficients` for entity 0..n-1; -1 where the entity
    was never seen in training (its effect is then zero)."""
    rows = np.full(n, -1, np.int64)
    for i in range(n):
        rows[i] = model.key_to_index.get(key_of(i), -1)
    return rows


def check(state: State, evidence: dict) -> dict:
    """The driver's validation AUC against a float64 rank-AUC of
    Xf·w + Xu·u[user] + Xi·v[item], built from the SAVED coefficients and
    the validation rows as generated (not as the program read them), and
    the lift over the same model's fixed effect alone."""
    from photon_tpu.data.model_io import load_game_model

    model, index_maps = load_game_model(evidence["model_dir"])
    Xf, Xu, Xi, uid, iid, y = (np.asarray(a, np.float64) if a.dtype.kind
                               == "f" else a for a in state.validation)
    fixed = model.coordinates["fixed"]
    w = np.asarray(fixed.model.coefficients.means, np.float64)
    imap = index_maps["fixed"]
    cols = _columns(imap, [flagship.fixed_feature_name(j)
                           for j in range(Xf.shape[1])])
    margin_fixed = Xf @ w[cols] + w[imap.intercept_id]
    margin = margin_fixed.copy()
    for name, X, ids, key_of, n in (
            ("per_user", Xu, uid, flagship.user_key,
             int(state.config["n_users"])),
            ("per_item", Xi, iid, flagship.item_key,
             int(state.config["n_items"]))):
        coord = model.coordinates[name]
        cols = _columns(index_maps[name],
                        [flagship.random_feature_name(j)
                         for j in range(X.shape[1])])
        coef = np.asarray(coord.coefficients, np.float64)[:, cols]
        coef = np.concatenate([coef, np.zeros((1, coef.shape[1]))])
        rows = _entity_rows(coord, key_of, n)[ids]  # -1 → the zero row
        margin += np.einsum("nd,nd->n", X, coef[rows])
    auc = reference.rank_auc(margin, y)
    fixed_auc = reference.rank_auc(margin_fixed, y)
    finite = all(bool(np.all(np.isfinite(np.asarray(
        c.coefficients if hasattr(c, "coefficients")
        else c.model.coefficients.means)))) for c in
        model.coordinates.values())
    return {"ok": finite and abs(auc - evidence["auc"]) <= AUC_ATOL
            and auc - fixed_auc >= AUC_LIFT,
            "driver_auc": evidence["auc"], "reference_auc": auc,
            "fixed_effect_only_auc": fixed_auc, "finite": finite}
