"""Two days of the GLMix job-recommendation model from one pattern: the
data of an incremental refresh (Photon-ML's incremental training: yesterday's
model, its coefficient means and variances, is today's prior).

The PATTERN — which member and job each row belongs to, the order of the
rows, each job's and member's feature ids — is `gen/glmix_wide.py`'s, drawn
once from its fixed seed; so is the set of entities NEW since day 0
(`new_entities`, from `NEW_SEED`): a share of the members and of the jobs
that the prior model has no row for. Both days therefore have the bucket
shapes of every other seed's.

``seed`` draws the planted effects, which BOTH days share (the members and
jobs are the same people and postings a day later), and, each day from a
stream of its own, the feature VALUES (never zero), the match features and
the labels: day 1's are drawn anew, not copied from day 0.
"""
from __future__ import annotations

import numpy as np

from benchmark.gen import glmix_wide

NEW_SEED = 20241015  # the members and jobs new since day 0


def _planted(config: dict, rng) -> dict:
    """The truth both days share: fixed weights, per-entity intercepts,
    the rank-`glmix_wide.RANK` member x job interactions."""
    users, items = int(config["n_users"]), int(config["n_items"])
    feats, nnz = int(config["re_features"]), int(config["re_nnz_per_row"])
    d_fixed = int(config["fixed_features"])
    rank = glmix_wide.RANK
    Q, _ = np.linalg.qr(np.random.default_rng(glmix_wide.PATTERN_SEED).normal(
        size=(d_fixed, d_fixed)))
    scales = glmix_wide.MATCH_SCALES ** (
        -np.arange(d_fixed) / max(d_fixed - 1, 1))
    return {
        "c": (rng.normal(size=d_fixed) * glmix_wide.FIXED_SD
              ).astype(np.float32),
        "mix": ((Q * scales) @ Q.T).astype(np.float32),
        "a_m": (rng.normal(size=users) * glmix_wide.INTERCEPT_SD
                ).astype(np.float32),
        "b_j": (rng.normal(size=items) * glmix_wide.INTERCEPT_SD
                ).astype(np.float32),
        "A": rng.normal(size=(feats, rank)).astype(np.float32),
        "B": rng.normal(size=(feats, rank)).astype(np.float32),
        "u": (rng.normal(size=(users, rank)) / np.sqrt(nnz)
              ).astype(np.float32),
        "v": (rng.normal(size=(items, rank)) / np.sqrt(nnz)
              ).astype(np.float32),
    }


def _day(config: dict, pat: dict, truth: dict, rng, parts: dict) -> dict:
    """One day's arrays, as `glmix_wide.draw` lays them out, over the
    planted ``truth`` and with values from ``rng``."""
    users, items = int(config["n_users"]), int(config["n_items"])
    feats, nnz = int(config["re_features"]), int(config["re_nnz_per_row"])
    d_fixed = int(config["fixed_features"])
    job_val = glmix_wide._values(rng, (items, nnz))
    member_val = glmix_wide._values(rng, (users, nnz))
    job_emb = np.einsum("jk,jkr->jr", job_val, truth["A"][pat["job_ids"]])
    member_emb = np.einsum("mk,mkr->mr", member_val,
                           truth["B"][pat["member_ids"]])

    def with_intercept(ids, val):
        n = ids.shape[0]
        return (np.concatenate([ids, np.full((n, 1), feats, np.int32)], 1),
                np.concatenate([val, np.ones((n, 1), np.float32)], 1))

    job_rows = with_intercept(pat["job_ids"], job_val)
    member_rows = with_intercept(pat["member_ids"], member_val)
    out = {}
    for part, (mk, jk, n) in parts.items():
        m, j = pat[mk], pat[jk]
        Z = rng.standard_normal(size=(n, d_fixed), dtype=np.float32)
        margin = (Z @ truth["c"] - 0.5 + truth["a_m"][m] + truth["b_j"][j]
                  + np.einsum("nr,nr->n", job_emb[j], truth["u"][m])
                  + np.einsum("nr,nr->n", member_emb[m], truth["v"][j]))
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
        out[part] = {
            "y": y, "member": m, "job": j,
            "match": np.concatenate([Z @ truth["mix"],
                                     np.ones((n, 1), np.float32)], axis=1),
            "job_f": (job_rows[0][j], job_rows[1][j]),
            "member_f": (member_rows[0][m], member_rows[1][m])}
    return out


def draw_days(config: dict, seed: int, pat: dict) -> dict:
    """{"day0": {"train"}, "day1": {"train", "validation"}}, each part laid
    out as `glmix_wide.draw`'s; the planted effects shared, every value and
    label of a day from a stream of its own."""
    planted, day0, day1 = (np.random.default_rng(s) for s in
                           np.random.SeedSequence(int(seed)).spawn(3))
    truth = _planted(config, planted)
    train = ("member", "job", int(config["n_train_rows"]))
    return {
        "day0": _day(config, pat, truth, day0, {"train": train}),
        "day1": _day(config, pat, truth, day1, {
            "train": train,
            "validation": ("val_member", "val_job",
                           int(config["n_validation_rows"]))})}


def new_entities(config: dict, pat: dict) -> dict:
    """{"member", "job": sorted ids} new since day 0: `new_user_share` of
    the members and `new_item_share` of the jobs that have training rows,
    drawn from `NEW_SEED` alone, so that every seed drops the same ones."""
    rng = np.random.default_rng(NEW_SEED)
    out = {}
    for key, share in (("member", config["new_user_share"]),
                       ("job", config["new_item_share"])):
        ids = np.unique(pat[key])
        out[key] = np.sort(rng.choice(ids, size=int(round(share * len(ids))),
                                      replace=False))
    return out
