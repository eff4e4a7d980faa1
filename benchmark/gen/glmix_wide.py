"""Seeded data of the GLMix job-recommendation model (Zhang et al., KDD 2016,
section 2): g(E[y_mjt]) = x_mjt'b + s_j'alpha_m + q_m'beta_j.

A row is one (member m, job j) impression. Three feature shards:

- ``match``: dense match features x_mjt of the row, intercept last;
- ``job_f``: the JOB's sparse features s_j (every row of job j carries the
  same ``nnz`` feature ids and values), intercept last: what the per-member
  model alpha_m is over;
- ``member_f``: the MEMBER's sparse features q_m, intercept last: what the
  per-job model beta_j is over.

What is fixed and what the seed draws. The PATTERN — which member and job
each row belongs to, in which order the rows come, and which feature ids
each job / member has — is drawn from `PATTERN_SEED`; ``seed`` draws the
feature VALUES (never zero), the planted effects and the labels. A
random-effect bucket's shape is (entities, capped rows, features the capped
rows touch): all three follow from the pattern alone, so every seed runs the
same compiled programs on the same amount of work (PERF.md section 4). The
rows are NOT reordered by the seed, unlike `gen/sparse.py`: the active-row
cap keeps each entity's rows by position, so another order would keep other
rows and change the widths.

Planted truth: fixed weights; per-member and per-job intercepts; and a
rank-`RANK` member x job interaction on each side, alpha_m = A u_m and
beta_j = B v_j, so s_j'alpha_m = (s_j'A) u_m is computed without a dense
(members, features) table.

The match features are CORRELATED and on different scales, as similarity
scores of one member-job pair are: x = z·M with z standard normal and M
symmetric with singular values spread geometrically over `MATCH_SCALES`.
The planted signal is z·c, so it lies as much along the weak directions as
along the strong ones. That is an ASSUMPTION about match features (raw
similarity scores are neither whitened nor on one scale), not a figure of
the source; what it changes in the cell is that the fixed effect's 20
iterations are iterations that move (on independent unit-scale features
L-BFGS is at f32 resolution after ~5, and a fixed-depth solve repeats its
last point for the other 15).
"""
from __future__ import annotations

import os

import numpy as np

PATTERN_SEED = 20240927
RANK = 8            # of the planted member x job interactions
INTERCEPT_SD = 1.0  # of the planted per-entity intercepts
FIXED_SD = 0.3      # of the planted fixed weights (on z)
MATCH_SCALES = 30.0  # largest over smallest scale of the match features


def zipf_probabilities(n: int, exponent: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    return p / p.sum()


def _feature_ids(rng, entities: int, features: int, nnz: int,
                 exponent: float) -> np.ndarray:
    """(entities, nnz) DISTINCT feature ids a row, sorted, feature popularity
    zipf(`exponent`): every row keeps drawing from the distribution, a
    repeat is thrown away, until it holds `nnz` — which is sampling without
    replacement, one draw of all unfinished rows at a time."""
    cdf = np.cumsum(zipf_probabilities(features, exponent))
    out = np.zeros((entities, nnz), np.int32)
    seen = np.zeros((entities, features), bool)
    have = np.zeros(entities, np.int64)
    todo = np.arange(entities)
    while todo.size:
        draw = np.minimum(np.searchsorted(cdf, rng.random(todo.size)),
                          features - 1)
        new = ~seen[todo, draw]
        rows, ids = todo[new], draw[new]
        seen[rows, ids] = True
        out[rows, have[rows]] = ids
        have[rows] += 1
        todo = todo[have[todo] < nnz]
    return np.sort(out, axis=1)


def pattern(config: dict, cache_dir: str) -> dict:
    """{"member", "job": (n_train,) int32 ids; "val_member", "val_job":
    (n_validation,); "job_ids": (n_items, nnz); "member_ids": (n_users,
    nnz)}, drawn once from `PATTERN_SEED` and kept in `cache_dir`."""
    n, nv = int(config["n_train_rows"]), int(config["n_validation_rows"])
    users, items = int(config["n_users"]), int(config["n_items"])
    feats, nnz = int(config["re_features"]), int(config["re_nnz_per_row"])
    zu, zi = float(config["user_zipf"]), float(config["item_zipf"])
    zf = float(config["feature_zipf"])
    path = os.path.join(
        cache_dir, f"glmix-wide-{PATTERN_SEED}-{n}-{nv}-{users}-{items}-"
        f"{feats}x{nnz}-{zu}-{zi}-{zf}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    rng = np.random.default_rng(PATTERN_SEED)
    # popularity rank -> entity id is itself shuffled, so a dense id says
    # nothing about an entity's size
    pu = zipf_probabilities(users, zu)[rng.permutation(users)]
    pi = zipf_probabilities(items, zi)[rng.permutation(items)]
    out = {
        "member": rng.choice(users, size=n, p=pu).astype(np.int32),
        "job": rng.choice(items, size=n, p=pi).astype(np.int32),
        "val_member": rng.choice(users, size=nv, p=pu).astype(np.int32),
        "val_job": rng.choice(items, size=nv, p=pi).astype(np.int32),
        "job_ids": _feature_ids(rng, items, feats, nnz, zf),
        "member_ids": _feature_ids(rng, users, feats, nnz, zf),
    }
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **out)
    os.replace(tmp, path)  # a run that is cut leaves no half-written file
    return out


def _values(rng, shape) -> np.ndarray:
    """Feature values in ±[0.5, 1.5): never zero, so the features an
    entity's rows touch are the pattern's whatever the seed."""
    sign = np.where(rng.random(shape) < 0.5, -1.0, 1.0)
    return (sign * (0.5 + rng.random(shape))).astype(np.float32)


def draw(config: dict, seed: int, pat: dict) -> dict:
    """The training and validation arrays of one seed:

    {"train" | "validation": {"y", "match" (n, d+1) f32, "job_f" |
    "member_f": (indices (n, nnz+1) int32, values (n, nnz+1) f32),
    "member", "job"}}; every shard's last column is the intercept."""
    users, items = int(config["n_users"]), int(config["n_items"])
    feats, nnz = int(config["re_features"]), int(config["re_nnz_per_row"])
    d_fixed = int(config["fixed_features"])
    rng = np.random.default_rng(seed)
    job_val, member_val = (_values(rng, (items, nnz)),
                           _values(rng, (users, nnz)))
    c = (rng.normal(size=d_fixed) * FIXED_SD).astype(np.float32)
    b0 = np.float32(-0.5)
    Q, _ = np.linalg.qr(np.random.default_rng(PATTERN_SEED).normal(
        size=(d_fixed, d_fixed)))
    scales = MATCH_SCALES ** (-np.arange(d_fixed) / max(d_fixed - 1, 1))
    mix = ((Q * scales) @ Q.T).astype(np.float32)
    a_m = (rng.normal(size=users) * INTERCEPT_SD).astype(np.float32)
    b_j = (rng.normal(size=items) * INTERCEPT_SD).astype(np.float32)
    A = rng.normal(size=(feats, RANK)).astype(np.float32)
    B = rng.normal(size=(feats, RANK)).astype(np.float32)
    u = (rng.normal(size=(users, RANK)) / np.sqrt(nnz)).astype(np.float32)
    v = (rng.normal(size=(items, RANK)) / np.sqrt(nnz)).astype(np.float32)
    # s_j'A and q_m'B: each entity's features folded to RANK numbers
    job_emb = np.einsum("jk,jkr->jr", job_val, A[pat["job_ids"]])
    member_emb = np.einsum("mk,mkr->mr", member_val, B[pat["member_ids"]])

    def with_intercept(ids, val):
        n = ids.shape[0]
        return (np.concatenate([ids, np.full((n, 1), feats, np.int32)], 1),
                np.concatenate([val, np.ones((n, 1), np.float32)], 1))

    job_rows = with_intercept(pat["job_ids"], job_val)
    member_rows = with_intercept(pat["member_ids"], member_val)
    out = {}
    for part, (mk, jk, n) in {
            "train": ("member", "job", int(config["n_train_rows"])),
            "validation": ("val_member", "val_job",
                           int(config["n_validation_rows"]))}.items():
        m, j = pat[mk], pat[jk]
        Z = rng.standard_normal(size=(n, d_fixed), dtype=np.float32)
        margin = (Z @ c + b0 + a_m[m] + b_j[j]
                  + np.einsum("nr,nr->n", job_emb[j], u[m])
                  + np.einsum("nr,nr->n", member_emb[m], v[j]))
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
        out[part] = {
            "y": y, "member": m, "job": j,
            "match": np.concatenate([Z @ mix, np.ones((n, 1), np.float32)],
                                    axis=1),
            "job_f": (job_rows[0][j], job_rows[1][j]),
            "member_f": (member_rows[0][m], member_rows[1][m])}
    return out
