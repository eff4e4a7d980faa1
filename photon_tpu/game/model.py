"""GAME model containers.

Reference parity: com.linkedin.photon.ml.model.{GameModel, FixedEffectModel,
RandomEffectModel, Coefficients}. The reference stores a RandomEffectModel as
an RDD of (entityId -> GeneralizedLinearModel); here it is one dense
(num_entities, d) coefficient matrix + a key→row index — scoring a batch of
rows is a single gather + rowwise dot instead of a per-entity join.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu import telemetry
from photon_tpu.data.matrix import Matrix, SparseRows
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.ops.losses import TaskType, mean_fn


@dataclasses.dataclass(frozen=True)
class FixedEffectModel:
    """Reference: model.FixedEffectModel (one GLM + its feature shard)."""

    model: GeneralizedLinearModel
    feature_shard: str

    @property
    def task(self) -> TaskType:
        return self.model.task

    def score(self, X: Matrix) -> jax.Array:
        return self.model.score(X)


def _padded_coeffs(coefficients, dense_ids):
    """(n, d) per-row coefficient gather; id == E selects the appended zero
    row — THE unseen-entity convention, shared by scoring and the
    incremental-prior path (coeffs_for)."""
    d = coefficients.shape[1]
    padded = jnp.concatenate(
        [coefficients, jnp.zeros((1, d), coefficients.dtype)])
    return padded[dense_ids]


@jax.jit
def _re_score_jit(coefficients, X, dense_ids):
    return score_entities(X, coefficients, dense_ids)


def score_entities(X: Matrix, coefficients: jax.Array,
                   dense_ids: jax.Array, exact: bool = False) -> jax.Array:
    """Rowwise margin x_i · coefficients[id_i]; id == E (unseen) scores 0.

    A SparseRows shard reads its k coefficients a row straight from the
    (E, d) table — the (n, d) per-row copy `_padded_coeffs` makes is never
    built (at d in the thousands it is the table many times over); the
    out-of-range id E gathers the fill value, which is the zero row.

    ``exact``: the k products a row as an f32 multiply and a sum. The
    default is the einsum the serving rungs mirror bit for bit — a batched
    dot, which a TPU compiler MAY run on the MXU at its default precision
    (both factors rounded to bf16, 2^-9 a product). A coordinate-descent
    update asks for exact margins, because the other coordinates train
    against them as offsets. A pin, not a repair: on the v5e the installed
    compiler runs the einsum as the same f32 multiply-and-sum, to the bit
    (PERF.md section 6, PR 27)."""
    with telemetry.device_scope("game_re.score"):
        if isinstance(X, SparseRows):
            gathered = coefficients.at[dense_ids[:, None], X.indices].get(
                mode="fill", fill_value=0)
            if exact:
                return jnp.sum(X.values.astype(jnp.float32) * gathered,
                               axis=-1)
            return jnp.einsum("nk,nk->n", X.values, gathered)
        return score_rows(X, _padded_coeffs(coefficients, dense_ids))


def score_lanes(solutions: tuple, values: jax.Array,
                positions: jax.Array) -> jax.Array:
    """(n, G) margins of G regularization-grid lanes out of a coordinate's
    buckets' (E_b, width_b, G) solutions laid end to end: a row's k slots
    (``values``) read their coefficients at ``positions``
    (`RandomEffectDataset.lane_positions`), ONE gathered index a slot for
    all G lanes; one past the end reads 0. An f32 multiply and sum, as
    `score_entities` with ``exact``. No (n, d) copy: a row reads k
    coefficients (a dense shard: its d)."""
    with telemetry.device_scope("game_re.score"):
        G = solutions[0].shape[-1]
        flat = jnp.concatenate([w.reshape(-1, G) for w in solutions])
        gathered = flat.at[positions].get(mode="fill", fill_value=0)
        return jnp.sum(values.astype(jnp.float32)[..., None] * gathered,
                       axis=1)


def score_rows(X: Matrix, coeff_rows: jax.Array) -> jax.Array:
    """Rowwise margin x_i · c_i with a per-row coefficient vector (n, d)."""
    if isinstance(X, SparseRows):
        gathered = jnp.take_along_axis(coeff_rows, X.indices, axis=1)
        return jnp.einsum("nk,nk->n", X.values, gathered)
    return jnp.einsum("nd,nd->n", X, coeff_rows)


@dataclasses.dataclass(frozen=True)
class RandomEffectModel:
    """Per-entity coefficient matrix (reference: model.RandomEffectModel).

    Row i of `coefficients` belongs to `entity_keys[i]`; entities unseen at
    training time score 0 (the reference's behavior for missing REModels).
    """

    entity_name: str
    feature_shard: str
    task: TaskType
    coefficients: jax.Array  # (E, d)
    entity_keys: np.ndarray  # (E,) raw keys
    key_to_index: dict
    variances: Optional[jax.Array] = None  # (E, d) or None

    @property
    def n_entities(self) -> int:
        return int(self.coefficients.shape[0])

    @property
    def dim(self) -> int:
        return int(self.coefficients.shape[1])

    def dense_ids(self, raw_ids: np.ndarray) -> np.ndarray:
        """Raw entity keys → dense row ids; unseen keys map to E (zero row).

        Vectorized via searchsorted — entity_keys comes from np.unique and is
        sorted, so the lookup is O(n log E) numpy, not an O(n) Python loop.
        """
        return entity_dense_ids(self.entity_keys, raw_ids)

    def coeffs_for(self, dense_ids) -> jax.Array:
        """(n, d) per-row coefficients; id == E selects the zero row."""
        return _padded_coeffs(self.coefficients, jnp.asarray(dense_ids))

    def score(self, X: Matrix, dense_ids) -> jax.Array:
        return _re_score_jit(self.coefficients, X, jnp.asarray(dense_ids))

    def model_for(self, key) -> GeneralizedLinearModel:
        """Single entity's GLM view (reference: RandomEffectModel.getModel)."""
        i = self.key_to_index[key]
        var = None if self.variances is None else self.variances[i]
        return GeneralizedLinearModel(Coefficients(self.coefficients[i], var), self.task)


def entity_dense_ids(entity_keys, raw_ids) -> np.ndarray:
    """`RandomEffectModel.dense_ids` over the sorted ``entity_keys`` of a
    table: raw keys → dense row ids, unseen keys → E (the zero row)."""
    raw = np.asarray(raw_ids)
    keys = np.asarray(entity_keys)
    if raw.dtype.kind != keys.dtype.kind:
        # Cross-kind lookup (e.g. int ids vs str keys): promote to str
        # rather than casting into keys' dtype — a fixed-width unicode
        # cast would TRUNCATE unseen longer ids into colliding with real
        # entities. Same-kind strings compare fine across widths.
        if keys.dtype.kind in "US":
            raw = raw.astype(np.str_)
        else:
            raw = raw.astype(keys.dtype)
    pos = np.searchsorted(keys, raw)
    pos_c = np.clip(pos, 0, len(keys) - 1)
    found = keys[pos_c] == raw
    return np.where(found, pos_c, len(keys)).astype(np.int32)


CoordinateModel = Union[FixedEffectModel, RandomEffectModel]


@dataclasses.dataclass(frozen=True)
class GameModel:
    """Ordered coordinate-name → model map (reference: model.GameModel)."""

    coordinates: dict  # name -> CoordinateModel (insertion-ordered)
    task: TaskType

    def __getitem__(self, name: str) -> CoordinateModel:
        return self.coordinates[name]

    def names(self):
        return list(self.coordinates)

    def mean(self, total_score: jax.Array) -> jax.Array:
        return mean_fn(self.task)(total_score)
