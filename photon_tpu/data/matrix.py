"""Design-matrix representations for TPU.

The reference stores examples as Breeze sparse/dense vectors inside Spark
partitions (com.linkedin.photon.ml.data.LabeledPoint). On TPU we need static
shapes, so two representations:

- dense: a plain (n, d) jnp array — matvecs hit the MXU directly.
- SparseRows: padded per-row COO — (n, k) int32 indices + (n, k) f32 values,
  rows padded to a fixed nnz-per-row k with (index 0, value 0). matvec is a
  gather + einsum; X^T r is a `segment_sum` scatter. This keeps shapes static
  for XLA while supporting the reference's 10M-feature regime, where a dense
  matrix is impossible.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# named device scopes (op metadata only): a profiler trace reads the X
# pass's share of a compiled solve by these names
from photon_tpu import telemetry
from photon_tpu.telemetry import device_scope


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("indices", "values"),
    meta_fields=("n_features",),
)
@dataclasses.dataclass(frozen=True)
class SparseRows:
    indices: jax.Array  # (n, k) int32, padded with 0
    values: jax.Array  # (n, k) f32, padded with 0.0
    n_features: int

    @property
    def shape(self):
        return (self.indices.shape[0], self.n_features)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("dense", "dense_cols", "tail_rows", "tail_cols",
                 "tail_vals"),
    meta_fields=("n_features",),
)
@dataclasses.dataclass(frozen=True)
class HybridRows:
    """Power-law hybrid: hot columns dense (MXU path), cold tail flat COO.

    On TPU, per-element gathers/scatters run at ~66M nnz/s while dense
    matmul streams at hundreds of GB/s — a dense column costs roughly as
    much as ONE sparse nnz per row. Real sparse feature spaces are
    power-law distributed, so routing the top-`d_sel` most frequent columns
    through a dense (n, d_sel) block covers most nnz at matmul speed and
    leaves only the long tail to the gather path. The tail is FLAT
    row-sorted COO (no per-row padding — padded slots cost as much as real
    nnz on the gather path). See `to_hybrid`.

    The reference has no analog (JVM sparse vectors are cheap to walk);
    this is the TPU-first representation of its 10M-feature regime.

    Residency contract: leaves may be HOST numpy (what `to_hybrid` builds —
    so callers can cast to bf16 before paying the transfer) or device
    arrays; `jax.device_put(hybrid)` moves the whole pytree once. Put it on
    device before repeated jitted use, or every call re-transfers the
    multi-GB dense block.
    """

    dense: jax.Array | np.ndarray       # (n, d_sel) hot-column values
    dense_cols: jax.Array | np.ndarray  # (d_sel,) original column ids
    tail_rows: jax.Array | np.ndarray   # (m,) int32 row ids, ascending
    tail_cols: jax.Array | np.ndarray   # (m,) int32 original column ids
    tail_vals: jax.Array | np.ndarray   # (m,) tail values (padding: 0.0)
    n_features: int

    @property
    def shape(self):
        return (self.dense.shape[0], self.n_features)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("dense", "dense_cols", "tail_rows", "tail_cols",
                 "tail_vals"),
    meta_fields=("n_features",),
)
@dataclasses.dataclass(frozen=True)
class ShardedHybridRows:
    """HybridRows laid out for a device mesh: per-shard flat-COO tails.

    A single HybridRows cannot row-shard over a mesh — its flat tail has
    arbitrary length per row range and global row ids. This layout fixes
    both: rows are split into `S` equal contiguous shards, each shard's
    tail is padded to one common length `m`, and tail row ids are LOCAL to
    the shard. The tail arrays are (S, m) with the shard axis leading, so
    sharding every data leaf's axis 0 over the mesh gives each device its
    own complete (dense block rows + local tail) piece — the tail gather/
    scatter never crosses devices; only the (d,) gradient psum does.

    Works in two views:
    - global (single device / plain jit): ops offset local row ids by
      `shard * n_local` — exactly equivalent to the unsharded HybridRows.
    - local (inside shard_map, leaves sliced to dense (n_local, d_sel) and
      tails (1, m)): `local()` squeezes the shard axis into a plain
      HybridRows; models.training routes mesh solves through this.

    Tail padding entries use (row = n_local-1, col = 0, val = 0): zero
    values contribute nothing, and padding with the LAST local row keeps
    each shard's row ids ascending for the sorted segment_sum in matvec.

    Residency contract: as HybridRows — `shard_hybrid` builds host numpy
    leaves (dense inherits the input's residency); models.training's
    `_sharded_prep` does the one device_put into the mesh sharding.
    """

    dense: jax.Array | np.ndarray       # (n, d_sel) hot-column values
    dense_cols: jax.Array | np.ndarray  # (d_sel,) original column ids
    tail_rows: jax.Array | np.ndarray   # (S, m) int32 LOCAL row ids, ascending
    tail_cols: jax.Array | np.ndarray   # (S, m) int32 original column ids
    tail_vals: jax.Array | np.ndarray   # (S, m) tail values (padding: 0.0)
    n_features: int

    @property
    def shape(self):
        return (self.dense.shape[0], self.n_features)

    @property
    def n_shards(self) -> int:
        return self.tail_rows.shape[0]

    @property
    def n_local(self) -> int:
        return self.dense.shape[0] // self.tail_rows.shape[0]

    def local(self) -> HybridRows:
        """The one-shard view (inside shard_map, where the shard axis has
        been sliced to length 1)."""
        return HybridRows(
            dense=self.dense,
            dense_cols=self.dense_cols,
            tail_rows=self.tail_rows[0],
            tail_cols=self.tail_cols[0],
            tail_vals=self.tail_vals[0],
            n_features=self.n_features,
        )

    def _global_tail(self):
        """(rows, cols, vals) flat with GLOBAL row ids, sorted ascending."""
        S, m = self.tail_rows.shape
        off = jnp.arange(S, dtype=jnp.int32) * self.n_local
        rows = (self.tail_rows + off[:, None]).reshape(-1)
        return rows, self.tail_cols.reshape(-1), self.tail_vals.reshape(-1)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("dense", "tail_pcols", "tail_vals", "row_bounds",
                 "bucket_rows", "bucket_vals", "perm_cols", "inv_perm"),
    meta_fields=("n_features", "n_prefix", "last_col_pos"),
)
@dataclasses.dataclass(frozen=True)
class PermutedHybridRows:
    """Scatter-free hybrid: hot columns dense, cold tail in a PERMUTED
    feature space whose layout makes both X passes scatter-free.

    Motivation (measured on v5e, docs/PERF.md): TPU gathers cost ~7 ns per
    index row regardless of row width, but scatter-adds cost ~12 ns per
    ELEMENT — a (nnz, G) lane-stacked segment_sum is G× a single lane, and
    even single-lane X passes are scatter-bound. This representation
    removes every per-nnz scatter from matvec and rmatvec while staying
    EXACT in R^d:

    - Columns are relabeled at build time: positions [0, d_sel) are the hot
      (most frequent) columns, [d_sel, P) the distinct tail columns GROUPED
      BY OCCURRENCE-COUNT BUCKET, and [P, d) the columns untouched by this
      batch (their X column is identically zero, so they contribute nothing
      to any X pass — they still exist in coefficient/optimizer state and
      feel regularization/prior terms exactly).
    - matvec: the hot block is one (n, d_sel) matmul against the CONTIGUOUS
      prefix slice w[:d_sel] (no dense_cols gather); the row-major flat
      tail gathers w per nnz and reduces per row via cumulative-sum
      differences over `row_bounds` — gathers only. (The cumsum pass adds
      f32 error ~1e-4·σ·√nnz on tail sums — below the bf16 hot-block
      storage quantization that dominates the representation's noise.)
    - rmatvec: the gradient is ASSEMBLED BY CONCATENATION: hot block
      (denseᵀ r), then each occurrence bucket's (c_b, k_b) row-index
      matrix gathers r and reduces over k_b giving that bucket's columns
      IN PREFIX ORDER, then zeros for the untouched suffix. No scatter.

    COORDINATE CONVENTION: matvec/rmatvec (and the whole solver stack)
    operate on PERMUTED-space vectors. `to_model_space` / `from_model_space`
    translate (one cheap gather); models/training does this at its public
    boundary, models/glm scoring translates per call — user-facing
    coefficient vectors are always in original column order.

    The reference has no analog (JVM sparse vectors are cheap to walk);
    upstream com.linkedin.photon.ml's 10M-feature regime maps here.
    """

    dense: jax.Array | np.ndarray       # (n, d_sel) hot-column values
    tail_pcols: jax.Array | np.ndarray  # (m,) int32 PERMUTED col ids, row-major
    tail_vals: jax.Array | np.ndarray   # (m,) tail values
    row_bounds: jax.Array | np.ndarray  # (n + 1,) int32 tail nnz bounds per row
    bucket_rows: tuple                  # per bucket: (c_b, k_b) int32 row ids
    bucket_vals: tuple                  # per bucket: (c_b, k_b) values
    perm_cols: jax.Array | np.ndarray   # (d,) original col id at each position
    inv_perm: jax.Array | np.ndarray    # (d,) position of each original col
    n_features: int
    n_prefix: int                       # P = d_sel + distinct tail columns
    last_col_pos: int                   # permuted position of original col d-1

    @property
    def shape(self):
        return (self.dense.shape[0], self.n_features)

    @property
    def d_sel(self) -> int:
        return self.dense.shape[1]

    def from_model_space(self, v):
        """Original-space (d,)-vector (or (d, ...) stack) → permuted space."""
        return jnp.asarray(v)[self.perm_cols]

    def to_model_space(self, w):
        """Permuted-space (d,)-vector (or (d, ...) stack) → original space."""
        return jnp.asarray(w)[self.inv_perm]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("dense", "tail_pcols", "tail_vals", "row_bounds",
                 "bucket_rows", "bucket_vals", "perm_cols", "inv_perm"),
    meta_fields=("n_features", "n_prefix", "last_col_pos"),
)
@dataclasses.dataclass(frozen=True)
class ShardedPermutedHybridRows:
    """PermutedHybridRows laid out for a device mesh: the multi-chip form
    of the scatter-free layout.

    Round 5 measured TPU scatter-adds as the sparse X-pass wall (~12 ns
    per ELEMENT vs ~7 ns per gather INDEX regardless of width —
    PermutedHybridRows docstring); ShardedHybridRows still pays them in
    every per-shard tail segment_sum. This layout gives each shard its
    own complete scatter-free piece: per-shard row-major flat tails
    (matvec's cumsum reduction) and per-shard occurrence-bucket matrices
    with LOCAL row ids (rmatvec's gather+reduce concatenation), under ONE
    GLOBAL column permutation so the (d,)-space solver state and the
    single gradient all-reduce stay aligned across shards. Inside
    shard_map, `local()` squeezes the shard axis into a plain
    PermutedHybridRows and the single-device ops run unchanged — the
    compiled per-evaluation pattern is ONE all-reduce, zero other
    collectives, zero scatters (pinned by tests/test_multihost.py).

    Scaling caveat (documented, not hidden): the hot dense block and the
    flat-tail matvec shard perfectly (per-device work ∝ n/S), but the
    bucket CONCATENATION does not — every shard must emit the full
    (P - d_sel,) tail-column block for the aligned psum, so its c_b axis
    is the GLOBAL distinct-tail-column count regardless of S (a column's
    absent shards carry zero-padded slots). Per-device bucket work is
    therefore ~the single-device cost, not 1/S of it; the layout wins
    where the hot block + lane-stacked scatters dominate (the measured
    regime for reg sweeps) and a column's bucket comes from its
    MAX-LOCAL occurrence count, so per-shard padding stays under a third
    of the slots of the column's fullest shard + one slot per absent
    shard.

    Works in two views like ShardedHybridRows: global (plain jit; ops
    vmap the shard axis) and local (inside shard_map via `local()`).
    Residency contract: host numpy leaves (dense inherits the builder
    input's residency); `models.training._sharded_prep` does the one
    device_put into the mesh sharding. COORDINATE CONVENTION as
    PermutedHybridRows: solver vectors live in permuted space;
    `to_model_space` / `from_model_space` translate at the public
    boundary.
    """

    dense: jax.Array | np.ndarray       # (n, d_sel) hot block, global rows
    tail_pcols: jax.Array | np.ndarray  # (S, m) int32 PERMUTED col ids
    tail_vals: jax.Array | np.ndarray   # (S, m) tail values (padding: 0)
    row_bounds: jax.Array | np.ndarray  # (S, n_local + 1) int32
    bucket_rows: tuple                  # per bucket: (S, c_b, k_b) LOCAL rows
    bucket_vals: tuple                  # per bucket: (S, c_b, k_b) values
    perm_cols: jax.Array | np.ndarray   # (d,) replicated
    inv_perm: jax.Array | np.ndarray    # (d,) replicated
    n_features: int
    n_prefix: int
    last_col_pos: int

    @property
    def shape(self):
        return (self.dense.shape[0], self.n_features)

    @property
    def d_sel(self) -> int:
        return self.dense.shape[1]

    @property
    def n_shards(self) -> int:
        return self.tail_pcols.shape[0]

    @property
    def n_local(self) -> int:
        return self.dense.shape[0] // self.tail_pcols.shape[0]

    def local(self) -> PermutedHybridRows:
        """The one-shard view (inside shard_map, where the shard axis has
        been sliced to length 1)."""
        return PermutedHybridRows(
            dense=self.dense,
            tail_pcols=self.tail_pcols[0],
            tail_vals=self.tail_vals[0],
            row_bounds=self.row_bounds[0],
            bucket_rows=tuple(b[0] for b in self.bucket_rows),
            bucket_vals=tuple(b[0] for b in self.bucket_vals),
            perm_cols=self.perm_cols,
            inv_perm=self.inv_perm,
            n_features=self.n_features,
            n_prefix=self.n_prefix,
            last_col_pos=self.last_col_pos,
        )

    def from_model_space(self, v):
        return jnp.asarray(v)[self.perm_cols]

    def to_model_space(self, w):
        return jnp.asarray(w)[self.inv_perm]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("dense", "ell_pcols", "ell_vals", "row_pos",
                 "bucket_rows", "bucket_vals", "perm_cols", "inv_perm",
                 "row_order"),
    meta_fields=("n_features", "n_prefix", "last_col_pos", "tail_nnz"),
)
@dataclasses.dataclass(frozen=True)
class BlockedEllRows:
    """Blocked-ELL hybrid: hot columns dense on the MXU, cold tail as
    nnz-bucketed ELL row blocks — gather-fused X passes with NO scans and
    NO scatters of any kind.

    PermutedHybridRows (round 5) removed the combining scatters but its
    matvec tail still rides a full-length `cumsum` over the flat tail plus
    a `row_bounds` boundary pass — a log-depth scan over every tail nnz,
    per X pass, per line-search direction. This layout replaces it with
    the classic blocked-ELL form: rows are bucketed by tail-nnz into a
    small set of widths — the rungs 1, 2, 3, 4, 6, 8, 12, 16, 24, … of
    the one width ladder (`_width_rungs`: every power of two and 3·2^(j−1)
    between 2^j and 2^(j+1)), a row in the smallest rung that holds it —
    each bucket is a dense (r_b, W_b) pair of permuted-column-id / value
    matrices, and the tail matvec is per bucket ONE gather of w plus ONE
    `einsum("rw,rw->r")` — a dense contraction XLA maps straight onto the
    vector/matrix units, f32 accumulation pinned by
    ``preferred_element_type``. Zero combining scatters, zero `.at[].set`
    scatters, zero cumsum — in BOTH X passes.

    ROW ORDER. A GLM objective is a sum over rows, so the order the rows
    are STORED in is free, and `to_blocked_ell` stores them in the order
    the bucket outputs concatenate in: width-1 rows, then width-2, ...,
    then the rows with no tail (original row id within a bucket). The
    forward tail is then `concatenate(bucket outputs + [zeros])` and
    `hot + tail` — no per-row gather in any evaluation. `row_order` (the
    original row id of each stored row; None = the caller's order) records
    it; it is fixed by the layout's builder, never by a caller, and which
    forward form runs follows from it alone. Everything that pairs with
    the rows of such a layout — a GLMBatch's y / weights / offsets, a
    cotangent handed to rmatvec — is in the STORED order
    (`data.dataset.make_batch` and `with_offsets` translate on the way
    in); `matvec` / `matvec_lanes` hand their result back in the CALLER's
    order through one `row_pos` gather, which scoring pays once a call
    and a solver (`layout_matvec`) never. The shard / chunk views
    (`ShardedBlockedEllRows.local()`, `.chunk(i)`) pad their buckets to a
    ladder shared across shards, so their rows stay in the caller's order
    and their forward tail keeps the `row_pos` gather per evaluation
    (rows with no tail hit an appended zero slot).

    rmatvec keeps the embedding-style PRE-SORTED gather of the permuted
    layouts: the distinct tail columns are grouped by occurrence-count
    bucket at build time, each bucket's (c_b, k_b) STORED-row-id matrix
    gathers the cotangent and reduces over k_b, and the gradient is
    assembled by concatenation in prefix order (identical machinery to
    PermutedHybridRows — `bucket_rows`/`bucket_vals` are byte-compatible).

    Mixed precision: with bf16 storage (dataset.cast_features) BOTH tail
    einsums multiply in bf16 and accumulate f32 — the same MXU recipe as
    the hot block, at half the value-storage bytes. With f32 storage the
    contractions are plain f32 (the parity-test reference path).

    COORDINATE CONVENTION as PermutedHybridRows: matvec/rmatvec (and the
    whole solver stack) operate on PERMUTED-space vectors;
    `to_model_space` / `from_model_space` translate at the public
    boundary (models/training, models/glm).

    Padding slots carry (column 0, value 0) so they contribute exactly
    0·w[0]; `tail_pad_waste` reports the ladder's slot overhead (under a
    third of a bucket; the occurrence buckets ride the same ladder).
    """

    dense: jax.Array | np.ndarray       # (n, d_sel) hot block, stored rows
    ell_pcols: tuple                    # per width bucket: (r_b, W_b) int32
    #                                     PREFIX-RELATIVE col ids (absolute
    #                                     permuted id − d_sel; padding 0 with
    #                                     value 0) — the tail gather then
    #                                     reads the small contiguous
    #                                     w[d_sel:n_prefix] slice (the ~U
    #                                     distinct tail columns), not the
    #                                     full (d,) vector: at 10M features
    #                                     that is a ~2 MB gather table vs
    #                                     40 MB, cache-resident on TPU
    ell_vals: tuple                     # per width bucket: (r_b, W_b) values
    row_pos: jax.Array | np.ndarray     # (n,) int32: where the CALLER's row
    #                                     i sits in the bucket concatenation.
    #                                     Caller-order layouts: slot B (after
    #                                     the B bucket rows) is the one zero
    #                                     every tail-free row reads. Stored-
    #                                     order layouts: the concatenation is
    #                                     padded with zeros to n rows and IS
    #                                     the stored order, so row_pos is the
    #                                     inverse of row_order
    bucket_rows: tuple                  # per occ bucket: (c_b, k_b) row ids
    bucket_vals: tuple                  # per occ bucket: (c_b, k_b) values
    perm_cols: jax.Array | np.ndarray   # (d,) original col id per position
    inv_perm: jax.Array | np.ndarray    # (d,) position of each original col
    n_features: int
    n_prefix: int                       # P = d_sel + distinct tail columns
    last_col_pos: int                   # permuted position of original col d-1
    tail_nnz: int                       # real (unpadded) tail nnz
    row_order: jax.Array | np.ndarray | None = None  # (n,) int32 caller row
    #                                     id of each stored row when the rows
    #                                     are stored in concatenation order

    @property
    def shape(self):
        return (self.dense.shape[0], self.n_features)

    @property
    def d_sel(self) -> int:
        return self.dense.shape[1]

    @property
    def tail_rows(self) -> int:
        """B: rows of the bucket concatenation (the rows with a tail, plus
        a shared ladder's padding rows)."""
        return sum(int(v.shape[0]) for v in self.ell_vals)

    @property
    def ell_slots(self) -> int:
        """Total (padded) ELL slots across the width ladder."""
        return sum(int(v.shape[0]) * int(v.shape[1]) for v in self.ell_vals)

    @property
    def tail_pad_waste(self) -> float:
        """ELL padding slots per real tail nonzero, slots ÷ nnz − 1 (0.0 =
        none): the width ladder's rounding and, in a shard or chunk view,
        the shared ladder's padding rows."""
        slots = self.ell_slots
        return (slots / self.tail_nnz - 1.0) if self.tail_nnz else 0.0

    def from_model_space(self, v):
        """Original-space (d,)-vector (or (d, ...) stack) → permuted space."""
        return jnp.asarray(v)[self.perm_cols]

    def to_model_space(self, w):
        """Permuted-space (d,)-vector (or (d, ...) stack) → original space."""
        return jnp.asarray(w)[self.inv_perm]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("dense", "ell_pcols", "ell_vals", "row_pos",
                 "bucket_rows", "bucket_vals", "perm_cols", "inv_perm"),
    meta_fields=("n_features", "n_prefix", "last_col_pos", "tail_nnz"),
)
@dataclasses.dataclass(frozen=True)
class ShardedBlockedEllRows:
    """BlockedEllRows laid out for a device mesh (or a streamed chunk
    ladder): per-shard ELL row buckets and occurrence buckets under ONE
    GLOBAL column permutation.

    Every per-shard structure is padded to a COMMON shape across shards
    (shard axis leading): the ELL width ladder is the union of per-shard
    rungs with r_b = the max per-shard row count, occurrence buckets
    use MAX-LOCAL counts exactly as ShardedPermutedHybridRows, and
    `row_pos` is (S, n_local) with LOCAL concat positions. Sharding every
    data leaf's axis 0 over the mesh gives each device a complete
    scatter-free piece; `local()` squeezes the shard axis into a plain
    BlockedEllRows inside shard_map, and the same common-shape property
    is what lets `data.dataset.chunk_blocked_ell` stream the shards as
    host chunks through ONE compiled chunk program.

    Residency/coordinate contracts as ShardedPermutedHybridRows.
    """

    dense: jax.Array | np.ndarray       # (n, d_sel) hot block, global rows
    ell_pcols: tuple                    # per width bucket: (S, r_b, W_b)
    ell_vals: tuple                     # per width bucket: (S, r_b, W_b)
    row_pos: jax.Array | np.ndarray     # (S, n_local) int32 local positions
    bucket_rows: tuple                  # per occ bucket: (S, c_b, k_b) LOCAL
    bucket_vals: tuple                  # per occ bucket: (S, c_b, k_b)
    perm_cols: jax.Array | np.ndarray   # (d,) replicated
    inv_perm: jax.Array | np.ndarray    # (d,) replicated
    n_features: int
    n_prefix: int
    last_col_pos: int
    tail_nnz: int

    @property
    def shape(self):
        return (self.dense.shape[0], self.n_features)

    @property
    def d_sel(self) -> int:
        return self.dense.shape[1]

    @property
    def n_shards(self) -> int:
        return self.row_pos.shape[0]

    @property
    def n_local(self) -> int:
        return self.row_pos.shape[1]

    @property
    def ell_slots(self) -> int:
        return sum(int(np.prod(v.shape)) for v in self.ell_vals)

    @property
    def tail_pad_waste(self) -> float:
        slots = self.ell_slots
        return (slots / self.tail_nnz - 1.0) if self.tail_nnz else 0.0

    def local(self) -> BlockedEllRows:
        """The one-shard view (inside shard_map, where the shard axis has
        been sliced to length 1)."""
        return BlockedEllRows(
            dense=self.dense,
            ell_pcols=tuple(b[0] for b in self.ell_pcols),
            ell_vals=tuple(b[0] for b in self.ell_vals),
            row_pos=self.row_pos[0],
            bucket_rows=tuple(b[0] for b in self.bucket_rows),
            bucket_vals=tuple(b[0] for b in self.bucket_vals),
            perm_cols=self.perm_cols,
            inv_perm=self.inv_perm,
            n_features=self.n_features,
            n_prefix=self.n_prefix,
            last_col_pos=self.last_col_pos,
            tail_nnz=self.tail_nnz,
        )

    def chunk(self, i: int) -> BlockedEllRows:
        """Shard ``i`` as a host BlockedEllRows (the streamed-chunk view:
        every chunk shares the common per-shard shapes, so the per-chunk
        device programs compile exactly once)."""
        return BlockedEllRows(
            dense=self.dense[i * self.n_local:(i + 1) * self.n_local],
            ell_pcols=tuple(np.asarray(b)[i] for b in self.ell_pcols),
            ell_vals=tuple(np.asarray(b)[i] for b in self.ell_vals),
            row_pos=np.asarray(self.row_pos)[i],
            bucket_rows=tuple(np.asarray(b)[i] for b in self.bucket_rows),
            bucket_vals=tuple(np.asarray(b)[i] for b in self.bucket_vals),
            perm_cols=self.perm_cols,
            inv_perm=self.inv_perm,
            n_features=self.n_features,
            n_prefix=self.n_prefix,
            last_col_pos=self.last_col_pos,
            tail_nnz=self.tail_nnz,
        )

    def shard_slice(self, lo: int, hi: int) -> "ShardedBlockedEllRows":
        """Shards ``lo:hi`` as one smaller ShardedBlockedEllRows (host
        views — no copies of the value blocks). This is how a MESH chunk
        ladder is cut (`data.dataset.chunk_blocked_ell(..., n_shards=D)`):
        one `shard_blocked_ell` pass with S = n_chunks × D builds the
        global permutation and common shapes, and each streamed chunk is
        the D-shard group [i·D, (i+1)·D) — every chunk then row-shards
        over the mesh with the SAME per-shard structures, so the sharded
        per-chunk programs compile exactly once."""
        nl = self.n_local
        return ShardedBlockedEllRows(
            dense=self.dense[lo * nl:hi * nl],
            ell_pcols=tuple(np.asarray(b)[lo:hi] for b in self.ell_pcols),
            ell_vals=tuple(np.asarray(b)[lo:hi] for b in self.ell_vals),
            row_pos=np.asarray(self.row_pos)[lo:hi],
            bucket_rows=tuple(np.asarray(b)[lo:hi]
                              for b in self.bucket_rows),
            bucket_vals=tuple(np.asarray(b)[lo:hi]
                              for b in self.bucket_vals),
            perm_cols=self.perm_cols,
            inv_perm=self.inv_perm,
            n_features=self.n_features,
            n_prefix=self.n_prefix,
            last_col_pos=self.last_col_pos,
            tail_nnz=self.tail_nnz,
        )

    def from_model_space(self, v):
        return jnp.asarray(v)[self.perm_cols]

    def to_model_space(self, w):
        return jnp.asarray(w)[self.inv_perm]


Matrix = (jax.Array | SparseRows | HybridRows | ShardedHybridRows
          | PermutedHybridRows | ShardedPermutedHybridRows
          | BlockedEllRows | ShardedBlockedEllRows)


_SCATTER_CHUNK_ELEMS = 1 << 29  # ~2 GB f32 intermediate per scatter chunk


@partial(jax.jit, static_argnames=("n", "d", "dtype"))
def _dense_scatter(r, p, v, n, d, dtype):
    """Hot-COO → (n, d) dense block, f32 scatter-add then storage cast."""
    return jnp.zeros((n, d), jnp.float32).at[r, p].add(v).astype(dtype)


@partial(jax.jit, donate_argnums=(0,))
def _place_chunk(out, chunk, r0):
    """Write ``chunk`` into rows [r0, r0 + len(chunk)) of the preallocated
    result in place (donated buffer: nothing full-size is ever live
    twice). Shared by the device scatter below and the streamed chunk
    uploads (`data.dataset.device_put_in_pieces`)."""
    return jax.lax.dynamic_update_slice_in_dim(out, chunk, r0, axis=0)


def _dense_scatter_chunked(rows_h, pos_h, vals_h, n, d_sel, dtype):
    """Row-chunked device scatter: peak HBM = ONE full-size block in the
    target dtype + one f32 chunk + its cast — each chunk scatters then
    lands in a DONATED preallocated result, so nothing full-size is ever
    live twice (at the bench's 2M×1024 bf16 that is ~6.5 GB instead of
    the ~13 a whole-block f32 intermediate costs on a 16 GB v5e; the
    unattended bench must not flirt with OOM). The hot COO is row-major,
    so row ranges are contiguous slices found by searchsorted."""
    row_chunk = max(1, _SCATTER_CHUNK_ELEMS // max(d_sel, 1))
    if n <= row_chunk:
        return _dense_scatter(
            jnp.asarray(rows_h), jnp.asarray(pos_h), jnp.asarray(vals_h),
            n, d_sel, dtype)
    out = jnp.zeros((n, d_sel), dtype)
    for r0 in range(0, n, row_chunk):
        r1 = min(n, r0 + row_chunk)
        lo, hi = np.searchsorted(rows_h, [r0, r1])
        m = hi - lo
        # pad the COO length — to a power of two, from 2^20 on to a
        # multiple of 2^20 — so the jitted scatter compiles a couple of
        # shapes, not one per chunk (padding entries add 0.0 at local
        # (0, 0) — a no-op for scatter-add). Powers of two all the way
        # held 200 MB of padding on the device at the build's peak when
        # a chunk's COO sat just past 2^24 (the bench's tail-free rows).
        m_pad = min(next_pow2(max(m, 1)), quantize_rows(m, 1 << 20))
        r = np.zeros(m_pad, np.int32)
        p = np.zeros(m_pad, np.int32)
        v = np.zeros(m_pad, np.float32)
        r[:m] = rows_h[lo:hi] - r0
        p[:m] = pos_h[lo:hi]
        v[:m] = vals_h[lo:hi]
        chunk = _dense_scatter(
            jnp.asarray(r), jnp.asarray(p), jnp.asarray(v),
            r1 - r0, d_sel, dtype)
        out = _place_chunk(out, chunk, jnp.int32(r0))
    return out


def _hot_positions(X: SparseRows, d_dense: int):
    """Pick the `d_dense` most frequent columns as the hot block. Returns
    (ind, val, sel, pos): the host COO, the selected column ids ascending,
    and per entry its hot-block slot ((n, k) int32; -1 = stays sparse)."""
    ind = np.asarray(X.indices)
    val = np.asarray(X.values)
    d = X.n_features
    counts = np.bincount(ind[val != 0.0].ravel(), minlength=d)
    d_sel = min(d_dense, d)
    sel = np.sort(np.argpartition(-counts, d_sel - 1)[:d_sel])
    col_to_pos = np.full(d, -1, np.int32)
    col_to_pos[sel] = np.arange(d_sel, dtype=np.int32)
    return ind, val, sel, col_to_pos[ind]


def _dense_on_devices(hot, pos, val, d_sel, dtype, mesh):
    """The (n, d_sel) hot block built on the device(s) that KEEP it: one
    `_dense_scatter_chunked` per keeping device over that device's own
    row range, from the compact hot COO of that range alone. With no mesh
    (the one-device builders) the default device keeps every row and the
    result is a plain device array; with a mesh, slot j of
    `flat_mesh_devices` keeps rows [j·n/D, (j+1)·n/D), THIS process builds
    the slots its own devices own (`local_row_slots`: on several hosts
    each builds its own rows and touches no other's) and the pieces are
    assembled, without a copy, into ONE array already sharded as
    `models.training._hybrid_specs` shards a hot block — so no device ever
    holds more than its own shard plus one scatter chunk, and a later
    `device_put` to that sharding moves nothing."""
    n, k = hot.shape
    from photon_tpu.parallel.mesh import (data_sharding, flat_mesh_devices,
                                          local_row_slots)

    if mesh is None:
        devices, slots = [None], [0]
    else:
        devices, slots = flat_mesh_devices(mesh), local_row_slots(mesh)
    n_loc = n // len(devices)
    local_rows = np.repeat(np.arange(n_loc, dtype=np.int32), k).reshape(
        n_loc, k)
    parts = []
    for j in slots:
        rows_j = slice(j * n_loc, (j + 1) * n_loc)
        h = hot[rows_j]
        with jax.default_device(devices[j]):
            parts.append(_dense_scatter_chunked(
                local_rows[h], pos[rows_j][h].astype(np.int32),
                val[rows_j][h].astype(np.float32), n_loc, d_sel, dtype))
    if mesh is None:
        return parts[0]
    return jax.make_array_from_single_device_arrays(
        (n, d_sel), data_sharding(mesh), parts)


def _hot_cold_split(X: SparseRows, d_dense: int, device_dense_dtype,
                    mesh=None, host_dense_dtype=np.float32,
                    host_pinned=None):
    """Shared front half of the hybrid builders: pick the `d_dense` most
    frequent columns, build the (n, d_sel) hot block (on device when
    `device_dense_dtype` is set — on the devices of ``mesh`` that keep its
    rows when one is handed in — else on the host, piece by piece, stored
    as ``host_dense_dtype``), and extract the cold nnz as flat row-major
    COO. Returns (dense, sel, t_rows, t_cols, t_vals) with t_* exact-size
    (possibly empty) int64/f32 host arrays. ``host_pinned`` =
    (group rows, piece bytes) keeps a host-built block in pinned host
    memory instead (`PinnedRows`)."""
    return _split_at(*_hot_positions(X, d_dense), device_dense_dtype, mesh,
                     host_dense_dtype, host_pinned)


_HOST_PIECE_CELLS = 1 << 20  # 8 MB of float64 scratch: stays in cache and
#                              in the allocator's reused pages (a 1 GB
#                              piece page-faults its way through fresh
#                              memory, ten times slower a cell)


class PinnedRows:
    """A host (n, d) block kept in PINNED host memory, as consecutive row
    pieces (`jax.Array`s of memory kind ``pinned_host``) that never
    straddle a multiple of ``group_rows`` — a streamed chunk ladder's hot
    block, whose chunk i is ``block[i * c:(i + 1) * c]``: whole pieces.

    Why pinned: the runtime uploads pageable host memory through a staging
    copy, and that copy is what a streamed solve's wall time moves with —
    32 MB pieces cross a v5e's host link at 13.35–13.67 GB/s from numpy
    memory and 9.8–11.8 beside four memory-streaming neighbours, but at
    14.13 ± 0.003 GB/s from pinned memory, neighbours or none (chip runs
    of PR 34, PERF.md §6). It is a leaf to `jax.tree_util` (an opaque
    object), has numpy's ``shape`` / ``dtype`` / ``nbytes``, slices by
    whole pieces, and `np.asarray` gives its values (a copy)."""

    def __init__(self, pieces, starts, shape, dtype):
        self._pieces, self._starts = tuple(pieces), tuple(starts)
        self.shape, self.dtype = tuple(shape), np.dtype(dtype)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * self.dtype.itemsize

    def pieces(self):
        """(first row within this block, piece) in row order."""
        return zip(self._starts, self._pieces)

    def __getitem__(self, rows) -> "PinnedRows":
        if not isinstance(rows, slice) or rows.step not in (None, 1):
            raise TypeError("PinnedRows slices by contiguous row ranges")
        lo, hi, _ = rows.indices(self.shape[0])
        ends = self._starts[1:] + (self.shape[0],)
        if hi > lo and (lo not in self._starts or hi not in ends):
            raise ValueError(f"rows {lo}:{hi} cut a pinned piece")
        keep = [k for k, r0 in enumerate(self._starts) if lo <= r0 < hi]
        return PinnedRows([self._pieces[k] for k in keep],
                          [self._starts[k] - lo for k in keep],
                          (max(hi - lo, 0),) + self.shape[1:], self.dtype)

    def __array__(self, dtype=None, copy=None):
        out = (np.concatenate([np.asarray(p) for p in self._pieces])
               if self._pieces else np.empty(self.shape, self.dtype))
        return out if dtype is None else out.astype(dtype)

    def delete(self) -> None:
        """Free the pinned memory now (else: when the last view goes)."""
        for p in self._pieces:
            if not p.is_deleted():
                p.delete()


def _dense_pinned(hot, pos, val, d_sel, dtype, group_rows, piece_bytes):
    """`_dense_on_host`'s block, every value the same, laid into PINNED
    host memory piece by piece (`PinnedRows`): each piece is built as a
    numpy block of at most ``piece_bytes`` and copied into pinned memory
    of the first device, so the host holds the block once and a sliver of
    scratch, as `_dense_on_host` does."""
    from jax.sharding import SingleDeviceSharding

    n = hot.shape[0]
    pinned = SingleDeviceSharding(jax.devices()[0],
                                  memory_kind="pinned_host")
    rows = max(1, min(group_rows,
                      piece_bytes // max(d_sel * np.dtype(dtype).itemsize,
                                         1)))
    pieces, starts = [], []
    for g0 in range(0, n, group_rows):
        for r0 in range(g0, min(n, g0 + group_rows), rows):
            r1 = min(n, g0 + group_rows, r0 + rows)
            # a numpy block of its own a piece: one reused buffer races
            # with the runtime's copy (a ready pinned array has not always
            # read its source yet: the CPU runtime, 2 runs of 6)
            pieces.append(jax.device_put(
                _dense_on_host(hot[r0:r1], pos[r0:r1], val[r0:r1], d_sel,
                               dtype), pinned, may_alias=False))
            pieces[-1].block_until_ready()
            starts.append(r0)
    return PinnedRows(pieces, starts, (n, d_sel), dtype)


def _dense_on_host(hot, pos, val, d_sel, dtype):
    """The (n, d_sel) hot block built on the HOST straight into its
    storage ``dtype``: a float64 bincount over flat (row, pos) ids (C-speed
    accumulation — np.add.at is an order of magnitude slower at the
    10M-feature bench scale), rounded to f32 and then to ``dtype``, one
    PIECE of rows at a time. A piece is at most `_HOST_PIECE_CELLS` cells
    and at most a 64th of the rows, so whatever the size the build holds
    the block itself and a sliver of scratch — never a second block, and
    never an f32 one where the storage is narrower (a streamed chunk
    ladder's bf16 block is 17 GB at 8.4M rows: its f32 form would be 34).
    Which rows share a piece changes no value: a cell's repeats are added
    in their row's order either way."""
    n = hot.shape[0]
    dense = np.empty((n, d_sel), dtype)
    piece = max(1, min(_HOST_PIECE_CELLS // max(d_sel, 1), -(-n // 64)))
    for r0 in range(0, n, piece):
        r1 = min(n, r0 + piece)
        h = hot[r0:r1]
        flat_ids = (np.nonzero(h)[0] * np.int64(d_sel)
                    + pos[r0:r1][h])
        dense[r0:r1] = np.bincount(
            flat_ids, weights=val[r0:r1][h].astype(np.float64),
            minlength=(r1 - r0) * d_sel,
        ).astype(np.float32).reshape(r1 - r0, d_sel)
    return dense


def _split_at(ind, val, sel, pos, device_dense_dtype, mesh=None,
              host_dense_dtype=np.float32, host_pinned=None):
    """`_hot_cold_split` from `_hot_positions`' output on: a builder that
    stores its rows in another order permutes the four row-wise in
    between. No (n, k) row-id array is made: the hot COO's row ids are
    local to each keeping device's (or each host piece's) row range, and
    the tail's follow from the flat positions."""
    n, k = ind.shape
    d_sel = sel.shape[0]
    nnz_mask = val != 0.0
    hot = (pos >= 0) & nnz_mask
    if device_dense_dtype is not None:
        dense = _dense_on_devices(hot, pos, val, d_sel, device_dense_dtype,
                                  mesh)
    elif host_pinned is not None:
        dense = _dense_pinned(hot, pos, val, d_sel, host_dense_dtype,
                              *host_pinned)
    else:
        dense = _dense_on_host(hot, pos, val, d_sel, host_dense_dtype)
    cold = (~hot) & nnz_mask
    flat = np.flatnonzero(cold)       # row-major → tail rows ascending
    t_rows = flat // k
    t_cols = ind.reshape(-1)[flat]
    t_vals = val.reshape(-1)[flat].astype(np.float32)
    return dense, sel, t_rows, t_cols, t_vals


def to_hybrid(X: SparseRows, d_dense: int = 1024,
              device_dense_dtype=None) -> HybridRows:
    """Split a SparseRows into (hot dense block, cold sparse tail).

    Selects the `d_dense` columns with the most nonzeros (host-side pass
    over the padded COO); the remaining nnz are COMPACTED into exact-size
    flat row-sorted COO (tail_rows/tail_cols/tail_vals) — per-row padding
    would cost as much as real nnz on the gather path.

    `device_dense_dtype` (e.g. jnp.bfloat16) builds the dense hot block ON
    DEVICE by scattering the compact hot COO (f32 accumulation, then cast):
    the host→device copy carries 12 bytes per hot nnz (i32 row + i32 slot +
    f32 val) instead of the materialized n×d_dense block — ~5× fewer bytes
    at the bench's power-law density, and no host materialization. The
    returned HybridRows then has a device `dense` leaf and host tail
    leaves (device_put'ing it later is a no-op for the big block).
    """
    d = X.n_features
    dense, sel, tail_rows, tail_cols, tail_vals = _hot_cold_split(
        X, d_dense, device_dense_dtype)
    # Flat row-sorted COO tail: exactly the cold nnz, no per-row padding
    # (row-major traversal keeps rows ascending for the sorted segment_sum
    # in matvec). One zero sentinel entry keeps the arrays non-empty.
    if tail_rows.size == 0:
        tail_rows = np.zeros(1, np.int64)
        tail_cols = np.zeros(1, np.int64)
        tail_vals = np.zeros(1, np.float32)
    # HOST leaves: the caller decides when (and in what dtype) to transfer —
    # e.g. cast_features to bf16 FIRST, then one device_put. An eager
    # jnp.asarray here would ship the dense block f32 over the link (at
    # bench scale, gigabytes) before any cast could halve it.
    return HybridRows(
        dense=dense,
        dense_cols=sel.astype(np.int32),
        tail_rows=tail_rows.astype(np.int32),
        tail_cols=tail_cols.astype(np.int32),
        tail_vals=tail_vals.astype(np.float32),
        n_features=d,
    )


def _width_rungs(counts: np.ndarray) -> np.ndarray:
    """Rung of the ONE width ladder both tail structures are bucketed by —
    widths 1, 2, 3, 4, 6, 8, 12, 16, 24, …: every power of two and 3·2^(j−1)
    between 2^j and 2^(j+1) — per count: the smallest rung whose width
    (`_rung_width`) is ≥ the count (rung 0 for counts ≤ 1). Monotone in the
    count, so a sort by rung is a sort by width, and a bucket is under a
    third padding. Integer arithmetic (the count's bit length via `frexp`,
    exact below 2^53)."""
    c = np.asarray(counts).astype(np.int64)
    rung = np.zeros(c.shape, np.int64)
    big = c > 1
    cb = c[big]
    j = np.frexp((cb - 1).astype(np.float64))[1].astype(np.int64)
    # 2^(j-1) < c ≤ 2^j: the rung of 2^j is 2j − 1; from j = 2 on the rung
    # below it, 2j − 2, is 3·2^(j−2)
    between = (j >= 2) & (cb <= 3 * (np.int64(1) << np.maximum(j - 2, 0)))
    rung[big] = 2 * j - 1 - between
    return rung


def _rung_width(rung):
    """Width of a rung of `_width_rungs`' ladder — 1, 2, 3, 4, 6, 8, 12, … —
    elementwise (int64)."""
    above = np.maximum(np.asarray(rung, np.int64) - 1, 0)
    return np.where(np.asarray(rung) == 0, 1, (2 + above % 2) << (above // 2))


def _column_perm(sel, u_cols, order, d):
    """(perm_cols, inv_perm) for the hot-prefix + bucket-ordered-tail +
    untouched-suffix column relabeling shared by every permuted layout."""
    perm_prefix = np.concatenate([sel, u_cols[order]])
    untouched = np.setdiff1d(np.arange(d), perm_prefix)
    perm_cols = np.concatenate([perm_prefix, untouched]).astype(np.int32)
    inv_perm = np.empty(d, np.int64)
    inv_perm[perm_cols] = np.arange(d)
    return perm_cols, inv_perm.astype(np.int32)


def _occurrence_buckets(t_rows, t_vals, pcol, d_sel, e, order, u_counts):
    """Column-major padded occurrence buckets (rmatvec's embedding-style
    pre-sorted gather): tail nnz sorted by prefix id groups each column's
    occurrences contiguously, in rank (= output) order. Returns
    (bucket_rows, bucket_vals) tuples of (c_b, k_b) matrices."""
    m = pcol.shape[0]
    nnz_order = np.argsort(pcol, kind="stable")
    rank_per = pcol[nnz_order].astype(np.int64) - d_sel
    counts_by_rank = u_counts[order]
    col_offsets = np.concatenate([[0], np.cumsum(counts_by_rank)])
    pos_within = np.arange(m) - col_offsets[rank_per]
    es = e[order]                      # rung per rank, ascending
    bucket_rows, bucket_vals = [], []
    for e_v in np.unique(es):
        r0, r1 = np.searchsorted(es, [e_v, e_v + 1])
        c_b, k_b = int(r1 - r0), int(_rung_width(e_v))
        lo, hi = int(col_offsets[r0]), int(col_offsets[r1])
        br = np.zeros((c_b, k_b), np.int32)
        bv = np.zeros((c_b, k_b), np.float32)
        lr = rank_per[lo:hi] - r0
        pw = pos_within[lo:hi]
        br[lr, pw] = t_rows[nnz_order[lo:hi]]
        bv[lr, pw] = t_vals[nnz_order[lo:hi]]
        bucket_rows.append(br)
        bucket_vals.append(bv)
    return tuple(bucket_rows), tuple(bucket_vals)


def _sharded_occurrence_buckets(loc_rows, t_vals, rank_nnz, s_ids, S, e,
                                order):
    """Per-shard occurrence buckets (S, c_b, k_b) with LOCAL row ids:
    sort nnz by (rank, shard); within a (rank, shard) group the row-major
    source keeps local rows ascending."""
    m_tot = rank_nnz.shape[0]
    U = order.shape[0]
    nnz_order = np.lexsort((s_ids, rank_nnz))
    rs_key = (rank_nnz * S + s_ids)[nnz_order]
    counts_rs = np.bincount(rs_key, minlength=U * S)
    offsets_rs = np.concatenate([[0], np.cumsum(counts_rs)])
    pos_within = np.arange(m_tot) - offsets_rs[rs_key]
    rank_sorted = rank_nnz[nnz_order]
    es = e[order]                      # rung per rank, ascending
    bucket_rows, bucket_vals = [], []
    for e_v in np.unique(es):
        r0, r1 = np.searchsorted(es, [e_v, e_v + 1])
        c_b, k_b = int(r1 - r0), int(_rung_width(e_v))
        lo, hi = np.searchsorted(rank_sorted, [r0, r1])
        br = np.zeros((S, c_b, k_b), np.int32)
        bv = np.zeros((S, c_b, k_b), np.float32)
        sel_nnz = nnz_order[lo:hi]
        ls = s_ids[sel_nnz]
        lr = rank_nnz[sel_nnz] - r0
        pw = pos_within[lo:hi]
        br[ls, lr, pw] = loc_rows[sel_nnz]
        bv[ls, lr, pw] = t_vals[sel_nnz]
        bucket_rows.append(br)
        bucket_vals.append(bv)
    return tuple(bucket_rows), tuple(bucket_vals)


def _row_rungs(counts: np.ndarray) -> np.ndarray:
    """ELL width-bucket rung per row tail-nnz count (-1 = no tail)."""
    return np.where(counts > 0, _width_rungs(counts), -1)


def _count_tail_slots(tail_nnz, ell_vals, bucket_vals):
    """Counters ``layout.tail_nnz`` / ``layout.ell_slots`` /
    ``layout.occ_slots`` of one blocked-ELL build: the tail's real
    nonzeros, and the slots the ELL row buckets and the occurrence buckets
    hold for them — what the forward and the transposed tail gather read
    an evaluation, summed over the shards of a sharded build. Padding is
    slots ÷ nnz − 1. A layout with no tail counts nothing."""
    telemetry.count("layout.tail_nnz", tail_nnz)
    telemetry.count("layout.ell_slots", sum(int(v.size) for v in ell_vals))
    telemetry.count("layout.occ_slots",
                    sum(int(v.size) for v in bucket_vals))


def _fill_ell(widths, counts, e_row, starts, pcol, vals):
    """One shard's ELL row buckets over a shared ``widths`` ladder of
    (rung, r_b) pairs. ``starts``: per-row offset of the row's slice
    in the (global) flat row-major tail arrays. Returns
    ([(r_b, W_b) pcols], [(r_b, W_b) vals], row_pos) where row_pos maps
    each row to its position in the bucket concatenation (rows with no
    tail map to the appended zero slot at B = Σ r_b)."""
    n = counts.shape[0]
    B = sum(r_b for _, r_b in widths)
    row_pos = np.full(n, B, np.int32)
    out_c, out_v = [], []
    base = 0
    for e_v, r_b in widths:
        w_b = int(_rung_width(e_v))
        rows_b = np.flatnonzero(e_row == e_v)
        pc = np.zeros((r_b, w_b), np.int32)
        pv = np.zeros((r_b, w_b), np.float32)
        if rows_b.size:
            L = counts[rows_b]
            tot = int(L.sum())
            pw = np.arange(tot) - np.repeat(np.cumsum(L) - L, L)
            src = np.repeat(starts[rows_b], L) + pw
            dr = np.repeat(np.arange(rows_b.size), L)
            pc[dr, pw] = pcol[src]
            pv[dr, pw] = vals[src]
            row_pos[rows_b] = base + np.arange(rows_b.size, dtype=np.int64)
        base += r_b
        out_c.append(pc)
        out_v.append(pv)
    return out_c, out_v, row_pos


def to_permuted_hybrid(X: SparseRows, d_dense: int = 1024,
                       device_dense_dtype=None) -> PermutedHybridRows:
    """Build the scatter-free permuted hybrid from padded COO rows.

    One vectorized host pass: pick the `d_dense` most frequent columns as
    the hot block (relabeled to prefix positions [0, d_sel)), group the
    distinct tail columns by occurrence bucket — a column's count rounded
    up to a rung of the width ladder 1, 2, 3, 4, 6, 8, 12, …
    (`_width_rungs`); relabeled to [d_sel, P) in bucket order, the order
    rmatvec's concatenation produces — and lay the tail twice:
    row-major flat (matvec's cumsum reduction) and column-major padded per
    bucket (rmatvec's gather+reduce; the ladder's padding is under a third
    of a bucket on multi-occurrence columns, none on the count-1
    majority). `device_dense_dtype` builds the dense block on
    device from compact COO triples as `to_hybrid` does.
    """
    n = np.asarray(X.indices).shape[0]
    d = X.n_features
    d_sel = min(d_dense, d)
    dense, sel, t_rows, t_cols, t_vals = _hot_cold_split(
        X, d_dense, device_dense_dtype)
    m = t_rows.size

    if m == 0:
        perm_cols = np.concatenate(
            [sel, np.setdiff1d(np.arange(d), sel)]).astype(np.int32)
        inv_perm = np.empty(d, np.int64)
        inv_perm[perm_cols] = np.arange(d)
        return PermutedHybridRows(
            dense=dense, tail_pcols=np.zeros(1, np.int32),
            tail_vals=np.zeros(1, np.float32),
            row_bounds=np.zeros(n + 1, np.int32),
            bucket_rows=(), bucket_vals=(),
            perm_cols=perm_cols, inv_perm=inv_perm.astype(np.int32),
            n_features=d, n_prefix=d_sel,
            last_col_pos=int(inv_perm[d - 1]))

    row_bounds = np.searchsorted(t_rows, np.arange(n + 1)).astype(np.int32)

    u_cols, inv, u_counts = np.unique(t_cols, return_inverse=True,
                                      return_counts=True)
    U = u_cols.size
    e = _width_rungs(u_counts)
    order = np.lexsort((u_cols, e))   # bucket-major, col-id within bucket
    rank = np.empty(U, np.int64)
    rank[order] = np.arange(U)

    pcol = (d_sel + rank[inv]).astype(np.int32)   # (m,) prefix ids, row-major
    perm_cols, inv_perm = _column_perm(sel, u_cols, order, d)
    bucket_rows, bucket_vals = _occurrence_buckets(
        t_rows, t_vals, pcol, d_sel, e, order, u_counts)

    return PermutedHybridRows(
        dense=dense, tail_pcols=pcol, tail_vals=t_vals.astype(np.float32),
        row_bounds=row_bounds,
        bucket_rows=bucket_rows, bucket_vals=bucket_vals,
        perm_cols=perm_cols, inv_perm=inv_perm,
        n_features=d, n_prefix=d_sel + U,
        last_col_pos=int(inv_perm[d - 1]))


def to_blocked_ell(X: SparseRows, d_dense: int = 1024,
                   device_dense_dtype=None) -> BlockedEllRows:
    """Build the blocked-ELL hybrid (see BlockedEllRows) from padded COO
    rows, its rows STORED in the order the ELL buckets concatenate in.

    One vectorized host pass sharing the hot/cold split and the permuted
    column machinery with `to_permuted_hybrid`, plus the ELL side: once
    the hot columns are chosen each row's tail nnz is counted and the rows
    are put in the stable order (width rung ascending, tail-free rows
    last, original row id within a bucket) BEFORE anything is laid, so the
    hot block, the ELL buckets (each a contiguous row range, filled
    row-major from the flat tail) and the occurrence buckets' row ids all
    share that one order. `row_order` records it and `row_pos` is its
    inverse. `device_dense_dtype` builds the hot block on device from
    compact COO triples as `to_hybrid` does.
    """
    d = X.n_features
    d_sel = min(d_dense, d)
    ind, val, sel, pos = _hot_positions(X, d_dense)
    n = ind.shape[0]
    counts = ((pos < 0) & (val != 0.0)).sum(axis=1)     # tail nnz per row
    e_row = _row_rungs(counts)
    # int8 keys: numpy's stable sort of them is a radix sort
    row_order = np.argsort(np.where(e_row < 0, 127, e_row).astype(np.int8),
                           kind="stable").astype(np.int32)
    row_pos = np.empty(n, np.int32)
    row_pos[row_order] = np.arange(n, dtype=np.int32)
    dense, sel, t_rows, t_cols, t_vals = _split_at(
        ind[row_order], val[row_order], sel, pos[row_order],
        device_dense_dtype)
    t_vals = t_vals.astype(np.float32)
    m = t_rows.size

    if m == 0:
        perm_cols, inv_perm = _column_perm(
            sel, np.zeros(0, np.int64), np.zeros(0, np.int64), d)
        return BlockedEllRows(
            dense=dense, ell_pcols=(), ell_vals=(), row_pos=row_pos,
            bucket_rows=(), bucket_vals=(),
            perm_cols=perm_cols, inv_perm=inv_perm,
            n_features=d, n_prefix=d_sel,
            last_col_pos=int(inv_perm[d - 1]), tail_nnz=0,
            row_order=row_order)

    u_cols, inv, u_counts = np.unique(t_cols, return_inverse=True,
                                      return_counts=True)
    U = u_cols.size
    e = _width_rungs(u_counts)
    order = np.lexsort((u_cols, e))
    rank = np.empty(U, np.int64)
    rank[order] = np.arange(U)
    pcol = (d_sel + rank[inv]).astype(np.int32)
    perm_cols, inv_perm = _column_perm(sel, u_cols, order, d)
    bucket_rows, bucket_vals = _occurrence_buckets(
        t_rows, t_vals, pcol, d_sel, e, order, u_counts)

    counts, e_row = counts[row_order], e_row[row_order]
    widths = [(int(ev), int((e_row == ev).sum()))
              for ev in np.unique(e_row[e_row >= 0])]
    # prefix-RELATIVE ids: the device tail gather reads w[d_sel:n_prefix]
    pcol_rel = (pcol.astype(np.int64) - d_sel).astype(np.int32)
    pcs, pvs, _ = _fill_ell(widths, counts, e_row,
                            np.cumsum(counts) - counts, pcol_rel, t_vals)
    _count_tail_slots(m, pvs, bucket_vals)

    return BlockedEllRows(
        dense=dense, ell_pcols=tuple(pcs), ell_vals=tuple(pvs),
        row_pos=row_pos,
        bucket_rows=bucket_rows, bucket_vals=bucket_vals,
        perm_cols=perm_cols, inv_perm=inv_perm,
        n_features=d, n_prefix=d_sel + U,
        last_col_pos=int(inv_perm[d - 1]), tail_nnz=int(m),
        row_order=row_order)


def blocked_ell_from_scipy_csr(csr, d_dense: int = 1024,
                               device_dense_dtype=None,
                               strict: bool = False) -> BlockedEllRows:
    """scipy CSR → BlockedEllRows in one call (the ingestion shortcut):
    pads to fixed nnz-per-row on host (`from_scipy_csr` — never truncating,
    k defaults to the max row nnz; ``strict`` is forwarded for callers that
    cap k upstream) and lays the blocked-ELL hybrid."""
    return to_blocked_ell(
        from_scipy_csr(csr, host=True, strict=strict), d_dense,
        device_dense_dtype=device_dense_dtype)


def shard_blocked_ell(X: SparseRows, n_shards: int, d_dense: int = 1024,
                      device_dense_dtype=None, mesh=None,
                      host_dense_dtype=np.float32,
                      host_pinned_piece_bytes=0) -> ShardedBlockedEllRows:
    """Build the SHARDED blocked-ELL hybrid (see ShardedBlockedEllRows)
    from padded COO rows. Rows must already divide ``n_shards``
    (`data.dataset.shard_blocked_ell_batch` pads + builds; the streamed
    chunk ladder rides the same builder with S = n_chunks).

    ``mesh`` says WHERE a device-built hot block (`device_dense_dtype`)
    lives, and a device build does not go without it: each device of the
    mesh builds the rows it keeps (`_dense_on_devices`) and ``dense``
    comes back row-sharded over it, one addressable shard a device — at
    8.4M × 1024 in bf16 the whole block is 17 GB, more than a chip holds,
    and there is no second route that assembles it on one. A host-built
    block (`device_dense_dtype=None`: the streamed chunk ladder's, with
    S = n_chunks × D) takes no mesh and is stored as ``host_dense_dtype``
    from its first byte (`_dense_on_host`: the ladder's f32 form is never
    made); with ``host_pinned_piece_bytes`` it is kept in PINNED host
    memory, in pieces of that many bytes that never straddle a shard
    (`PinnedRows`: what a one-device chunk ladder streams from on an
    accelerator). Every other leaf is host numpy either way, and no
    leaf's value depends on the mesh or on where the block is kept.

    One vectorized host pass mirroring `shard_permuted_hybrid`: a GLOBAL
    column permutation (hot prefix from global frequencies, tail ranks by
    MAX-LOCAL occurrence bucket) and PER-SHARD structures padded to
    common shapes — the ELL width ladder is the union of per-shard row
    rungs (`_width_rungs`: widths 1, 2, 3, 4, 6, 8, 12, …) with r_b = max
    over shards (absent (shard, width) pairs carry all-zero rows that
    contribute nothing and are never gathered).
    """
    n = np.asarray(X.indices).shape[0]
    d = X.n_features
    if n % n_shards != 0:
        raise ValueError(
            f"{n} rows do not divide {n_shards} shards; pad the batch first "
            "(data.dataset.shard_blocked_ell_batch)")
    if device_dense_dtype is not None and mesh is None:
        raise ValueError(
            "a device-built hot block of a sharded layout is built on the "
            "devices that keep its shards: hand in the mesh "
            "(shard_blocked_ell_batch(..., mesh=mesh)), or build it on the "
            "host (device_dense_dtype=None)")
    if mesh is not None and int(mesh.devices.size) != n_shards:
        raise ValueError(
            f"{n_shards} shards cannot live on a mesh of "
            f"{int(mesh.devices.size)} devices: a mesh keeps one shard a "
            "device")
    with telemetry.span("layout.shard_build", shards=n_shards, rows=n):
        return _shard_blocked_ell(
            X, n_shards, d_dense, device_dense_dtype, mesh,
            host_dense_dtype,
            (n // n_shards, host_pinned_piece_bytes)
            if host_pinned_piece_bytes else None)


def _count_shard_bytes(S, ell_rows_own, ladder, cs_counts, bucket_rows):
    """Counters ``layout.shard_bytes_real`` / ``layout.shard_bytes_padded``:
    the bytes (an int32 id and an f32 value a slot) of the shards' ELL and
    occurrence buckets laid out each to its OWN shapes — its own rows a
    width, its own count's rung of the width ladder (`_width_rungs`) a
    column — and padded to the common shapes every shard shares (r_b = the
    most rows any shard has at a width, a column's bucket from its
    MAX-LOCAL count)."""
    slot = 4 + 4
    own_occ = np.where(cs_counts > 0,
                       _rung_width(_width_rungs(cs_counts)), 0).sum()
    real = sum(int(r) * int(_rung_width(ev)) for own in ell_rows_own
               for ev, r in own.items()) + int(own_occ)
    padded = S * (sum(r_b * int(_rung_width(ev)) for ev, r_b in ladder)
                  + sum(int(b.shape[1]) * int(b.shape[2])
                        for b in bucket_rows))
    telemetry.count("layout.shard_bytes_real", real * slot)
    telemetry.count("layout.shard_bytes_padded", padded * slot)


def _shard_blocked_ell(X, n_shards, d_dense, device_dense_dtype, mesh,
                       host_dense_dtype, host_pinned=None):
    """`shard_blocked_ell` after its checks, inside its span."""
    n = np.asarray(X.indices).shape[0]
    d = X.n_features
    n_local = n // n_shards
    d_sel = min(d_dense, d)
    dense, sel, t_rows, t_cols, t_vals = _hot_cold_split(
        X, d_dense, device_dense_dtype, mesh, host_dense_dtype,
        host_pinned)
    t_vals = t_vals.astype(np.float32)
    m_tot = t_rows.size
    S = n_shards

    if m_tot == 0:
        perm_cols, inv_perm = _column_perm(
            sel, np.zeros(0, np.int64), np.zeros(0, np.int64), d)
        return ShardedBlockedEllRows(
            dense=dense, ell_pcols=(), ell_vals=(),
            row_pos=np.zeros((S, n_local), np.int32),
            bucket_rows=(), bucket_vals=(),
            perm_cols=perm_cols, inv_perm=inv_perm,
            n_features=d, n_prefix=d_sel,
            last_col_pos=int(inv_perm[d - 1]), tail_nnz=0)

    s_ids = (t_rows // n_local).astype(np.int64)       # (m,) shard per nnz
    loc_rows = (t_rows - s_ids * n_local).astype(np.int64)

    u_cols, inv, u_counts = np.unique(t_cols, return_inverse=True,
                                      return_counts=True)
    U = u_cols.size
    # per-(column, shard) occurrence counts -> MAX-LOCAL count per column
    cs_counts = np.bincount(inv * S + s_ids, minlength=U * S).reshape(U, S)
    e = _width_rungs(cs_counts.max(axis=1))
    order = np.lexsort((u_cols, e))   # bucket-major, col-id within bucket
    rank = np.empty(U, np.int64)
    rank[order] = np.arange(U)
    pcol = (d_sel + rank[inv]).astype(np.int32)   # (m,) global prefix ids
    perm_cols, inv_perm = _column_perm(sel, u_cols, order, d)
    bucket_rows, bucket_vals = _sharded_occurrence_buckets(
        loc_rows, t_vals, rank[inv], s_ids, S, e, order)

    # per-shard ELL row buckets over a SHARED width ladder (t_rows is
    # ascending, so shard slices of the flat tail are contiguous and
    # _fill_ell's `starts` index straight into the global arrays)
    sb = np.searchsorted(t_rows, np.arange(S + 1) * n_local)
    shard_layouts = []
    for s in range(S):
        lo, hi = int(sb[s]), int(sb[s + 1])
        rbs = lo + np.searchsorted(loc_rows[lo:hi], np.arange(n_local + 1))
        counts_s = np.diff(rbs)
        shard_layouts.append((counts_s, _row_rungs(counts_s),
                              rbs[:-1].astype(np.int64)))
    ell_rows_own = [
        {int(ev): int((e_row_s == ev).sum())
         for ev in np.unique(e_row_s[e_row_s >= 0])}
        for _, e_row_s, _ in shard_layouts]
    widths: dict[int, int] = {}
    for own in ell_rows_own:
        for ev, r_b in own.items():
            widths[ev] = max(widths.get(ev, 0), r_b)
    ladder = sorted(widths.items())
    _count_shard_bytes(S, ell_rows_own, ladder, cs_counts, bucket_rows)
    pcol_rel = (pcol.astype(np.int64) - d_sel).astype(np.int32)
    per_shard = [_fill_ell(ladder, counts_s, e_row_s, starts_s, pcol_rel,
                           t_vals)
                 for counts_s, e_row_s, starts_s in shard_layouts]
    ell_pcols = tuple(np.stack([p[0][b] for p in per_shard])
                      for b in range(len(ladder)))
    ell_vals = tuple(np.stack([p[1][b] for p in per_shard])
                     for b in range(len(ladder)))
    row_pos = np.stack([p[2] for p in per_shard])
    _count_tail_slots(m_tot, ell_vals, bucket_vals)

    return ShardedBlockedEllRows(
        dense=dense, ell_pcols=ell_pcols, ell_vals=ell_vals,
        row_pos=row_pos,
        bucket_rows=bucket_rows, bucket_vals=bucket_vals,
        perm_cols=perm_cols, inv_perm=inv_perm,
        n_features=d, n_prefix=d_sel + U,
        last_col_pos=int(inv_perm[d - 1]), tail_nnz=int(m_tot))


def shard_hybrid(X: SparseRows | HybridRows, n_shards: int,
                 d_dense: int = 1024) -> ShardedHybridRows:
    """Re-lay a hybrid matrix for an `n_shards`-device mesh (see
    ShardedHybridRows). Rows must already divide `n_shards` — pad the batch
    first (`data.dataset.shard_hybrid_batch` does both).

    Host-side, one pass: the flat tail is row-sorted, so each shard's slice
    is contiguous (searchsorted on the shard row boundaries); slices are
    padded to the max per-shard tail length.
    """
    if isinstance(X, SparseRows):
        X = to_hybrid(X, d_dense)
    n = X.dense.shape[0]
    if n % n_shards != 0:
        raise ValueError(
            f"{n} rows do not divide {n_shards} shards; pad the batch first "
            "(data.dataset.shard_hybrid_batch)")
    n_local = n // n_shards
    tr = np.asarray(X.tail_rows)
    tc = np.asarray(X.tail_cols)
    tv = np.asarray(X.tail_vals)
    keep = tv != 0.0  # drop the sentinel / any padding before re-padding
    tr, tc, tv = tr[keep], tc[keep], tv[keep]
    bounds = np.searchsorted(tr, np.arange(n_shards + 1) * n_local)
    m = max(1, int(np.max(np.diff(bounds))))
    rows = np.full((n_shards, m), n_local - 1, np.int32)
    cols = np.zeros((n_shards, m), np.int32)
    vals = np.zeros((n_shards, m), np.asarray(X.tail_vals).dtype)
    for s in range(n_shards):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        c = hi - lo
        rows[s, :c] = tr[lo:hi] - s * n_local
        cols[s, :c] = tc[lo:hi]
        vals[s, :c] = tv[lo:hi]
    # Host leaves (dense keeps the input's residency); the one transfer
    # happens at _sharded_prep's device_put into the mesh sharding.
    return ShardedHybridRows(
        dense=X.dense,
        dense_cols=np.asarray(X.dense_cols),
        tail_rows=rows,
        tail_cols=cols,
        tail_vals=vals,
        n_features=X.n_features,
    )


def shard_permuted_hybrid(X: SparseRows, n_shards: int,
                          d_dense: int = 1024,
                          device_dense_dtype=None
                          ) -> ShardedPermutedHybridRows:
    """Build the scatter-free SHARDED permuted hybrid (see
    ShardedPermutedHybridRows) from padded COO rows. Rows must already
    divide ``n_shards`` (`data.dataset.shard_permuted_batch` pads + builds).

    One vectorized host pass, mirroring `to_permuted_hybrid` with a
    GLOBAL column permutation (hot prefix from global frequencies, tail
    ranks by occurrence bucket) and PER-SHARD structures: each shard's
    row-major flat tail slice (padded to the max shard length) and its
    occurrence-bucket matrices holding the shard's LOCAL occurrences of
    every bucket column (absent shards carry zero slots). A column's
    bucket comes from its MAX-LOCAL count across shards — not the global
    count — so per-shard padding stays under a third of the slots of the
    column's fullest shard.
    """
    n = np.asarray(X.indices).shape[0]
    d = X.n_features
    if n % n_shards != 0:
        raise ValueError(
            f"{n} rows do not divide {n_shards} shards; pad the batch first "
            "(data.dataset.shard_permuted_batch)")
    n_local = n // n_shards
    d_sel = min(d_dense, d)
    dense, sel, t_rows, t_cols, t_vals = _hot_cold_split(
        X, d_dense, device_dense_dtype)
    t_vals = t_vals.astype(np.float32)
    m_tot = t_rows.size
    S = n_shards

    if m_tot == 0:
        perm_cols = np.concatenate(
            [sel, np.setdiff1d(np.arange(d), sel)]).astype(np.int32)
        inv_perm = np.empty(d, np.int64)
        inv_perm[perm_cols] = np.arange(d)
        return ShardedPermutedHybridRows(
            dense=dense, tail_pcols=np.zeros((S, 1), np.int32),
            tail_vals=np.zeros((S, 1), np.float32),
            row_bounds=np.zeros((S, n_local + 1), np.int32),
            bucket_rows=(), bucket_vals=(),
            perm_cols=perm_cols, inv_perm=inv_perm.astype(np.int32),
            n_features=d, n_prefix=d_sel,
            last_col_pos=int(inv_perm[d - 1]))

    s_ids = (t_rows // n_local).astype(np.int64)       # (m,) shard per nnz
    loc_rows = (t_rows - s_ids * n_local).astype(np.int64)

    u_cols, inv, u_counts = np.unique(t_cols, return_inverse=True,
                                      return_counts=True)
    U = u_cols.size
    # per-(column, shard) occurrence counts -> MAX-LOCAL count per column
    cs_counts = np.bincount(inv * S + s_ids, minlength=U * S).reshape(U, S)
    e = _width_rungs(cs_counts.max(axis=1))
    order = np.lexsort((u_cols, e))   # bucket-major, col-id within bucket
    rank = np.empty(U, np.int64)
    rank[order] = np.arange(U)

    pcol = (d_sel + rank[inv]).astype(np.int32)   # (m,) global prefix ids
    perm_cols, inv_perm = _column_perm(sel, u_cols, order, d)

    # per-shard row-major flat tails (t_rows ascending -> shard slices are
    # contiguous); padding entries (pcol=d_sel, val=0) sit past each
    # shard's last row bound and contribute nothing either way
    sb = np.searchsorted(t_rows, np.arange(S + 1) * n_local)
    m = max(1, int(np.max(np.diff(sb))))
    tail_pcols = np.full((S, m), d_sel, np.int32)
    tail_vals = np.zeros((S, m), np.float32)
    row_bounds = np.zeros((S, n_local + 1), np.int32)
    for s in range(S):
        lo, hi = int(sb[s]), int(sb[s + 1])
        c = hi - lo
        tail_pcols[s, :c] = pcol[lo:hi]
        tail_vals[s, :c] = t_vals[lo:hi]
        row_bounds[s] = np.searchsorted(
            loc_rows[lo:hi], np.arange(n_local + 1)).astype(np.int32)

    bucket_rows, bucket_vals = _sharded_occurrence_buckets(
        loc_rows, t_vals, rank[inv], s_ids, S, e, order)

    return ShardedPermutedHybridRows(
        dense=dense, tail_pcols=tail_pcols, tail_vals=tail_vals,
        row_bounds=row_bounds,
        bucket_rows=bucket_rows, bucket_vals=bucket_vals,
        perm_cols=perm_cols, inv_perm=inv_perm,
        n_features=d, n_prefix=d_sel + U,
        last_col_pos=int(inv_perm[d - 1]))


def from_scipy_csr(csr, k: int | None = None, host: bool = False,
                   strict: bool = False) -> SparseRows:
    """Pad a scipy CSR matrix to fixed nnz-per-row (fully vectorized —
    no per-row Python loop, so billion-row ingestion is numpy-bound).

    If ``k`` is smaller than some row's nnz, the row keeps its k
    largest-|value| entries and a UserWarning reports how many rows were
    truncated and what FRACTION of the total |value| mass was dropped
    (the honest severity signal — a 0.01% mass drop is padding hygiene, a
    10% drop is a modeling decision). ``strict=True`` raises ValueError
    instead of truncating (the reference never truncates; Breeze vectors
    are exact).
    """
    n, d = csr.shape
    indptr = np.asarray(csr.indptr)
    row_nnz = np.diff(indptr)
    max_nnz = int(row_nnz.max()) if n else 0
    if k is None:
        k = max(1, max_nnz)
    col = np.asarray(csr.indices)
    dat = np.asarray(csr.data, np.float32)
    row = np.repeat(np.arange(n, dtype=np.int64), row_nnz)
    truncating = max_nnz > k
    if truncating:
        # Reorder within each row by descending |value| so the first k kept
        # below are the largest-magnitude entries.
        order = np.lexsort((-np.abs(dat), row))
        col, dat, row = col[order], dat[order], row[order]
    pos = np.arange(row.shape[0], dtype=np.int64) - np.repeat(
        indptr[:-1].astype(np.int64), row_nnz
    )
    keep = pos < k
    if truncating:
        n_trunc = int((row_nnz > k).sum())
        n_drop = int((~keep).sum())
        total_mass = float(np.abs(dat).sum())
        frac = float(np.abs(dat[~keep]).sum()) / total_mass \
            if total_mass > 0.0 else 0.0
        detail = (f"{n_trunc} rows exceed k={k} nnz (max row nnz = "
                  f"{max_nnz}); dropping {n_drop} smallest-|value| entries "
                  f"= {frac:.4%} of the total |value| mass")
        if strict:
            raise ValueError(f"from_scipy_csr(strict=True): {detail}")
        warnings.warn(
            f"from_scipy_csr: {detail}; keeping the k largest-|value| "
            "entries per row", stacklevel=2)
    indices = np.zeros((n, k), np.int32)
    values = np.zeros((n, k), np.float32)
    indices[row[keep], pos[keep]] = col[keep]
    values[row[keep], pos[keep]] = dat[keep]
    if host:  # numpy-backed (streaming chunks: no device round-trip)
        return SparseRows(indices, values, d)
    return SparseRows(jnp.asarray(indices), jnp.asarray(values), d)


def _tail_rowsum(contrib, row_bounds):
    """Per-row sums of row-major flat tail contributions via cumsum
    differences — the scatter-free segmented reduction ((n,) or (n, G);
    contrib may be (m,) or (m, G))."""
    zero = jnp.zeros((1,) + contrib.shape[1:], contrib.dtype)
    cs = jnp.concatenate([zero, jnp.cumsum(contrib, axis=0)])
    b = cs[row_bounds]
    return b[1:] - b[:-1]


def _permuted_matvec(X: PermutedHybridRows, w):
    """w: (d,) PERMUTED. Hot block against the contiguous prefix slice,
    tail via gather + cumsum row reduction — no scatter anywhere."""
    hot = jnp.matmul(X.dense, w[:X.d_sel].astype(X.dense.dtype),
                     preferred_element_type=jnp.float32)
    contrib = X.tail_vals.astype(jnp.float32) * w[X.tail_pcols]
    return hot + _tail_rowsum(contrib, X.row_bounds)


def _sperm_matvec(X: ShardedPermutedHybridRows, w):
    """Global (plain-jit) view of the sharded permuted matvec: per-shard
    cumsum tails vmapped over the shard axis. Inside shard_map the solver
    never reaches this — `local()` routes to the single-device ops."""
    hot = jnp.matmul(X.dense, w[:X.d_sel].astype(X.dense.dtype),
                     preferred_element_type=jnp.float32)
    if w.ndim == 1:
        contrib = X.tail_vals.astype(jnp.float32) * w[X.tail_pcols]
    else:
        contrib = X.tail_vals.astype(jnp.float32)[..., None] * w[X.tail_pcols]
    tails = jax.vmap(_tail_rowsum)(contrib, X.row_bounds)
    return hot + tails.reshape((X.dense.shape[0],) + w.shape[1:])


def _sperm_rmatvec(X: ShardedPermutedHybridRows, r, square: bool = False):
    """Global view of the sharded permuted rmatvec: per-shard bucket
    gather+reduce (local row ids index the shard's row slice), summed over
    shards, assembled by concatenation — still no scatter."""
    f32 = jnp.float32
    S, n_local = X.n_shards, X.n_local
    lanes = r.ndim == 2
    dense = X.dense * X.dense if square else X.dense
    parts = [jnp.matmul(dense.T, r.astype(X.dense.dtype),
                        preferred_element_type=f32)]
    r2 = r.reshape((S, n_local) + r.shape[1:])
    s_idx = jnp.arange(S)[:, None, None]
    for br, bv in zip(X.bucket_rows, X.bucket_vals):
        v = bv.astype(f32)
        if square:
            v = v * v
        g = r2[s_idx, br]                      # (S, c_b, k_b[, G])
        if lanes:
            parts.append(jnp.einsum("sck,sckg->cg", v, g))
        else:
            parts.append(jnp.einsum("sck,sck->c", v, g))
    pad = X.n_features - X.n_prefix
    if pad:
        shape = (pad, r.shape[1]) if lanes else (pad,)
        parts.append(jnp.zeros(shape, f32))
    return jnp.concatenate(parts, axis=0)


def _permuted_rmatvec(X: PermutedHybridRows, r, square: bool = False):
    """Xᵀr (or (X∘X)ᵀr with square=True): assembled by CONCATENATION — the
    hot block's matmul, each occurrence bucket's gather+reduce (columns
    emerge in prefix order by construction), zeros for the untouched
    suffix."""
    f32 = jnp.float32
    dense = X.dense * X.dense if square else X.dense
    parts = [jnp.matmul(dense.T, r.astype(X.dense.dtype),
                        preferred_element_type=f32)]
    for br, bv in zip(X.bucket_rows, X.bucket_vals):
        v = bv.astype(f32)
        if square:
            v = v * v
        parts.append(jnp.einsum("ck,ck->c", v, r[br]))
    pad = X.n_features - X.n_prefix
    if pad:
        parts.append(jnp.zeros((pad,), f32))
    return jnp.concatenate(parts)


def _permuted_matvec_lanes(X: PermutedHybridRows, W):
    """W: (d, G) PERMUTED lane-minor — hot is ONE (n, d_sel) × (d_sel, G)
    MXU matmul, the tail gather moves G contiguous floats per index."""
    hot = jnp.matmul(X.dense, W[:X.d_sel].astype(X.dense.dtype),
                     preferred_element_type=jnp.float32)
    contrib = X.tail_vals.astype(jnp.float32)[:, None] * W[X.tail_pcols]
    return hot + _tail_rowsum(contrib, X.row_bounds)


def _permuted_rmatvec_lanes(X: PermutedHybridRows, R):
    """R: (n, G) lane-minor cotangents → (d, G) by concatenation."""
    f32 = jnp.float32
    G = R.shape[1]
    parts = [jnp.matmul(X.dense.T, R.astype(X.dense.dtype),
                        preferred_element_type=f32)]
    for br, bv in zip(X.bucket_rows, X.bucket_vals):
        parts.append(jnp.einsum("ck,ckg->cg", bv.astype(f32), R[br]))
    pad = X.n_features - X.n_prefix
    if pad:
        parts.append(jnp.zeros((pad, G), f32))
    return jnp.concatenate(parts, axis=0)


def sorted_segment_sum(data, segment_ids, num_segments: int):
    """Scatter-free segment sum for ids SORTED ascending: one cumsum plus
    boundary gathers — the same cumulative-sum-difference machinery as the
    permuted layouts' tail reduction (`_tail_rowsum`), exposed for the
    other sorted-reduction consumers (evaluation/grouped.py).

    ``data``: (m,) or (m, G); ``segment_ids``: (m,) nondecreasing ints.
    Matches ``jax.ops.segment_sum(..., indices_are_sorted=True)`` up to
    f32 summation order, with zero combining scatters in the traced
    program (segment boundaries come from a binary-search
    ``searchsorted``, per-segment sums from cumsum differences)."""
    bounds = jnp.searchsorted(
        jnp.asarray(segment_ids),
        jnp.arange(num_segments + 1, dtype=jnp.int32))
    return _tail_rowsum(data, bounds)


def _bell_compute(v, g):
    """(values, gathered) in the tail-contraction compute dtype: bf16
    storage multiplies in bf16 (the MXU recipe — f32 accumulation is
    pinned at the einsum), f32 storage stays exact f32."""
    if g.dtype != v.dtype:
        g = g.astype(v.dtype)
    return v, g


def _bell_tail(X, w):
    """Blocked-ELL tail matvec, bucket by bucket: one gather of the SMALL
    contiguous tail-coefficient slice w[d_sel:n_prefix] (ell_pcols are
    prefix-relative — the gather table is the ~U distinct tail columns,
    cache-resident at 10M-feature scale) + one dense einsum (f32
    accumulation) per width bucket. Returns the bucket outputs in ladder
    order; the caller lays them over the rows. w: (d,) or (d, G)
    permuted; works on the (S, ...) sharded buckets unchanged (the einsum
    string carries the extra axis).
    """
    lanes = w.ndim == 2
    sharded = isinstance(X, ShardedBlockedEllRows)
    parts = []
    wt = w[X.d_sel:X.n_prefix]
    for pc, pv in zip(X.ell_pcols, X.ell_vals):
        v, g = _bell_compute(pv, wt[pc])      # ([S,] r_b, W_b[, G])
        eq = ("srw,srwg->srg" if lanes else "srw,srw->sr") if sharded \
            else ("rw,rwg->rg" if lanes else "rw,rw->r")
        parts.append(jnp.einsum(eq, v, g,
                                preferred_element_type=jnp.float32))
    return parts


def _bell_matvec(X: BlockedEllRows, w):
    """w: (d,) or (d, G) PERMUTED → (n,) / (n, G) in the layout's STORED
    row order. Hot block against the contiguous prefix slice, blocked-ELL
    tail — gathers of `w` and dense contractions only. A stored-order
    layout (`X.row_order`) lays the bucket outputs over its rows by
    CONCATENATION (zeros for the tail-free rows at the end); a
    caller-order one (a shard or chunk view) by the `row_pos` gather."""
    with device_scope("xpass.fwd.hot"):
        hot = jnp.matmul(X.dense, w[:X.d_sel].astype(X.dense.dtype),
                         preferred_element_type=jnp.float32)
    if not X.ell_vals:
        return hot
    with device_scope("xpass.fwd.tail"):
        parts = _bell_tail(X, w)
        if X.row_order is not None:
            rest = X.dense.shape[0] - X.tail_rows
            if rest:
                parts.append(jnp.zeros((rest,) + w.shape[1:], jnp.float32))
            return hot + jnp.concatenate(parts, axis=0)
    with device_scope("xpass.fwd.reassemble"):
        zero = jnp.zeros((1,) + w.shape[1:], jnp.float32)
        tail = jnp.concatenate(parts + [zero], axis=0)[X.row_pos]
    return hot + tail


def _bell_rmatvec(X: BlockedEllRows, r, square: bool = False):
    """Xᵀr (or (X∘X)ᵀr): hot matmul + per-occurrence-bucket pre-sorted
    gather/reduce, assembled by concatenation — no scatter. r: (n,) or
    (n, G)."""
    f32 = jnp.float32
    lanes = r.ndim == 2
    with device_scope("xpass.t.hot"):
        dense = X.dense * X.dense if square else X.dense
        parts = [jnp.matmul(dense.T, r.astype(X.dense.dtype),
                            preferred_element_type=f32)]
    with device_scope("xpass.t.tail"):
        for br, bv in zip(X.bucket_rows, X.bucket_vals):
            if square:
                v = bv.astype(f32)
                v, g = v * v, r[br].astype(f32)
            else:
                v, g = _bell_compute(bv, r[br])
            eq = "ck,ckg->cg" if lanes else "ck,ck->c"
            parts.append(jnp.einsum(eq, v, g, preferred_element_type=f32))
    pad = X.n_features - X.n_prefix
    if pad:
        parts.append(jnp.zeros((pad, r.shape[1]) if lanes else (pad,), f32))
    return jnp.concatenate(parts, axis=0)


def _sbell_matvec(X: ShardedBlockedEllRows, w):
    """Global (plain-jit) view of the sharded blocked-ELL matvec: the
    per-shard bucket einsums carry the shard axis, the reassembly gather
    vmaps over shards. Inside shard_map the solver never reaches this —
    `local()` routes to the single-device ops."""
    with device_scope("xpass.fwd.hot"):
        hot = jnp.matmul(X.dense, w[:X.d_sel].astype(X.dense.dtype),
                         preferred_element_type=jnp.float32)
    lanes = w.ndim == 2
    S = X.n_shards
    with device_scope("xpass.fwd.tail"):
        parts = _bell_tail(X, w)
    with device_scope("xpass.fwd.reassemble"):
        zero = jnp.zeros((S, 1, w.shape[1]) if lanes else (S, 1),
                         jnp.float32)
        cat = jnp.concatenate(parts + [zero], axis=1)
        tail = jax.vmap(lambda c, rp: c[rp])(cat, jnp.asarray(X.row_pos))
        tail = tail.reshape((X.dense.shape[0],) + w.shape[1:])
    return hot + tail


def _sbell_rmatvec(X: ShardedBlockedEllRows, r, square: bool = False):
    """Global view of the sharded blocked-ELL rmatvec: per-shard
    occurrence-bucket gather/reduce summed over shards, assembled by
    concatenation — still no scatter."""
    f32 = jnp.float32
    S, n_local = X.n_shards, X.n_local
    lanes = r.ndim == 2
    with device_scope("xpass.t.hot"):
        dense = X.dense * X.dense if square else X.dense
        parts = [jnp.matmul(dense.T, r.astype(X.dense.dtype),
                            preferred_element_type=f32)]
    with device_scope("xpass.t.tail"):
        r2 = r.reshape((S, n_local) + r.shape[1:])
        s_idx = jnp.arange(S)[:, None, None]
        for br, bv in zip(X.bucket_rows, X.bucket_vals):
            g = r2[s_idx, br]                      # (S, c_b, k_b[, G])
            if square:
                v = bv.astype(f32)
                v, g = v * v, g.astype(f32)
            else:
                v, g = _bell_compute(bv, g)
            eq = "sck,sckg->cg" if lanes else "sck,sck->c"
            parts.append(jnp.einsum(eq, v, g, preferred_element_type=f32))
    pad = X.n_features - X.n_prefix
    if pad:
        parts.append(jnp.zeros((pad, r.shape[1]) if lanes else (pad,), f32))
    return jnp.concatenate(parts, axis=0)


def rows_from_caller(X: Matrix, v):
    """Per-row values ((n,) or (n, ...), host or device) in the CALLER's
    row order → the order X stores its rows in. The identity for every
    layout but a stored-order BlockedEllRows."""
    if isinstance(X, BlockedEllRows) and X.row_order is not None:
        return v[X.row_order]
    return v


def rows_to_caller(X: Matrix, v):
    """The inverse of `rows_from_caller`: per-row values in X's stored
    order → the caller's."""
    if isinstance(X, BlockedEllRows) and X.row_order is not None:
        with device_scope("xpass.fwd.reassemble"):
            return v[X.row_pos]
    return v


@device_scope("xpass.fwd")
def matvec(X: Matrix, w: jax.Array) -> jax.Array:
    """X @ w -> (n,) in the CALLER's row order: row i of the result is row
    i of the matrix the layout was built from (`layout_matvec` is the form
    a solver evaluates). The scoring path."""
    return rows_to_caller(X, _matvec(X, w))


@device_scope("xpass.fwd")
def layout_matvec(X: Matrix, w: jax.Array) -> jax.Array:
    """X @ w -> (n,) in the order X STORES its rows — the order of the y /
    weights / offsets of a GLMBatch over X, and of the cotangent `rmatvec`
    takes. The GLM margin hot path: a `to_blocked_ell` layout's has no
    per-row gather."""
    return _matvec(X, w)


def _matvec(X: Matrix, w: jax.Array) -> jax.Array:
    """X @ w -> (n,), stored row order.

    Mixed precision: when X is stored in bfloat16 (see dataset.cast_features),
    w is cast to bf16 so the contraction's OPERANDS are bf16 (half the HBM
    traffic, native MXU input width) while `preferred_element_type=float32`
    keeps the ACCUMULATION in f32 — the TPU matmul recipe. Output is always
    f32; everything downstream (losses, solver state) never sees bf16.

    PermutedHybridRows expects w in ITS permuted space (see the class
    docstring; models/training and models/glm translate at their
    boundaries).
    """
    if isinstance(X, BlockedEllRows):
        return _bell_matvec(X, w)
    if isinstance(X, ShardedBlockedEllRows):
        return _sbell_matvec(X, w)
    if isinstance(X, PermutedHybridRows):
        return _permuted_matvec(X, w)
    if isinstance(X, ShardedPermutedHybridRows):
        return _sperm_matvec(X, w)
    if isinstance(X, ShardedHybridRows):
        rows, cols, vals = X._global_tail()
        tail = jax.ops.segment_sum(
            vals.astype(jnp.float32) * w[cols], rows,
            num_segments=X.dense.shape[0], indices_are_sorted=True)
        return tail + jnp.matmul(
            X.dense, w[X.dense_cols].astype(X.dense.dtype),
            preferred_element_type=jnp.float32)
    if isinstance(X, HybridRows):
        tail = jax.ops.segment_sum(
            X.tail_vals.astype(jnp.float32) * w[X.tail_cols],
            X.tail_rows, num_segments=X.dense.shape[0],
            indices_are_sorted=True)
        return tail + jnp.matmul(
            X.dense, w[X.dense_cols].astype(X.dense.dtype),
            preferred_element_type=jnp.float32)
    if isinstance(X, SparseRows):
        # Sparse runs on the VPU (gather + multiply + reduce), never the MXU:
        # bf16 is a STORAGE format only — upcast in registers, full-precision
        # products, f32 accumulation. w/r vectors are small; never downcast.
        # (a multiply and a sum, not an einsum: a batched dot may run on
        # the MXU at its default precision)
        return jnp.sum(X.values.astype(jnp.float32) * w[X.indices], axis=-1)
    return jnp.matmul(X, w.astype(X.dtype), precision=_dense_precision(X),
                      preferred_element_type=jnp.float32)


def _dense_precision(X: jax.Array):
    """Matmul precision of a plain dense X against one vector. f32 storage
    MEANS f32 products: the TPU's default would round both operands to bf16
    (2^-9 a product), which a solve that is meant to reach f32 resolution
    cannot see past. bf16 storage is exact in one pass already. One vector
    a pass is bound by reading X, not by the multiplier, so the extra MXU
    passes cost little. Where it matters on the v5e: the per-entity solves'
    transposed pass under `vmap` is an MXU convolution (off 2.4e-3 of the
    result's scale at the default, 7e-8 at HIGHEST); the forward pass and
    an unbatched (n, d) shard are f32 multiply-and-reduce either way
    (PERF.md section 6, PR 27)."""
    return (jax.lax.Precision.HIGHEST if X.dtype == jnp.float32 else None)


@device_scope("xpass.t")
def rmatvec(X: Matrix, r: jax.Array) -> jax.Array:
    """X^T @ r -> (d,). The gradient aggregation hot path (f32 accumulation,
    bf16-storage aware like matvec). ``r`` is in the order X STORES its
    rows (a GLMBatch's order; `rows_from_caller` translates a caller-order
    vector) — as for rmatvec_lanes, sq_rmatvec and weighted_gram."""
    if isinstance(X, BlockedEllRows):
        return _bell_rmatvec(X, r)
    if isinstance(X, ShardedBlockedEllRows):
        return _sbell_rmatvec(X, r)
    if isinstance(X, PermutedHybridRows):
        return _permuted_rmatvec(X, r)
    if isinstance(X, ShardedPermutedHybridRows):
        return _sperm_rmatvec(X, r)
    if isinstance(X, ShardedHybridRows):
        rows, cols, vals = X._global_tail()
        out = jax.ops.segment_sum(
            vals.astype(jnp.float32) * r[rows], cols,
            num_segments=X.n_features)
        hot = jnp.matmul(X.dense.T, r.astype(X.dense.dtype),
                         preferred_element_type=jnp.float32)
        return out.at[X.dense_cols].add(hot)
    if isinstance(X, HybridRows):
        out = jax.ops.segment_sum(
            X.tail_vals.astype(jnp.float32) * r[X.tail_rows],
            X.tail_cols, num_segments=X.n_features)
        hot = jnp.matmul(X.dense.T, r.astype(X.dense.dtype),
                         preferred_element_type=jnp.float32)
        return out.at[X.dense_cols].add(hot)
    if isinstance(X, SparseRows):
        contrib = (X.values.astype(jnp.float32) * r[:, None]).reshape(-1)
        return jax.ops.segment_sum(
            contrib, X.indices.reshape(-1), num_segments=X.n_features,
        )
    return jnp.matmul(X.T, r.astype(X.dtype), precision=_dense_precision(X),
                      preferred_element_type=jnp.float32)


@device_scope("xpass.fwd")
def matvec_lanes(X: Matrix, W: jax.Array) -> jax.Array:
    """X @ W -> (n, G), lane-minor W: (d, G), rows in the CALLER's order
    (see `matvec`)."""
    return rows_to_caller(X, _matvec_lanes(X, W))


@device_scope("xpass.fwd")
def layout_matvec_lanes(X: Matrix, W: jax.Array) -> jax.Array:
    """X @ W -> (n, G), rows in X's STORED order (see `layout_matvec`):
    the multi-lane (reg-weight grid) hot path."""
    return _matvec_lanes(X, W)


def _matvec_lanes(X: Matrix, W: jax.Array) -> jax.Array:
    """X @ W -> (n, G) for LANE-MINOR stacked coefficients W: (d, G),
    stored row order.

    The multi-lane (reg-weight grid) hot path. Lane-minor layout is the
    TPU-native form: the hot dense block becomes ONE true (n, d_sel) ×
    (d_sel, G) MXU matmul shared by every lane, and the tail gather
    W[tail_cols] fetches G *contiguous* floats per index — the same number
    of random accesses as a single lane. A vmapped single-lane matvec
    (lane-MAJOR (G, d)) pays both per lane: measured ~3.5× slower at G=4
    on the 10M-feature headline problem (docs/PERF.md).
    """
    if isinstance(X, BlockedEllRows):
        return _bell_matvec(X, W)
    if isinstance(X, ShardedBlockedEllRows):
        return _sbell_matvec(X, W)
    if isinstance(X, PermutedHybridRows):
        return _permuted_matvec_lanes(X, W)
    if isinstance(X, ShardedPermutedHybridRows):
        return _sperm_matvec(X, W)
    if isinstance(X, ShardedHybridRows):
        rows, cols, vals = X._global_tail()
        tail = jax.ops.segment_sum(
            vals.astype(jnp.float32)[:, None] * W[cols], rows,
            num_segments=X.dense.shape[0], indices_are_sorted=True)
        return tail + jnp.matmul(
            X.dense, W[X.dense_cols].astype(X.dense.dtype),
            preferred_element_type=jnp.float32)
    if isinstance(X, HybridRows):
        tail = jax.ops.segment_sum(
            X.tail_vals.astype(jnp.float32)[:, None] * W[X.tail_cols],
            X.tail_rows, num_segments=X.dense.shape[0],
            indices_are_sorted=True)
        return tail + jnp.matmul(
            X.dense, W[X.dense_cols].astype(X.dense.dtype),
            preferred_element_type=jnp.float32)
    if isinstance(X, SparseRows):
        # (n, k, G) gather then contraction over k on the VPU; storage bf16
        # upcasts in registers as in matvec.
        return jnp.einsum("nk,nkg->ng", X.values.astype(jnp.float32),
                          W[X.indices])
    return jnp.matmul(X, W.astype(X.dtype), preferred_element_type=jnp.float32)


@device_scope("xpass.t")
def rmatvec_lanes(X: Matrix, R: jax.Array) -> jax.Array:
    """X^T @ R -> (d, G) for lane-minor per-row cotangents R: (n, G).

    The multi-lane gradient aggregation: the tail scatter-add lands G
    contiguous floats per segment id (one scatter row of width G instead of
    G scalar scatters), the hot block is one (d_sel, n) × (n, G) matmul.
    """
    if isinstance(X, BlockedEllRows):
        return _bell_rmatvec(X, R)
    if isinstance(X, ShardedBlockedEllRows):
        return _sbell_rmatvec(X, R)
    if isinstance(X, PermutedHybridRows):
        return _permuted_rmatvec_lanes(X, R)
    if isinstance(X, ShardedPermutedHybridRows):
        return _sperm_rmatvec(X, R)
    if isinstance(X, ShardedHybridRows):
        rows, cols, vals = X._global_tail()
        out = jax.ops.segment_sum(
            vals.astype(jnp.float32)[:, None] * R[rows], cols,
            num_segments=X.n_features)
        hot = jnp.matmul(X.dense.T, R.astype(X.dense.dtype),
                         preferred_element_type=jnp.float32)
        return out.at[X.dense_cols].add(hot)
    if isinstance(X, HybridRows):
        out = jax.ops.segment_sum(
            X.tail_vals.astype(jnp.float32)[:, None] * R[X.tail_rows],
            X.tail_cols, num_segments=X.n_features)
        hot = jnp.matmul(X.dense.T, R.astype(X.dense.dtype),
                         preferred_element_type=jnp.float32)
        return out.at[X.dense_cols].add(hot)
    if isinstance(X, SparseRows):
        contrib = (X.values.astype(jnp.float32)[:, :, None]
                   * R[:, None, :])  # (n, k, G)
        G = R.shape[1]
        return jax.ops.segment_sum(
            contrib.reshape(-1, G), X.indices.reshape(-1),
            num_segments=X.n_features)
    return jnp.matmul(X.T, R.astype(X.dtype), preferred_element_type=jnp.float32)


@device_scope("xpass.t")
def sq_rmatvec(X: Matrix, r: jax.Array) -> jax.Array:
    """(X∘X)^T @ r -> (d,): Hessian diagonal building block.

    Duplicate (row, col) COO entries: SparseRows squares each ENTRY
    (a² + b²), while the hybrid representations pre-aggregate the cell
    (a + b)² in their dense block. Feature-bag rows never repeat a feature
    (reference: one value per feature name+term per example), so the
    distinction never arises on real data; dedupe the COO if yours can.
    """
    if isinstance(X, BlockedEllRows):
        return _bell_rmatvec(X, r, square=True)
    if isinstance(X, ShardedBlockedEllRows):
        return _sbell_rmatvec(X, r, square=True)
    if isinstance(X, PermutedHybridRows):
        return _permuted_rmatvec(X, r, square=True)
    if isinstance(X, ShardedPermutedHybridRows):
        return _sperm_rmatvec(X, r, square=True)
    if isinstance(X, ShardedHybridRows):
        rows, cols, vals = X._global_tail()
        tv = vals.astype(jnp.float32)
        out = jax.ops.segment_sum(
            tv * tv * r[rows], cols, num_segments=X.n_features)
        hot = jnp.matmul((X.dense * X.dense).T, r.astype(X.dense.dtype),
                         preferred_element_type=jnp.float32)
        return out.at[X.dense_cols].add(hot)
    if isinstance(X, HybridRows):
        tv = X.tail_vals.astype(jnp.float32)
        out = jax.ops.segment_sum(
            tv * tv * r[X.tail_rows], X.tail_cols,
            num_segments=X.n_features)
        hot = jnp.matmul((X.dense * X.dense).T, r.astype(X.dense.dtype),
                         preferred_element_type=jnp.float32)
        return out.at[X.dense_cols].add(hot)
    if isinstance(X, SparseRows):
        v = X.values.astype(jnp.float32)
        contrib = (v * v * r[:, None]).reshape(-1)
        return jax.ops.segment_sum(
            contrib, X.indices.reshape(-1), num_segments=X.n_features,
        )
    return jnp.matmul((X * X).T, r.astype(X.dtype),
                      preferred_element_type=jnp.float32)


MAX_GRAM_FEATURES = 20_000


def weighted_gram(X: Matrix, r: jax.Array) -> jax.Array:
    """X^T diag(r) X -> (d, d). Dense-only; used for full-Hessian variances
    (reference: VarianceComputationType.FULL) on small feature spaces.

    Sparse inputs are densified, so d is capped at MAX_GRAM_FEATURES —
    at the 10M-feature regime a (d, d) Gram is impossible anyway; use
    hess_diag (VarianceComputationType.SIMPLE) there.
    """
    if isinstance(X, (PermutedHybridRows, BlockedEllRows)):
        if X.n_features > MAX_GRAM_FEATURES:
            raise ValueError(
                f"weighted_gram densifies {type(X).__name__}: "
                f"d={X.n_features} exceeds "
                f"MAX_GRAM_FEATURES={MAX_GRAM_FEATURES}; use "
                "hess_diag/SIMPLE variances for large feature spaces"
            )
        # Densify in PERMUTED space (the solver's space — consistent with
        # every other X op on this representation).
        n, d = X.dense.shape[0], X.n_features
        rows = jnp.zeros((n, d), jnp.float32)
        rows = rows.at[:, :X.d_sel].add(X.dense.astype(jnp.float32))
        off = X.d_sel
        for br, bv in zip(X.bucket_rows, X.bucket_vals):
            c_b = br.shape[0]
            cols_ids = off + jnp.arange(c_b)
            rows = rows.at[br, cols_ids[:, None]].add(
                bv.astype(jnp.float32))
            off += c_b
        return (rows * r[:, None]).T @ rows
    if isinstance(X, (HybridRows, ShardedHybridRows)):
        if X.n_features > MAX_GRAM_FEATURES:
            raise ValueError(
                f"weighted_gram densifies HybridRows: d={X.n_features} "
                f"exceeds MAX_GRAM_FEATURES={MAX_GRAM_FEATURES}; use "
                "hess_diag/SIMPLE variances for large feature spaces"
            )
        n = X.dense.shape[0]
        if isinstance(X, ShardedHybridRows):
            t_rows, t_cols, t_vals = X._global_tail()
        else:
            t_rows, t_cols, t_vals = X.tail_rows, X.tail_cols, X.tail_vals
        rows = jnp.zeros((n, X.n_features), jnp.float32)
        rows = rows.at[:, X.dense_cols].add(X.dense.astype(jnp.float32))
        rows = rows.at[t_rows, t_cols].add(t_vals.astype(jnp.float32))
        return (rows * r[:, None]).T @ rows
    if isinstance(X, SparseRows):
        n, k = X.indices.shape
        d = X.n_features
        if d > MAX_GRAM_FEATURES:
            raise ValueError(
                f"weighted_gram densifies SparseRows: d={d} exceeds "
                f"MAX_GRAM_FEATURES={MAX_GRAM_FEATURES}; use hess_diag/"
                "SIMPLE variances for large feature spaces"
            )
        rows = jnp.zeros((n, d), jnp.float32)
        rows = rows.at[jnp.arange(n)[:, None], X.indices].add(
            X.values.astype(jnp.float32))
        return (rows * r[:, None]).T @ rows
    # Small-d variance path: plain f32 regardless of storage dtype.
    return (X.astype(jnp.float32) * r[:, None]).T @ X.astype(jnp.float32)


def next_pow2(x: int, floor: int = 2) -> int:
    """Smallest power of two ≥ x (≥ floor) — the static-shape bucket padding
    used for entity row counts and projected feature dims alike."""
    m = floor
    while m < x:
        m *= 2
    return m


def quantize_rows(n: int, quantum: int) -> int:
    """Smallest multiple of ``quantum`` ≥ n (≥ quantum) — the linear rung of
    the static-shape height ladder. Chunked paths whose heights cluster
    around a known chunk size (the scoring driver's streamed blocks)
    quantize linearly so XLA compiles a handful of shapes without pow2's
    up-to-2× pad waste; open-ended heights (serving request batches,
    entity lane counts) bucket by `next_pow2` instead."""
    q = int(quantum)
    return max((max(int(n), 1) + q - 1) // q * q, q)


def quantize_blocks(block, mode: str = "int8"):
    """Row-wise symmetric quantization of a serving coefficient block —
    the store-load half of the quantized serving rungs (serving/programs
    fuses the matching dequant into the margin matvec).

    ``block``: a (d,) fixed-effect vector (ONE scale) or an (E + 1, d)
    random-effect block (one scale PER ROW — per-entity dynamic range;
    a global scale would crush small-norm entities under one hot one).

    ``mode="int8"`` → ``(q int8, scales f32)`` with ``scales =
    max|row| / 127`` and ``q = round(row / scale)``; dequant is
    ``q * scale``. All-zero rows (the cold-miss row E) take scale 1.0 so
    they dequantize to EXACT zeros — the graceful-degradation row stays
    bit-exact. ``mode="bf16"`` → ``(q bf16, None)``: a plain storage
    cast (half the bytes, ~3 decimal digits), no scales needed.
    """
    arr = np.ascontiguousarray(np.asarray(block, np.float32))
    if mode == "bf16":
        return arr.astype(jnp.bfloat16), None
    if mode != "int8":
        raise ValueError(f"quantize mode must be 'int8' or 'bf16', "
                         f"got {mode!r}")
    vec = arr.ndim == 1
    rows = arr[None] if vec else arr
    scales = np.abs(rows).max(axis=1) / 127.0
    scales = np.where(scales > 0.0, scales, 1.0).astype(np.float32)
    q = np.clip(np.rint(rows / scales[:, None]), -127, 127).astype(np.int8)
    if vec:
        return q[0], np.float32(scales[0])
    return q, scales


def last_column_is_intercept(X: Matrix) -> bool:
    """True when the design matrix's last column is constant 1 — the
    data.feature_bags intercept-last convention."""
    def _host_col(dense, j):
        # Slice BEFORE the host transfer: a device-resident dense block
        # (to_*_hybrid device_dense_dtype) then moves (n,) floats to answer
        # this, not the whole multi-GB block.
        return np.asarray(dense[:, j])

    if isinstance(X, (PermutedHybridRows, BlockedEllRows)):
        if X.last_col_pos < X.d_sel:  # an intercept is maximally hot
            return bool((_host_col(X.dense, X.last_col_pos) == 1.0).all())
        if X.last_col_pos >= X.n_prefix:
            return False  # untouched by this batch → has zero entries
        # Hot-selection tie-break can leave an every-row column in the
        # tail (many columns hit all n rows, argpartition picks d_sel of
        # them arbitrarily): scan its occurrence bucket — constant-1 in
        # every row means n entries, all 1.0, rows a permutation of
        # range(n).
        n = X.dense.shape[0]
        off = X.d_sel
        for br, bv in zip(X.bucket_rows, X.bucket_vals):
            c_b = br.shape[0]
            if X.last_col_pos < off + c_b:
                r = np.asarray(br[X.last_col_pos - off])
                v = np.asarray(bv[X.last_col_pos - off])
                real = v != 0.0
                return bool(int(real.sum()) == n and (v[real] == 1.0).all()
                            and (np.sort(r[real]) == np.arange(n)).all())
            off += c_b
        return False
    if isinstance(X, (HybridRows, ShardedHybridRows)):
        d = X.n_features
        cols = np.asarray(X.dense_cols)
        if d - 1 in cols:  # intercept is maximally hot: dense block
            col = _host_col(X.dense, int(np.where(cols == d - 1)[0][0]))
            return bool((col == 1.0).all())
        if isinstance(X, ShardedHybridRows):
            t_rows = np.asarray(X._global_tail()[0])
        else:
            t_rows = np.asarray(X.tail_rows)
        tc, tv = np.asarray(X.tail_cols).reshape(-1), \
            np.asarray(X.tail_vals).reshape(-1)
        hit = (tc == d - 1) & (tv != 0.0)
        per_row = np.zeros(X.shape[0], bool)
        per_row[t_rows[hit]] = True
        return bool(per_row.all() and (tv[hit] == 1.0).all())
    if isinstance(X, SparseRows):
        d = X.n_features
        ind, val = np.asarray(X.indices), np.asarray(X.values)
        hit = (ind == d - 1) & (val != 0.0)
        return bool(hit.any(axis=1).all() and (val[hit] == 1.0).all())
    col = np.asarray(X)[:, -1]
    return bool((col == 1.0).all())


def nnz_stats(X: Matrix) -> tuple[int, int]:
    n = X.shape[0]
    if isinstance(X, SparseRows):
        return n, int(np.prod(X.values.shape))
    if isinstance(X, PermutedHybridRows):
        return n, int(np.prod(X.dense.shape)) + int(X.tail_vals.shape[0])
    if isinstance(X, (BlockedEllRows, ShardedBlockedEllRows)):
        return n, int(np.prod(X.dense.shape)) + X.tail_nnz
    return n, int(np.prod(X.shape))


# ----------------------------------------------------------------- contracts
# Static-analysis contracts for the blocked-ELL layout, registered NEXT TO
# the layout they pin (photon_tpu/analysis convention): BOTH X passes are
# scatter-free — not just combining-scatter-free, the FULL scatter family
# is forbidden — and every tail dot/einsum accumulates f32 even with bf16
# storage (`require_f32_accum`, the round-12 dtype rule).
from photon_tpu.analysis.contracts import register_contract  # noqa: E402
from photon_tpu.analysis.walker import SCATTER_PRIMITIVES  # noqa: E402


def _contract_blocked_ell(n=48, d=96, k=6, d_dense=16, bf16=False):
    """A small zipf blocked-ELL matrix (hot block + multi-width ELL tail
    + occurrence buckets all populated); bf16=True casts feature storage
    the way dataset.cast_features does."""
    rng = np.random.default_rng(0)
    col = (rng.zipf(1.5, size=(n, k)).astype(np.int64) - 1) % (d - 1)
    val = rng.normal(size=(n, k)).astype(np.float32)
    ind = np.concatenate([col, np.full((n, 1), d - 1)], axis=1).astype(
        np.int32)
    va = np.concatenate([val, np.ones((n, 1), np.float32)], axis=1)
    X = to_blocked_ell(SparseRows(ind, va, d), d_dense)
    if bf16:
        bf = jnp.bfloat16
        X = dataclasses.replace(
            X, dense=jnp.asarray(X.dense).astype(bf),
            ell_vals=tuple(jnp.asarray(v).astype(bf) for v in X.ell_vals),
            bucket_vals=tuple(jnp.asarray(v).astype(bf)
                              for v in X.bucket_vals))
    return X


@register_contract(
    name="blocked_ell_x_passes",
    description="BlockedEllRows matvec + rmatvec (bf16 storage) traced as "
                "one program: gather-fused tail, ZERO scatters of any "
                "kind in either X pass, every sparse dot/einsum "
                "accumulating f32",
    collectives={}, forbid=SCATTER_PRIMITIVES, require_f32_accum=True,
    tags=("resident", "sparse"))
def _contract_blocked_ell_x_passes():
    X = _contract_blocked_ell(bf16=True)
    n, d = X.shape

    def both(Xb, w, r):
        z = layout_matvec(Xb, w)          # X pass 1: the margin
        return z, rmatvec(Xb, r * z)      # X pass 2: the gradient backprop

    return both, (X, jnp.zeros((d,), jnp.float32),
                  jnp.zeros((n,), jnp.float32))


@register_contract(
    name="blocked_ell_lane_x_passes",
    description="BlockedEllRows lane-minor X passes (matvec_lanes + "
                "rmatvec_lanes, G=4, bf16 storage): scatter-free, f32 "
                "accumulation — the reg-sweep form of the same law",
    collectives={}, forbid=SCATTER_PRIMITIVES, require_f32_accum=True,
    tags=("resident", "lane", "sparse"))
def _contract_blocked_ell_lane_x_passes():
    X = _contract_blocked_ell(bf16=True)
    n, d = X.shape
    G = 4

    def both(Xb, W, R):
        Z = layout_matvec_lanes(Xb, W)
        return Z, rmatvec_lanes(Xb, R * Z)

    return both, (X, jnp.zeros((d, G), jnp.float32),
                  jnp.zeros((n, G), jnp.float32))
