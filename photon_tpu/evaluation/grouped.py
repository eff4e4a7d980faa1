"""Per-entity (sharded) metrics via SORTED-segment ops — scatter-free.

Reference parity: com.linkedin.photon.ml.evaluation.{ShardedAUCEvaluator,
ShardedPrecisionAtKEvaluator} — metrics computed per entity id (e.g. per
query/document) and averaged across entities. The reference groups with a
Spark groupBy per id; here a single sort pass computes every group's
metric simultaneously on device — no per-group dispatch.

Round 12: the per-group reductions ride the SAME sorted-segment machinery
as the blocked sparse layouts (`data.matrix.sorted_segment_sum` — cumsum
+ boundary gathers) instead of `jax.ops.segment_sum`'s combining
scatters, and the segmented min/max these metrics need are all over
MONOTONE sequences (cumulative sums, arange), so they reduce to boundary
gathers too. The traced programs contain ZERO scatters of any kind
(pinned by the `grouped_auc_scatter_free` contract below); the scatter
elements this saves per call are counted on the
``eval.scatter_elems_saved`` telemetry counter (one element per value
that would have entered a combining scatter-add/min/max).

Groups are dense int ids in [0, num_groups); rows with weight 0 are padding.
Groups where the metric is undefined (e.g. single-class for AUC, empty for
P@K) are excluded from the average, as in the reference.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from photon_tpu.data.matrix import sorted_segment_sum

# jit at the impl entry points: one dispatch per metric call (the
# static group/k counts key the cache) instead of one launch per
# primitive. The public wrappers below only add the host-side telemetry
# count.


def _sort_by_group_then_key(groups, key):
    """Stable order: by group, then by `key` ascending within the group."""
    order1 = jnp.argsort(key, stable=True)
    order2 = jnp.argsort(groups[order1], stable=True)
    return order1[order2]


def _mean_over_valid(per_group, valid):
    """Unweighted mean over valid groups; NaN when none is valid."""
    n_valid = jnp.sum(valid.astype(jnp.float32))
    return jnp.where(
        n_valid > 0.0,
        jnp.sum(jnp.where(valid, per_group, 0.0)) / jnp.maximum(n_valid, 1.0),
        jnp.nan,
    )


def _bounds(sorted_ids, num_segments: int):
    """Segment boundaries of SORTED ids: bounds[s]..bounds[s+1] is segment
    s's row range (empty segments collapse)."""
    return jnp.searchsorted(
        sorted_ids, jnp.arange(num_segments + 1, dtype=jnp.int32))


def _first_of_segment(x, bounds, n):
    """x at each segment's FIRST row (x monotone ⇒ the segmented min of a
    nondecreasing sequence). Empty segments gather a clamped neighbor —
    callers only read non-empty segments (per-row gathers / valid masks).
    """
    return x[jnp.minimum(bounds[:-1], n - 1)]


def _last_of_segment(x, bounds):
    """x at each segment's LAST row (x monotone ⇒ the segmented max of a
    nondecreasing sequence)."""
    return x[jnp.maximum(bounds[1:] - 1, 0)]


def _count_saved(*segment_input_lengths) -> None:
    """Telemetry: elements that would have entered a combining scatter
    under the segment_sum/min/max formulation (host-side, per call)."""
    from photon_tpu import telemetry

    telemetry.count("eval.scatter_elems_saved",
                    int(sum(segment_input_lengths)))


@partial(jax.jit, static_argnames=("num_groups",))
def _grouped_auc(scores, labels, weights, groups, num_groups: int):
    scores = jnp.asarray(scores, jnp.float32)
    labels = jnp.asarray(labels, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    groups = jnp.asarray(groups, jnp.int32)
    n = scores.shape[0]

    order = _sort_by_group_then_key(groups, scores)
    s, y, w, g = scores[order], labels[order], weights[order], groups[order]
    wpos = w * y
    wneg = w * (1.0 - y)

    # Tie groups: runs of equal (group, score).
    new_tie = jnp.concatenate(
        [jnp.ones((1,), bool), (s[1:] != s[:-1]) | (g[1:] != g[:-1])]
    )
    tid = (jnp.cumsum(new_tie) - 1).astype(jnp.int32)
    cneg = jnp.cumsum(wneg)
    tb = _bounds(tid, n)
    gb = _bounds(g, num_groups)
    neg_in_tie = sorted_segment_sum(wneg, tid, n)
    # cneg is nondecreasing: its max over a tie is the tie's LAST row, and
    # the min of (cneg - wneg) over a group is attained at its FIRST row.
    tie_cum_end = _last_of_segment(cneg, tb)
    group_cum_before = _first_of_segment(cneg - wneg, gb, n)
    neg_below_in_group = tie_cum_end[tid] - neg_in_tie[tid] - group_cum_before[g]
    contrib = wpos * (neg_below_in_group + 0.5 * neg_in_tie[tid])

    wp_g = sorted_segment_sum(wpos, g, num_groups)
    wn_g = sorted_segment_sum(wneg, g, num_groups)
    num_g = sorted_segment_sum(contrib, g, num_groups)
    valid = (wp_g > 0.0) & (wn_g > 0.0)
    per_group = jnp.where(valid, num_g / jnp.where(valid, wp_g * wn_g, 1.0), jnp.nan)
    return per_group, valid, _mean_over_valid(per_group, valid)


def grouped_auc(scores, labels, weights, groups, num_groups: int):
    """(per_group_auc, valid_mask, mean_over_valid).

    per_group_auc[g] is the weighted tie-aware AUC of group g (NaN where the
    group lacks both classes); mean is over valid groups, unweighted, matching
    the reference's average of per-entity AUCs.
    """
    n = int(jnp.asarray(scores).shape[0])
    _count_saved(n, n, n, n, n, n)  # 4 segment sums + tie max + group min
    return _grouped_auc(scores, labels, weights, groups, num_groups)


@partial(jax.jit, static_argnames=("num_groups",))
def _grouped_aupr(scores, labels, weights, groups, num_groups: int):
    scores = jnp.asarray(scores, jnp.float32)
    labels = jnp.asarray(labels, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    groups = jnp.asarray(groups, jnp.int32)
    n = scores.shape[0]

    # Descending score within group: every prefix of the sorted order is
    # "predicted positive at this threshold".
    order = _sort_by_group_then_key(groups, -scores)
    s, y, w, g = scores[order], labels[order], weights[order], groups[order]
    wpos = w * y
    wneg = w * (1.0 - y)

    new_tie = jnp.concatenate(
        [jnp.ones((1,), bool), (s[1:] != s[:-1]) | (g[1:] != g[:-1])]
    )
    tid = (jnp.cumsum(new_tie) - 1).astype(jnp.int32)
    cpos = jnp.cumsum(wpos)
    cneg = jnp.cumsum(wneg)
    tb = _bounds(tid, n)
    gb = _bounds(g, num_groups)
    # Cumulative weights at each tie block's END (a tied block is one
    # threshold: all its rows count as retrieved together) minus the
    # group's cumulative before its first row — all monotone sequences,
    # so segmented max/min are boundary gathers.
    pos_tie_end = _last_of_segment(cpos, tb)
    neg_tie_end = _last_of_segment(cneg, tb)
    pos_before_g = _first_of_segment(cpos - wpos, gb, n)
    neg_before_g = _first_of_segment(cneg - wneg, gb, n)
    tp = pos_tie_end[tid] - pos_before_g[g]
    fp = neg_tie_end[tid] - neg_before_g[g]
    denom = tp + fp
    precision = tp / jnp.where(denom > 0.0, denom, 1.0)
    # Σ ΔR·P = Σ_rows (wpos_i / P_g) · precision(tie of i)
    ap_num = sorted_segment_sum(wpos * precision, g, num_groups)
    p_g = sorted_segment_sum(wpos, g, num_groups)
    valid = p_g > 0.0
    per_group = jnp.where(valid, ap_num / jnp.where(valid, p_g, 1.0),
                          jnp.nan)
    return per_group, valid, _mean_over_valid(per_group, valid)


def grouped_aupr(scores, labels, weights, groups, num_groups: int):
    """(per_group_aupr, valid_mask, mean_over_valid).

    Weighted, tie-aware area under the precision–recall curve in the
    STEP-WISE (average-precision) form sklearn uses:
    ``AP = Σ_t (R_t − R_{t−1}) · P_t`` over distinct thresholds descending,
    where a tied score block enters as one threshold. (Reference:
    AreaUnderPRCurveEvaluator; the reference's Spark-mllib backing uses
    the same curve points.) NaN where a group has no positive weight —
    precision is undefined with zero positives.
    """
    n = int(jnp.asarray(scores).shape[0])
    _count_saved(n, n, n, n, n, n)  # 2 sums + 2 tie maxes + 2 group mins
    return _grouped_aupr(scores, labels, weights, groups, num_groups)


@partial(jax.jit, static_argnames=("num_groups", "k"))
def _grouped_precision_at_k(scores, labels, weights, groups,
                            num_groups: int, k: int):
    scores = jnp.asarray(scores, jnp.float32)
    labels = jnp.asarray(labels, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    groups = jnp.asarray(groups, jnp.int32)
    n = scores.shape[0]

    real = weights > 0.0
    key = jnp.where(real, -scores, jnp.inf)  # ascending ⇒ best first, padding last
    order = _sort_by_group_then_key(groups, key)
    y, g, real_s = labels[order], groups[order], real[order]

    idx = jnp.arange(n)
    # idx is increasing, so each group's first row IS its segmented min.
    group_first = _first_of_segment(idx, _bounds(g, num_groups), n)
    pos_in_group = idx - group_first[g]
    mask = (pos_in_group < k) & real_s
    maskf = mask.astype(jnp.float32)

    hits = sorted_segment_sum(y * maskf, g, num_groups)
    considered = sorted_segment_sum(maskf, g, num_groups)
    valid = considered > 0.0
    per_group = jnp.where(valid, hits / jnp.where(valid, considered, 1.0), jnp.nan)
    return per_group, valid, _mean_over_valid(per_group, valid)


def grouped_precision_at_k(scores, labels, weights, groups,
                           num_groups: int, k: int):
    """(per_group_p_at_k, valid_mask, mean_over_valid).

    Top-k rows per group by score; precision = positives among them divided
    by the number considered (min(k, group size)). Labels are counted
    unweighted; weight 0 marks padding (see metrics.precision_at_k).
    """
    n = int(jnp.asarray(scores).shape[0])
    _count_saved(n, n, n)  # 2 segment sums + 1 group min
    return _grouped_precision_at_k(scores, labels, weights, groups,
                                   num_groups, k)


# ----------------------------------------------------------------- contracts
from photon_tpu.analysis.contracts import register_contract  # noqa: E402
from photon_tpu.analysis.walker import SCATTER_PRIMITIVES  # noqa: E402


@register_contract(
    name="grouped_auc_scatter_free",
    description="per-entity sharded AUC rides the sorted-segment "
                "machinery: zero scatters of any kind in the traced "
                "program (sums are cumsum differences, segmented min/max "
                "are boundary gathers over monotone sequences)",
    collectives={}, forbid=SCATTER_PRIMITIVES, tags=("evaluation",))
def _contract_grouped_auc_scatter_free():
    n, G = 64, 7
    z = jnp.zeros((n,), jnp.float32)
    groups = jnp.zeros((n,), jnp.int32)
    fn = lambda s, y, w, g: _grouped_auc(s, y, w, g, G)  # noqa: E731
    return fn, (z, z, z, groups)
