"""The round-8 random-effect block-loop pipeline (game/random_effect.py):

- pipelined (in-flight ledger) train() must be BIT-identical to the
  sequential loop (depth 0) across dense/sparse/INDEX_MAP/RANDOM
  projection, mesh/no-mesh, variances, and per-entity priors — the
  pipeline is a pure reordering of host readbacks over disjoint entity
  sets;
- difficulty-sorted chunk packing must be a pure permutation: every row
  still lands in exactly one lane, lanes within a block are row-count
  ordered, and scatter-back still addresses the right entity keys;
- the compacted straggler re-solve (budget-capped first pass + dense
  full-depth tail) must reach the same per-entity optima as the uncapped
  solve, including for an adversarial entity whose lane alone needs the
  whole iteration budget.
"""
import dataclasses

import numpy as np
import pytest

from photon_tpu.data.matrix import SparseRows
from photon_tpu.game import (
    GameData,
    RandomEffectCoordinate,
    RandomEffectDataset,
)
from photon_tpu.game.projector import ProjectionConfig, ProjectorType
from photon_tpu.models.variance import VarianceComputationType
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim import regularization as reg
from photon_tpu.optim.config import OptimizerConfig

# vmapped while_loop solver compiles accumulate fast here; release them at
# module teardown (see tests/conftest.py).
pytestmark = pytest.mark.release_programs

CFG = OptimizerConfig(max_iters=30, reg=reg.l2(), reg_weight=0.5, history=4)


def _mixed_problem(rng, n_entities=13, d=4, sparse=False):
    rows = rng.integers(3, 28, size=n_entities)
    ent = np.repeat(np.arange(n_entities), rows)
    rng.shuffle(ent)
    n = ent.shape[0]
    w_re = rng.normal(size=(n_entities, d)) * 1.5
    if sparse:
        k = 2
        ind = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        Xd = np.zeros((n, d), np.float32)
        np.add.at(Xd, (np.arange(n)[:, None], ind), val)
        X = SparseRows(ind, val, d)
    else:
        Xd = rng.normal(size=(n, d)).astype(np.float32)
        X = Xd
    logit = np.einsum("nd,nd->n", Xd, w_re[ent])
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return GameData.build(y, {"s": X}, {"e": ent.astype(np.int64)}), n


def _train(ds, n, *, depth, budget=None, mesh=None,
           variance=VarianceComputationType.NONE, prior=None, cfg=CFG):
    coord = RandomEffectCoordinate(
        ds, TaskType.LOGISTIC_REGRESSION, cfg, mesh=mesh, variance=variance,
        pipeline_depth=depth, straggler_budget=budget)
    return coord.train(np.zeros(n, np.float32), prior=prior)


@pytest.mark.parametrize("variant", ["dense", "sparse", "index_map",
                                     "random_proj", "variance"])
def test_pipelined_matches_sequential(rng, variant):
    """depth-2 pipeline == depth-0 sequential loop: bit-identical
    coefficients/variances and identical RETrainStats totals."""
    sparse = variant == "sparse"
    projection = None
    variance = VarianceComputationType.NONE
    if variant == "index_map":
        projection = ProjectionConfig(ProjectorType.INDEX_MAP)
    elif variant == "random_proj":
        projection = ProjectionConfig(ProjectorType.RANDOM, projected_dim=3)
    elif variant == "variance":
        variance = VarianceComputationType.SIMPLE
    data, n = _mixed_problem(rng, sparse=sparse)
    # max_blocks=2 keeps the multi-bucket pipeline real while halving the
    # per-variant vmapped-solver compile count (tier-1 wall budget).
    ds = RandomEffectDataset.build(data, "e", "s", projection=projection,
                                   max_blocks=2)
    m_seq, s_seq = _train(ds, n, depth=0, variance=variance)
    m_pipe, s_pipe = _train(ds, n, depth=2, variance=variance)
    np.testing.assert_array_equal(np.asarray(m_seq.coefficients),
                                  np.asarray(m_pipe.coefficients))
    if variance is not VarianceComputationType.NONE:
        np.testing.assert_array_equal(np.asarray(m_seq.variances),
                                      np.asarray(m_pipe.variances))
    assert (s_seq.n_entities, s_seq.n_converged, s_seq.n_failed,
            s_seq.total_iterations) == \
           (s_pipe.n_entities, s_pipe.n_converged, s_pipe.n_failed,
            s_pipe.total_iterations)
    np.testing.assert_array_equal(s_seq.iterations_per_entity,
                                  s_pipe.iterations_per_entity)


def test_pipelined_matches_sequential_mesh(rng, mesh8):
    data, n = _mixed_problem(rng)
    ds = RandomEffectDataset.build(data, "e", "s", max_blocks=2)
    m_seq, s_seq = _train(ds, n, depth=0, mesh=mesh8)
    m_pipe, s_pipe = _train(ds, n, depth=1, mesh=mesh8)
    np.testing.assert_array_equal(np.asarray(m_seq.coefficients),
                                  np.asarray(m_pipe.coefficients))
    assert s_seq.total_iterations == s_pipe.total_iterations


def test_pipelined_matches_sequential_with_prior(rng):
    """Incremental-training shape: per-entity Gaussian priors ride the
    pipeline unchanged."""
    data, n = _mixed_problem(rng)
    ds = RandomEffectDataset.build(data, "e", "s", max_blocks=2)
    prior_model, _ = _train(ds, n, depth=0,
                            variance=VarianceComputationType.SIMPLE)
    m_seq, s_seq = _train(ds, n, depth=0, prior=prior_model)
    m_pipe, s_pipe = _train(ds, n, depth=2, prior=prior_model)
    np.testing.assert_array_equal(np.asarray(m_seq.coefficients),
                                  np.asarray(m_pipe.coefficients))
    assert s_seq.total_iterations == s_pipe.total_iterations


def test_sorted_packing_permutation_roundtrip(rng):
    """Difficulty-sorted packing is a pure permutation: per-block lanes are
    active-row-count ordered, every real row lands in exactly one lane of
    its own entity, and every entity appears exactly once."""
    n_entities = 23
    rows = rng.integers(1, 50, size=n_entities)
    ent = np.repeat(np.arange(n_entities), rows)
    rng.shuffle(ent)
    n = ent.shape[0]
    X = rng.normal(size=(n, 2)).astype(np.float32)
    data = GameData.build(np.zeros(n), {"s": X}, {"e": ent})
    ds = RandomEffectDataset.build(data, "e", "s")
    seen = np.zeros(n, np.int32)
    total_entities = 0
    for b in ds.blocks:
        w = np.asarray(b.weights)
        ri = np.asarray(b.row_index)
        active = (w > 0).sum(axis=1)
        assert (np.diff(active) >= 0).all(), "lanes not row-count sorted"
        total_entities += b.n_entities
        for i in range(b.n_entities):
            real = w[i] > 0
            assert (ent[ri[i][real]] == b.entity_index[i]).all()
            seen[ri[i][real]] += 1
    assert total_entities == n_entities
    np.testing.assert_array_equal(seen, 1)


def test_sorted_packing_scatter_back_recovers(rng):
    """Planted per-entity coefficients come back under the sorted packing —
    the scatter respects the permutation threaded through entity_index."""
    n_entities, d = 11, 3
    w_true = rng.normal(size=(n_entities, d)).astype(np.float32)
    rows = rng.integers(30, 60, size=n_entities)  # diverse -> real sorting
    ent = np.repeat(np.arange(n_entities), rows)
    n = ent.shape[0]
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.einsum("nd,nd->n", X, w_true[ent]) + 0.01 * rng.normal(size=n)
    data = GameData.build(y, {"s": X}, {"e": ent})
    ds = RandomEffectDataset.build(data, "e", "s", max_blocks=1)
    cfg = OptimizerConfig(max_iters=50, reg=reg.l2(), reg_weight=1e-4)
    coord = RandomEffectCoordinate(ds, TaskType.LINEAR_REGRESSION, cfg)
    model, stats = coord.train(np.zeros(n, np.float32))
    got = np.asarray(model.coefficients)[
        np.asarray([model.key_to_index[k] for k in range(n_entities)])]
    np.testing.assert_allclose(got, w_true, atol=0.05)
    assert stats.n_converged == n_entities


class TestStragglerResolve:
    def _adversarial_problem(self, rng, n_entities=9, d=3):
        """Entity 0's lane alone needs (nearly) the whole iteration budget:
        anisotropically scaled features + separable labels converge slowly
        under weak L2; the other entities finish in a handful of steps."""
        rows = np.full(n_entities, 24)
        ent = np.repeat(np.arange(n_entities), rows)
        n = ent.shape[0]
        X = rng.normal(size=(n, d)).astype(np.float32)
        bad = ent == 0
        X[bad] *= np.geomspace(1e-1, 1e1, d).astype(np.float32)[None, :]
        w_re = rng.normal(size=(n_entities, d)) * 1.0
        logit = np.einsum("nd,nd->n", X, w_re[ent])
        y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
        y[bad] = (logit[bad] > 0).astype(np.float32)
        data = GameData.build(y, {"s": X}, {"e": ent})
        return RandomEffectDataset.build(data, "e", "s"), n

    def test_straggler_resolve_parity(self, rng):
        ds, n = self._adversarial_problem(rng)
        cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=reg.l2(),
                              reg_weight=1e-2, history=5)
        m_full, s_full = _train(ds, n, depth=1, cfg=cfg)
        m_comp, s_comp = _train(ds, n, depth=1, budget=4, cfg=cfg)
        # same per-entity optima (convex problems solved to tolerance) —
        # the tail restart changes the path, not the destination. How far
        # apart two converged solves may stop: a relative-decrease stop at
        # tol leaves a gap of order tol*f (f <= rows*log 2 per entity), and
        # in a lam-strongly-convex objective a gap g allows a distance
        # sqrt(2g/lam) from the optimum — twice, for two solves. (The
        # chip's verdict on the same comparison: chip_smoke.py's `parity`
        # phase.)
        atol = 2.0 * np.sqrt(2.0 * cfg.tolerance * 24 * np.log(2.0)
                             / cfg.reg_weight)
        np.testing.assert_allclose(np.asarray(m_comp.coefficients),
                                   np.asarray(m_full.coefficients),
                                   atol=atol)
        assert s_comp.n_converged >= s_full.n_converged
        # the adversarial entity really went through the tail pass and
        # dominates the per-entity iteration counts — the lane the
        # sequential loop would have run the WHOLE chunk for
        adv = ds.key_to_index[0]
        ipe = s_comp.iterations_per_entity
        assert ipe[adv] > 4
        assert ipe[adv] == ipe.max()
        assert ipe[adv] > 1.5 * np.median(ipe)
        # and the cap alone (no tail) would NOT have converged everyone:
        # the compaction did real work
        capped_only = dataclasses.replace(cfg, max_iters=4)
        _, s_capped = _train(ds, n, depth=1, cfg=capped_only)
        assert s_capped.n_converged < s_full.n_entities
        assert s_comp.n_converged == s_full.n_entities

    def test_budget_noop_when_at_or_above_max_iters(self, rng):
        """budget >= max_iters (or <= 0) degrades to the plain path.
        (Same problem/config family as the parity test: the solver
        programs are already compiled.)"""
        ds, n = self._adversarial_problem(rng)
        cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=reg.l2(),
                              reg_weight=1e-2, history=5)
        m_a, s_a = _train(ds, n, depth=1, budget=None, cfg=cfg)
        m_b, s_b = _train(ds, n, depth=1, budget=80, cfg=cfg)
        m_c, s_c = _train(ds, n, depth=1, budget=0, cfg=cfg)
        np.testing.assert_array_equal(np.asarray(m_a.coefficients),
                                      np.asarray(m_b.coefficients))
        np.testing.assert_array_equal(np.asarray(m_a.coefficients),
                                      np.asarray(m_c.coefficients))
        assert s_a.total_iterations == s_b.total_iterations \
            == s_c.total_iterations

    def test_straggler_budget_disables_fused_program(self, rng):
        """The compacted re-solve needs a host repack between passes, so a
        budgeted coordinate must take the pipelined train() path. (Builds
        the fused callable only — jit is lazy, nothing compiles.)"""
        ds, n = self._adversarial_problem(rng)
        cfg = OptimizerConfig(max_iters=80, tolerance=1e-6, reg=reg.l2(),
                              reg_weight=1e-2, history=5)
        plain = RandomEffectCoordinate(ds, TaskType.LOGISTIC_REGRESSION, cfg)
        budgeted = RandomEffectCoordinate(ds, TaskType.LOGISTIC_REGRESSION,
                                          cfg, straggler_budget=4)
        assert plain.fused_update_program() is not None
        assert budgeted.fused_update_program() is None
