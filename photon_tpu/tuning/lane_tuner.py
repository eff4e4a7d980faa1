"""Lane-batched, budget-aware hyperparameter tuner: GP proposal batches
dispatched as lock-step regularization LANES, with asynchronous successive
halving and modeled-cost budget enforcement.

The reference's HyperparameterTuner evaluates one proposal per training
run (one Spark job per candidate); `tuning/tuner.py::tune_glm_reg` already
amortizes a GP round's batch into one `train_glm_grid` program. This
module closes ROADMAP item 1 — the full fusion of the tuner with the
lane-minor solver family (optim/lane_{lbfgs,owlqn,tron}.py):

- **Fixed pow2 lane chunks** (`TUNER_LANES`): every GP/`qei_greedy`
  proposal batch pads to the same chunk (duplicating the last proposal —
  a duplicate lane converges identically and its result is discarded), so
  the dispatch signature NEVER depends on how many configs a round
  proposed. `_SIG_LOG` records every dispatch; after the first round
  warms the two programs (screen + re-solve), later rounds compile
  NOTHING (`LaneTuningResult.assert_no_retrace`, pinned statically by the
  ``tuning_lane_dispatch`` contract below and live by the bench leg).
- **Asynchronous successive halving** (the straggler-budget trick of the
  random-effect pipeline): each round first SCREENS its whole chunk at a
  capped iteration budget (`LaneBudget.screen_iters`), scores all lanes
  in one device program, then compacts the top `survivor_frac` lanes with
  `parallel.mesh.compact_rows(pad_mode="edge")` into a fixed smaller
  chunk and re-solves ONLY the survivors to full depth, warm-started from
  their screened coefficients (the per-lane (G, d) ``w0`` handoff in
  `models.training.train_glm_grid`).
- **Cost-aware acquisition**: each round's lane program is priced in
  modeled FLOPs/bytes (`profiling.model.estimate_fn`, trace-only) BEFORE
  dispatch; per-proposal prices feed `qei_greedy(costs=...)`, and the
  round must fit the modeled budget — zero collective bytes off-mesh and
  FLOPs within `cost_factor`× the lane roofline (`RoundBudgetError`
  otherwise; the ``tuning_round_budget`` contract pins the same law
  statically). The attribution ledger sees every round as
  ``tuning.lane_screen`` / ``tuning.lane_resolve`` dispatches with their
  static costs noted, so `finish_ledger()` reports measured tuner cost
  per round.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from photon_tpu import profiling, telemetry
from photon_tpu.analysis.rules import TraceSignatureLog, trace_signature
from photon_tpu.data.matrix import next_pow2
from photon_tpu.parallel.mesh import compact_rows
from photon_tpu.profiling.model import StaticCost, estimate_fn
from photon_tpu.tuning.gp import fit_gp
from photon_tpu.tuning.search import SearchRange, SearchSpace, candidates

# Fixed lane-chunk default: every proposal batch pads to this many lanes,
# so the screen program's signature depends only on (batch shape, config)
# — never on the round's proposal count. 64 lanes is the sweet spot
# measured for the lane-minor solvers ((n, d)×(d, 64) keeps the MXU busy
# without blowing the (d, G) state footprint at large d).
TUNER_LANES = 64

# The tuner's live signature log (the continual/refresh.py pattern):
# every lane dispatch records here; `LaneTuningResult.assert_no_retrace`
# proves rounds after the first reuse the warmed program signatures.
_SIG_LOG = TraceSignatureLog()
_SIG_SCREEN = "tuning.lane_screen"
_SIG_RESOLVE = "tuning.lane_resolve"

# Modeled-cost cache: one trace per distinct (shapes, config) — rounds
# re-use the price, they never re-trace the estimator.
_COST_CACHE: dict = {}


class RoundBudgetError(RuntimeError):
    """A proposed round's MODELED cost exceeds the configured budget —
    raised BEFORE dispatch (the estimate is trace-only), so a
    misconfigured sweep fails in milliseconds, not after burning the
    round's compute."""


@dataclasses.dataclass(frozen=True)
class LaneBudget:
    """Per-round compute budget for the halving tuner.

    ``screen_iters``: the straggler cap on the screening solve (None →
    max(4, config.max_iters // 8)). ``survivor_frac``: fraction of the
    chunk re-solved to full depth. ``cost_factor``: ceiling on modeled
    round FLOPs as a multiple of the lane-roofline ideal
    (4·n·d·G per iteration — the two fused X passes of a margin-cached
    lane step); ``max_round_flops`` is an absolute override. Collective
    bytes must be 0 off-mesh (on a mesh the per-evaluation psum is the
    budget, enforced by the training contracts)."""

    screen_iters: Optional[int] = None
    survivor_frac: float = 0.25
    cost_factor: float = 16.0
    max_round_flops: Optional[float] = None


@dataclasses.dataclass
class RoundStats:
    """One halving round's accounting: what was proposed, what survived,
    and what the dispatch was modeled to cost."""

    n_proposed: int
    n_survivors: int
    screen_iters: int
    modeled_flops: float
    modeled_bytes: float
    modeled_collective_bytes: float
    flops_per_config: float
    best_screen_y: float
    best_full_y: float


@dataclasses.dataclass
class LaneTuningResult:
    """Tuning outcome + per-round accounting.

    ``ys`` are the SCREEN-fidelity metrics of every proposed config (what
    the GP models — one consistent fidelity); ``best_y`` is the winning
    survivor's FULL-depth validation metric (minimized convention:
    higher-is-better metrics arrive negated)."""

    best_x: np.ndarray
    best_y: float
    xs: np.ndarray  # (n_configs, 1) original-space reg weights
    ys: np.ndarray  # (n_configs,) screen-fidelity metrics
    rounds: list

    def history(self) -> np.ndarray:
        """Running best screen metric after each evaluation."""
        return np.minimum.accumulate(self.ys)

    @staticmethod
    def signatures() -> dict:
        """Distinct lane-dispatch signatures seen process-wide, by
        program (one screen + one re-solve per (shapes, config) — NOT
        per round)."""
        return {name: _SIG_LOG.signatures(name)
                for name in (_SIG_SCREEN, _SIG_RESOLVE)}

    @staticmethod
    def signature_count() -> int:
        return sum(len(v) for v in LaneTuningResult.signatures().values())

    @staticmethod
    def assert_no_retrace(baseline: int) -> int:
        """Prove tuning rounds added no dispatch signatures over
        ``baseline`` (the count captured after the warming round) and no
        weak-type drift crept in. Returns the current count."""
        count = LaneTuningResult.signature_count()
        if count > baseline:
            raise AssertionError(
                f"{count} tuner dispatch signatures exceed the warmed "
                f"baseline of {baseline}: the lane tuner retraced")
        hazards = _SIG_LOG.hazards()
        if hazards:
            raise AssertionError(
                f"weak-type signature drift in tuner dispatch: {hazards}")
        return count


def pad_proposals(weights, chunk: int) -> list:
    """Pad a round's proposal weights to the fixed lane chunk by
    REPEATING the last proposal: a duplicate lane costs nothing extra in
    lock-step (it converges exactly with its original) where a zero/dummy
    weight would be the chunk's slowest lane; padded results are
    discarded by index."""
    weights = [float(w) for w in weights]
    if not weights:
        raise ValueError("a round needs at least one proposal")
    if len(weights) > chunk:
        raise ValueError(
            f"{len(weights)} proposals exceed the lane chunk {chunk}")
    return weights + [weights[-1]] * (chunk - len(weights))


def _lane_grid_cost(batch, task, config, weights, mesh) -> StaticCost:
    """Modeled StaticCost of one capped lane-grid dispatch — trace-only
    (`estimate_fn` runs jax.make_jaxpr; nothing compiles or executes),
    cached per (shapes, config). Mesh sweeps are priced on the
    single-device lane program (per-chip cost; the psum budget is pinned
    by the training contracts)."""
    from photon_tpu.models import training as _training

    l2s, l1s, static_cfg = _training.lane_weight_arrays(config, weights)
    d = _training._matrix_dim(batch.X)
    obj = _training.make_objective(task, config, d)
    w0 = jnp.zeros((d,), jnp.float32)
    key = (trace_signature((batch, w0, l2s, l1s)), static_cfg, task)
    hit = _COST_CACHE.get(key)
    if hit is not None:
        return hit

    def fn(b, w, o, l2, l1):
        return _training._train_run_grid_lanes(b, w, o, l2, l1, static_cfg)

    cost = estimate_fn(fn, (batch, w0, obj, l2s, l1s),
                       while_trips=int(static_cfg.max_iters))
    _COST_CACHE[key] = cost
    return cost


def _enforce_budget(cost: StaticCost, batch, d: int, chunk: int,
                    iters: int, budget: LaneBudget, mesh) -> None:
    ideal = 4.0 * float(batch.n) * float(d) * float(chunk) * float(iters)
    limit = budget.cost_factor * max(ideal, 1.0)
    if budget.max_round_flops is not None:
        limit = min(limit, float(budget.max_round_flops))
    if cost.flops > limit:
        raise RoundBudgetError(
            f"modeled round cost {cost.flops:.3g} FLOPs exceeds the "
            f"budget {limit:.3g} (lane roofline {ideal:.3g} × factor "
            f"{budget.cost_factor}; max_round_flops="
            f"{budget.max_round_flops}); shrink the chunk/screen budget "
            "or raise LaneBudget.cost_factor")
    if mesh is None and cost.collective_bytes > 0:
        raise RoundBudgetError(
            f"single-device tuner round models {cost.collective_bytes} "
            "collective bytes; the lane program must be collective-free "
            "off-mesh")


def _lane_scores(W, val_batch, evaluator, n_real: int) -> np.ndarray:
    """Validation metric per REAL lane, minimized convention. The only
    pass over the validation X runs for ALL lanes as one device program
    (`models.glm._score_many` — the dense case is a single
    (n, d)×(d, G) matmul); the (n,)-sized metric reductions run per lane
    on host."""
    from photon_tpu.models.glm import _score_many

    margins = np.asarray(_score_many(
        W, val_batch.X, jnp.asarray(val_batch.offsets, jnp.float32),
        stored_rows=True))
    ys = np.empty((n_real,), np.float64)
    for i in range(n_real):
        s = float(evaluator.evaluate(margins[i], val_batch.y,
                                     val_batch.weights))
        ys[i] = -s if evaluator.higher_is_better else s
    return ys


def tune_glm_reg_lanes(
    train_batch,
    task,
    config,
    val_batch,
    n_configs: int = 256,
    lane_chunk: int = TUNER_LANES,
    reg_range: tuple = (1e-4, 1e4),
    evaluator=None,
    mesh=None,
    seed: int = 0,
    budget: Optional[LaneBudget] = None,
    kernel: str = "matern52",
    n_pool: int = 512,
):
    """Tune a GLM's regularization weight over ``n_configs`` candidates in
    the wall-clock of a few solves: GP proposal batches dispatch as
    lock-step lane chunks with capped-budget screening, survivor
    compaction, and warm-started full-depth re-solves (module docstring).

    Returns ``(best_model, best_reg_weight, LaneTuningResult)`` — the
    same contract as ``tuning.tuner.tune_glm_reg``.
    """
    from photon_tpu.evaluation.evaluator import default_evaluator
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.models import training as _training

    if lane_chunk < 2 or (lane_chunk & (lane_chunk - 1)) != 0:
        raise ValueError(f"lane_chunk must be a pow2 >= 2, got {lane_chunk}")
    if n_configs < lane_chunk:
        raise ValueError(
            f"n_configs ({n_configs}) must cover at least one lane chunk "
            f"({lane_chunk})")
    budget = budget if budget is not None else LaneBudget()
    evaluator = evaluator if evaluator is not None else default_evaluator(task)
    screen_iters = (budget.screen_iters if budget.screen_iters is not None
                    else max(4, int(config.max_iters) // 8))
    cfg_screen = dataclasses.replace(config, max_iters=screen_iters)
    k = max(1, int(round(lane_chunk * budget.survivor_frac)))
    s_chunk = min(lane_chunk, next_pow2(k, floor=2))
    space = SearchSpace([SearchRange(*reg_range, log_scale=True)])
    d = _training._matrix_dim(train_batch.X)

    xs_unit: list = []
    screen_ys: list = []
    rounds: list = []
    best_y = np.inf
    best_weight = None
    best_coef = None

    n_rounds = -(-n_configs // lane_chunk)  # ceil
    done = 0
    for r in range(n_rounds):
        q = min(lane_chunk, n_configs - done)
        # ---- propose: Sobol seed round, then GP + cost-aware greedy q-EI
        if r == 0:
            units = list(candidates(space, q, "sobol", seed=seed))
        else:
            gp = fit_gp(np.asarray(xs_unit, np.float32),
                        np.asarray(screen_ys), kernel)
            pool = candidates(space, n_pool, "sobol", seed=seed + 1000 + r)
            best_screen = float(np.min(screen_ys))
            # every lane of a chunk is priced identically (one program);
            # the per-proposal price still flows through the cost-aware
            # greedy so heterogeneous-cost spaces pick gain-per-FLOP
            price = rounds[-1].flops_per_config if rounds else 1.0
            idx = qei_greedy_costed(gp, pool.astype(np.float32),
                                    best_screen, q,
                                    seed=seed + 2000 + r,
                                    price=price)
            units = [pool[i] for i in idx]
        weights = [float(space.from_unit(u)[0]) for u in units]
        padded = pad_proposals(weights, lane_chunk)

        # ---- price & budget-check the round BEFORE dispatch
        cost = _lane_grid_cost(train_batch, task, cfg_screen, padded, mesh)
        _enforce_budget(cost, train_batch, d, lane_chunk, screen_iters,
                        budget, mesh)
        telemetry.gauge("tuning.round_model_flops", cost.flops)

        with telemetry.span("tuning.round", index=r, proposed=q,
                            chunk=lane_chunk):
            # ---- screen: capped lock-step solve of the whole chunk
            l2s_sig = jnp.asarray(padded, jnp.float32)
            _SIG_LOG.record(_SIG_SCREEN, (train_batch, l2s_sig))
            with profiling.dispatch(_SIG_SCREEN, (train_batch, l2s_sig)):
                res, _ = _training.train_glm_grid(
                    train_batch, task, cfg_screen, padded, mesh=mesh,
                    device_results=True)
            ys = _lane_scores(res.w, val_batch, evaluator, q)
            xs_unit.extend(units)
            screen_ys.extend(ys.tolist())

            # ---- halve: compact the top-k survivors (device gather,
            # edge-padded to the fixed survivor chunk) and re-solve them
            # full-depth from their screened coefficients
            kk = min(k, q)
            survivors = np.argsort(ys, kind="stable")[:kk]
            idx_pad = np.concatenate(
                [survivors, np.full(s_chunk - kk, survivors[0], np.int64)])
            W0 = compact_rows(res.w, idx_pad, pad_mode="edge")
            sur_weights = [padded[i] for i in idx_pad]
            _SIG_LOG.record(_SIG_RESOLVE,
                            (train_batch, W0,
                             jnp.asarray(sur_weights, jnp.float32)))
            with profiling.dispatch(_SIG_RESOLVE, (train_batch, W0)):
                res_full, _ = _training.train_glm_grid(
                    train_batch, task, config, sur_weights, mesh=mesh,
                    w0=W0, device_results=True)
            full_ys = _lane_scores(res_full.w, val_batch, evaluator, kk)
            telemetry.count("tuning.rounds")
            telemetry.count("tuning.configs", q)
            telemetry.count("tuning.survivor_resolves", kk)

        j = int(np.argmin(full_ys))
        if full_ys[j] < best_y:
            best_y = float(full_ys[j])
            best_weight = sur_weights[j]
            best_coef = np.asarray(res_full.w[j])
        rounds.append(RoundStats(
            n_proposed=q, n_survivors=kk, screen_iters=screen_iters,
            modeled_flops=cost.flops, modeled_bytes=cost.bytes,
            modeled_collective_bytes=cost.collective_bytes,
            flops_per_config=cost.flops / lane_chunk,
            best_screen_y=float(ys.min()), best_full_y=float(full_ys[j])))
        done += q

    xs_arr = np.asarray([space.from_unit(u) for u in xs_unit])
    model = GeneralizedLinearModel(Coefficients(jnp.asarray(best_coef),
                                                None), task)
    result = LaneTuningResult(
        best_x=np.asarray([best_weight]), best_y=best_y,
        xs=xs_arr, ys=np.asarray(screen_ys), rounds=rounds)
    return model, float(best_weight), result


def qei_greedy_costed(gp, pool, best_y: float, q: int, seed: int,
                      price: float):
    """The tuner's cost-aware pick: every pool candidate dispatches into
    the SAME lane program, so each is priced at the round's modeled
    FLOPs / chunk — uniform here (reducing to plain greedy q-EI), but
    routed through ``qei_greedy(costs=...)`` so spaces whose candidates
    imply different budgets (e.g. per-candidate iteration caps) price
    picks as gain-per-FLOP with no tuner change."""
    from photon_tpu.tuning.acquisition import qei_greedy

    costs = np.full(pool.shape[0], max(float(price), 1.0), np.float64)
    return qei_greedy(gp, pool, best_y, q, seed=seed, costs=costs)


# ----------------------------------------------------------------- contracts
# The tuner's two performance laws, pinned statically (traced + enforced
# by `python -m photon_tpu.analysis` and tier-1 on every PR): proposal
# batches of ANY size dispatch one fixed-chunk signature (the batched
# tuner compiles exactly two programs per problem shape), and a round's
# modeled cost fits the collective/compute budget BEFORE anything runs.
from photon_tpu.analysis.contracts import register_contract  # noqa: E402


def _tuner_contract_problem(chunk: int = 8, iters: int = 4):
    """(small dense lane problem at the fixed chunk) — constructed
    directly from zeros; contracts are shape/dtype facts, nothing jitted
    executes to build them."""
    from photon_tpu.data.dataset import GLMBatch
    from photon_tpu.models import training as _training
    from photon_tpu.ops.losses import TaskType
    from photon_tpu.optim.config import OptimizerConfig
    from photon_tpu.optim.regularization import l2

    n, d = 32, 5
    cfg = OptimizerConfig(max_iters=iters, tolerance=1e-7, reg=l2(),
                          reg_weight=0.0, history=3,
                          regularize_intercept=True)
    batch = GLMBatch(X=jnp.zeros((n, d), jnp.float32),
                     y=jnp.zeros((n,), jnp.float32),
                     weights=jnp.zeros((n,), jnp.float32),
                     offsets=jnp.zeros((n,), jnp.float32))
    weights = pad_proposals([0.1], chunk)
    l2s, l1s, static_cfg = _training.lane_weight_arrays(cfg, weights)
    obj = _training.make_objective(TaskType.LOGISTIC_REGRESSION, cfg, d)
    return batch, obj, l2s, l1s, static_cfg, cfg


@register_contract(
    name="tuning_lane_dispatch",
    description="the batched tuner's screen dispatch: proposal batches "
                "of DIFFERENT sizes pad to the fixed pow2 lane chunk, so "
                "every round carries one TraceSignatureLog signature with "
                "no weak-type drift (builder raises on divergence), and "
                "the traced lock-step lane program is collective-free "
                "with no transfers and no f64",
    collectives={}, tags=("tuning", "lane"))
def _contract_tuning_lane_dispatch():
    from photon_tpu.models.training import _train_run_grid_lanes

    batch, obj, l2s, l1s, static_cfg, _ = _tuner_contract_problem()
    chunk = int(l2s.shape[0])

    # Rounds proposing 3 vs 7 configs pad to the same chunk: their
    # dispatch argument signatures must be identical (shape/dtype facts
    # only — nothing executes).
    log = TraceSignatureLog()
    for q in (3, 7):
        padded = pad_proposals([0.1] * q, chunk)
        log.record("screen", (batch, jnp.asarray(padded, jnp.float32)))
    sigs = log.signatures("screen")
    if len(sigs) != 1:
        raise AssertionError(
            f"tuner dispatch signatures diverged across proposal counts: "
            f"{sigs}")
    if log.hazards():
        raise AssertionError(
            f"weak-type drift in tuner dispatch: {log.hazards()}")

    def fn(b, w, o, l2):
        return _train_run_grid_lanes(b, w, o, l2, None, static_cfg)

    w0 = jnp.zeros((int(batch.X.shape[1]),), jnp.float32)
    return fn, (batch, w0, obj, l2s)


@register_contract(
    name="tuning_round_budget",
    description="a tuner round fits its modeled budget BEFORE dispatch: "
                "the builder prices the capped screen program with "
                "estimate_fn and raises unless collective bytes are zero "
                "and FLOPs sit within LaneBudget.cost_factor of the lane "
                "roofline; the traced program is the halving tail — "
                "compact_rows survivor gather + warm-started full-depth "
                "re-solve from per-lane w0 — equally collective-free",
    collectives={}, tags=("tuning", "lane"))
def _contract_tuning_round_budget():
    from photon_tpu.models.training import _train_run_grid_lanes

    batch, obj, l2s, l1s, static_cfg, cfg = _tuner_contract_problem()
    chunk = int(l2s.shape[0])
    d = int(batch.X.shape[1])
    iters = int(static_cfg.max_iters)

    def screen(b, w, o, l2):
        return _train_run_grid_lanes(b, w, o, l2, None, static_cfg)

    w0 = jnp.zeros((d,), jnp.float32)
    cost = estimate_fn(screen, (batch, w0, obj, l2s), while_trips=iters)
    _enforce_budget(cost, batch, d, chunk, iters, LaneBudget(), mesh=None)

    # The halving tail at the fixed survivor chunk: device gather of the
    # winning lanes (edge-padded) + the per-lane-w0 warm re-solve.
    s_chunk = 4

    def tail(w_lanes, b, o, l2_sur, idx):
        W0 = compact_rows(w_lanes, idx, pad_rows=s_chunk, pad_mode="edge")
        return _train_run_grid_lanes(b, W0, o, l2_sur, None, static_cfg)

    idx = jnp.asarray(np.asarray([1, 5, 2]), jnp.int32)
    w_lanes = jnp.zeros((chunk, d), jnp.float32)
    l2_sur = jnp.zeros((s_chunk,), jnp.float32)
    return tail, (w_lanes, batch, obj, l2_sur, idx)
