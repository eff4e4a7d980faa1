"""GameEstimator: train GAME models over candidate configurations and select
the best on validation data.

Reference parity: com.linkedin.photon.ml.estimators.GameEstimator — fit()
takes a sequence of per-coordinate optimization configurations, trains one
GameModel per configuration (warm-starting each from the previous one when
enabled), evaluates each on the validation set, and the driver selects the
best by the task's primary evaluator.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
from jax.sharding import Mesh

from photon_tpu import telemetry
from photon_tpu.evaluation.evaluator import Evaluator, default_evaluator
from photon_tpu.game.coordinate_descent import (
    CoordinateDescentResult,
    coordinate_descent,
)
from photon_tpu.game.dataset import FixedEffectDataset, GameData, RandomEffectDataset
from photon_tpu.game.fixed_effect import FixedEffectCoordinate
from photon_tpu.game.model import GameModel
from photon_tpu.game.random_effect import RandomEffectCoordinate
from photon_tpu.game.scoring import score_game
from photon_tpu.models.variance import VarianceComputationType
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig


@dataclasses.dataclass(frozen=True)
class FixedEffectConfig:
    """Reference: FixedEffectCoordinateConfiguration (shard + optimizer)."""

    feature_shard: str
    optimizer: OptimizerConfig = OptimizerConfig()


@dataclasses.dataclass(frozen=True)
class RandomEffectConfig:
    """Reference: RandomEffectCoordinateConfiguration (entity type, shard,
    optimizer, active-data cap)."""

    entity_name: str
    feature_shard: str
    optimizer: OptimizerConfig = OptimizerConfig()
    active_cap: Optional[int] = None
    # Feature-space projection for the per-entity solves (reference:
    # projector.ProjectorType on the random-effect data configuration).
    projection: Optional[object] = None  # game.projector.ProjectionConfig
    # Block-loop software pipeline depth (RandomEffectCoordinate.
    # pipeline_depth): in-flight bucket solves beyond the one being
    # retired; 0 = sequential. Bit-identical at every depth.
    pipeline_depth: int = 1
    # Straggler mitigation (RandomEffectCoordinate.straggler_budget):
    # first-pass iteration cap before the compacted full-depth re-solve
    # of unconverged lanes. None = off (also disables on the fused path).
    straggler_budget: Optional[int] = None


CoordinateConfig = FixedEffectConfig | RandomEffectConfig


from photon_tpu.data.matrix import last_column_is_intercept as _last_column_is_intercept

# Auto-mode lane-axis gate: reg-weight spread (max/min across lanes) above
# which lock-step lanes are assumed to lose to the per-lane-adaptive
# sequential path (docs/PERF.md's masking A/B: spread 1e5 → lane-axis 3.7×
# WORSE; spread ≤1e2 grids — every headline sweep — win on lanes).
_GRID_SKEW_MAX = 1e4


@dataclasses.dataclass
class GameFitResult:
    """One (configuration → model) outcome (reference: fit()'s result tuples)."""

    model: GameModel
    descent: CoordinateDescentResult
    configs: dict  # name -> CoordinateConfig actually used
    validation_score: Optional[float] = None


@dataclasses.dataclass
class GameEstimator:
    """Reference: estimators.GameEstimator."""

    task: TaskType
    coordinate_configs: dict  # name -> CoordinateConfig (insertion order = default update sequence)
    update_sequence: Optional[list] = None
    n_sweeps: int = 2
    mesh: Optional[Mesh] = None
    variance: VarianceComputationType = VarianceComputationType.NONE
    locked: frozenset = frozenset()
    # Coordinates whose initial model becomes an informative prior
    # (incremental training); must be present in fit()'s initial_models.
    incremental: frozenset = frozenset()
    warm_start: bool = True
    evaluator: Optional[Evaluator] = None
    # Per-coordinate feature normalization (reference: the driver's
    # normalization applied per feature shard): coordinate name → either a
    # NormalizationType (context computed from that coordinate's design
    # matrix; intercept assumed LAST column per data.feature_bags) or a
    # prebuilt NormalizationContext.
    normalization: dict = dataclasses.field(default_factory=dict)
    # Per-training-data caches of bucketed datasets and jit-compiled
    # coordinates, persisted ACROSS fit() calls so a tuner loop that fits the
    # same data repeatedly reuses bucketing and compiled solvers. Keyed by the
    # GameData object's identity; the entry keeps a strong reference to the
    # data so an id() is never reused while cached.
    _caches: dict = dataclasses.field(default_factory=dict, init=False,
                                      repr=False)

    def _caches_for(self, data) -> tuple[dict, dict]:
        entry = self._caches.get(id(data))
        if entry is None or entry[0] is not data:
            entry = (data, {}, {})
            self._caches[id(data)] = entry
        return entry[1], entry[2]
    def datasets(self, data) -> dict:
        """{coordinate name: the dataset `fit(data)` built for it} — the
        bucketed blocks a fit trained on (which rows an active-row cap
        kept, each bucket's index map), for whoever checks a fit against
        the data; a coordinate no fit has built yet is absent."""
        cache, _ = self._caches_for(data)
        return {name: cache[self._dataset_key(cfg)]
                for name, cfg in self.coordinate_configs.items()
                if self._dataset_key(cfg) in cache}

    # entity-id column for sharded (per-entity) validation evaluators;
    # defaults to the first random-effect coordinate's entity type.
    evaluator_entity: Optional[str] = None
    # Fixed-effect-only models whose config_grid varies nothing but the
    # regularization weight can run the WHOLE grid as one compiled program
    # (models.training.train_glm_grid: vmapped lanes share every X pass).
    # Semantics difference vs the sequential path: lanes run concurrently,
    # so `warm_start` cannot chain models across grid points — every lane
    # starts from zeros (each still converges to its own optimum within
    # tolerance). Tri-state: None (default) vectorizes only when
    # `warm_start` is False, so an explicitly requested warm-started sweep
    # is never silently replaced; True forces the vectorized path (dropping
    # warm starts); False forces the sequential path.
    vectorized_grid: Optional[bool] = None

    @staticmethod
    def _dataset_key(cfg: CoordinateConfig) -> tuple:
        """Fields that change the dataset (not just the solve)."""
        if isinstance(cfg, FixedEffectConfig):
            return ("fixed", cfg.feature_shard)
        return ("random", cfg.entity_name, cfg.feature_shard, cfg.active_cap,
                cfg.projection)

    @staticmethod
    def _build_dataset(data: GameData, cfg: CoordinateConfig):
        if isinstance(cfg, FixedEffectConfig):
            return FixedEffectDataset.build(data, cfg.feature_shard)
        with telemetry.span("game_re.build", entity=cfg.entity_name):
            return RandomEffectDataset.build(
                data, cfg.entity_name, cfg.feature_shard,
                active_cap=cfg.active_cap, projection=cfg.projection,
            )

    def _build_coordinates(self, datasets: dict, configs: dict,
                           cache: Optional[dict] = None) -> dict:
        """Coordinates are cached by (dataset key, optimizer config) so a
        config_grid sweep that only changes OTHER coordinates reuses this
        one's jit-compiled (vmapped) solver instead of recompiling it."""
        coords = {}
        for name, cfg in configs.items():
            # Solve knobs that live OUTSIDE cfg.optimizer but change the
            # compiled/driven solve must be part of the coordinate cache key
            # (the RE pipeline/straggler knobs select different programs).
            knobs = ((cfg.pipeline_depth, cfg.straggler_budget)
                     if isinstance(cfg, RandomEffectConfig) else ())
            key = (self._dataset_key(cfg), cfg.optimizer, knobs)
            if cache is not None and key in cache:
                coords[name] = cache[key]
                continue
            norm = self._normalization_for(name, datasets[name])
            if isinstance(cfg, FixedEffectConfig):
                coord = FixedEffectCoordinate(
                    datasets[name], self.task, cfg.optimizer,
                    mesh=self.mesh, variance=self.variance,
                    normalization=norm,
                )
            else:
                coord = RandomEffectCoordinate(
                    datasets[name], self.task, cfg.optimizer,
                    mesh=self.mesh, variance=self.variance,
                    normalization=norm,
                    pipeline_depth=cfg.pipeline_depth,
                    straggler_budget=cfg.straggler_budget,
                )
            if cache is not None:
                cache[key] = coord
            coords[name] = coord
        return coords

    def _normalization_for(self, name: str, dataset):
        """Resolve this coordinate's NormalizationContext (build from the
        dataset's design matrix when a bare NormalizationType was given)."""
        from photon_tpu.data.normalization import (
            NormalizationContext,
            NormalizationType,
        )

        spec = self.normalization.get(name)
        if spec is None:
            return None
        if isinstance(spec, NormalizationContext):
            return spec
        if isinstance(spec, NormalizationType):
            # Detect the intercept-last convention rather than assuming it:
            # treating a real feature as the intercept would silently corrupt
            # factor/shift handling for shards built with has_intercept=False.
            icpt = -1 if _last_column_is_intercept(dataset.X) else None
            if spec is NormalizationType.STANDARDIZATION and icpt is None:
                raise ValueError(
                    f"normalization[{name!r}]: STANDARDIZATION requires an "
                    "intercept column (all-ones, last) in the feature shard"
                )
            return NormalizationContext.build(dataset.X, spec,
                                              intercept_index=icpt)
        raise TypeError(
            f"normalization[{name!r}] must be a NormalizationType or "
            f"NormalizationContext, got {type(spec)}"
        )

    def fit(
        self,
        data: GameData,
        validation: Optional[GameData] = None,
        config_grid: Optional[list] = None,
        initial_models: Optional[dict] = None,
    ) -> list:
        """Train one GameModel per candidate configuration.

        `config_grid`: list of {name -> CoordinateConfig} overrides — one
        GameModel is trained per entry (reference: one
        GameOptimizationConfiguration per model). None trains a single model
        with `coordinate_configs`. Successive models warm-start from the
        previous one when `warm_start` (reference: GameEstimator warm start
        across regularization weights) — EXCEPT on the vectorized
        fixed-effect-only grid path (see `vectorized_grid`), whose lanes
        run concurrently from zeros. Datasets are cached per
        (shard, entity, active_cap) so overrides that change only the
        optimizer reuse the bucketed blocks.
        """
        grid = config_grid or [self.coordinate_configs]
        evaluator = self.evaluator or default_evaluator(self.task)
        telemetry.count("game.grid_points", len(grid))
        if self._chunked_shards(data):
            # the pod-scale (streamed-objective) GAME regime: fixed-effect
            # coordinates stream their host-chunked shards; the descent
            # loop runs its host-margin-cache exchange
            telemetry.count("game_e2e.chunked_fit_points", len(grid))
        dataset_cache, coord_cache = self._caches_for(data)
        if validation is not None:
            # One transfer for the whole grid: every grid point scores the
            # same validation shards.
            validation = validation.to_device()

        chain_warm = self.warm_start
        if self.would_vectorize(grid, initial_models):
            if self.n_sweeps == 1 and not self._chunked_shards(data):
                probe = self._fixed_only_reg_grid(grid)
                if probe is not None and self._fixed_seq_ok(probe):
                    # single fixed effect, one sweep: the leanest form —
                    # the whole grid is ONE train_glm_grid program
                    return self._fit_fixed_grid(probe, data, validation,
                                                evaluator, dataset_cache)
            lanes = self._game_grid_probe(grid)
            if lanes is not None:
                if self._grid_data_supported(data):
                    return self._fit_game_grid(lanes, data, validation,
                                               evaluator, dataset_cache,
                                               coord_cache)
                # Vectorization was requested (and its contract is "lanes
                # never chain warm starts across grid points"); keep that
                # contract on the unsupported-layout fallback so results do
                # not depend on the matrix representation.
                chain_warm = False

        results: list[GameFitResult] = []
        prev_models = dict(initial_models or {})
        # Incremental priors come from the USER's initial models and stay
        # fixed across the whole grid (warm starts move, priors don't).
        user_priors = {n: prev_models[n] for n in self.incremental
                       if n in prev_models}
        missing = self.incremental - set(user_priors)
        if missing:
            raise ValueError(
                f"incremental coordinates {sorted(missing)} need initial_models")
        for overrides in grid:
            configs = {**self.coordinate_configs, **overrides}
            datasets = {}
            for name, cfg in configs.items():
                key = self._dataset_key(cfg)
                if key not in dataset_cache:
                    dataset_cache[key] = self._build_dataset(data, cfg)
                datasets[name] = dataset_cache[key]
            coords = self._build_coordinates(datasets, configs, coord_cache)
            with telemetry.span("game.fit_point", index=len(results)):
                descent = coordinate_descent(
                    coords,
                    data.y,
                    data.weights,
                    data.offsets,
                    self.task,
                    update_sequence=self.update_sequence,
                    n_sweeps=self.n_sweeps,
                    locked=self.locked,
                    initial_models=prev_models,
                    incremental=self.incremental,
                    priors=user_priors,
                )
            result = GameFitResult(descent.model, descent, configs)
            if validation is not None:
                with telemetry.span("game.validate_point",
                                    index=len(results)):
                    scores = score_game(descent.model, validation)
                    result.validation_score = self._evaluate(
                        evaluator, scores, validation
                    )
            results.append(result)
            if chain_warm:
                prev_models = dict(descent.model.coordinates)
        return results

    def would_vectorize(self, grid, initial_models=None, data=None) -> bool:
        """Whether fit(config_grid=grid) would take a vectorized grid path:
        either the one-program fixed-effect path (single fixed coordinate,
        n_sweeps == 1) or the general lane-axis GAME grid (game.grid:
        fixed + random effects, any n_sweeps — each lane runs the same
        sweeps the sequential path would). Both paths are semantic no-ops
        apart from warm starts ACROSS grid points (lanes run concurrently
        from zeros; a forced vectorized_grid=True keeps that contract even
        on fallback). Public so the training driver's resume logic can make
        the same call without duplicating the gate. Pass ``data`` to also
        check the matrix layouts the lane path supports — without it, the
        answer can be a false positive for Sharded/HybridRows shards
        (fit() would fall back to the sequential path)."""
        vectorize = (self.vectorized_grid is True
                     or (self.vectorized_grid is None
                         and not self.warm_start
                         and self._grid_reg_skew(grid) <= _GRID_SKEW_MAX))
        if not (vectorize and len(grid) >= 2
                and not self.locked and not self.incremental
                and not initial_models):
            return False
        if self.n_sweeps == 1:
            probe = self._fixed_only_reg_grid(grid)
            if probe is not None and self._fixed_seq_ok(probe):
                return True
        if self._game_grid_probe(grid) is None:
            return False
        return data is None or self._grid_data_supported(data)

    def _grid_reg_skew(self, grid) -> float:
        """Max over coordinates of the grid's reg-weight spread
        (max/min across lanes). The lane-axis grid runs every chunk to its
        SLOWEST lane's convergence (masked lanes still execute —
        docs/PERF.md's masking A/B), so a strongly skewed grid pays
        ~G × the hardest lane where the sequential path pays the sum of
        adaptive per-lane costs (measured 3.7× worse lane-axis at spread
        1e5). Auto mode (`vectorized_grid=None`) falls back to sequential
        above ``_GRID_SKEW_MAX``; the explicit tri-state always wins. A
        zero weight among positive ones counts as ≤1e-4 (zero-reg lanes
        are the least-conditioned, slowest converging — strictly slower
        than any positive-reg lane)."""
        skew = 1.0
        for name in set().union(*[set(g) for g in grid]) if grid else ():
            ws = [float(g[name].optimizer.reg_weight)
                  for g in grid if name in g]
            pos = [w for w in ws if w > 0.0]
            if not pos:
                continue
            lo = min(pos)
            if len(pos) < len(ws):  # zero-reg lanes present
                lo = min(lo / 10.0, 1e-4)
            skew = max(skew, max(pos) / lo)
        return skew

    def _fixed_seq_ok(self, probe) -> bool:
        return (self.update_sequence is None
                or list(self.update_sequence) == [probe[0]])

    def _game_grid_probe(self, grid) -> Optional[dict]:
        """{name: [reg_weight per grid point]} when the grid is expressible
        as lane weights over the base configs — every override varies ONLY
        its coordinate's reg weight — and nothing on the model needs the
        sequential path (no projection, no normalization); None otherwise."""
        if any(v is not None for v in self.normalization.values()):
            return None
        names = set(self.coordinate_configs)
        if self.update_sequence is not None and \
                set(self.update_sequence) - names:
            return None
        for cfg in self.coordinate_configs.values():
            if isinstance(cfg, RandomEffectConfig) and cfg.projection is not None:
                return None
        lanes: dict = {n: [] for n in names}
        for overrides in grid:
            if set(overrides) - names:
                return None
            for n, base in self.coordinate_configs.items():
                cfg = overrides.get(n, base)
                if type(cfg) is not type(base):
                    return None
                strip = lambda c: dataclasses.replace(  # noqa: E731
                    c, optimizer=dataclasses.replace(c.optimizer,
                                                     reg_weight=0.0))
                if strip(cfg) != strip(base):
                    return None
                lanes[n].append(float(cfg.optimizer.reg_weight))
        return lanes

    def _chunked_shards(self, data: GameData) -> bool:
        """True when any coordinate's shard is a host-chunked
        (streamed-objective) matrix — those solves are host loops, so every
        vectorized grid path must fall back to the sequential sweep."""
        from photon_tpu.data.dataset import ChunkedMatrix

        return any(isinstance(data.shards[c.feature_shard], ChunkedMatrix)
                   for c in self.coordinate_configs.values())

    def _grid_data_supported(self, data: GameData) -> bool:
        """Matrix layouts the lane-axis grid can run: dense or SparseRows.
        HybridRows' flat COO tail has no (entity, lane) batched form,
        ShardedHybridRows needs the shard_map solver route, and
        PermutedHybridRows' coefficient-space translation lives at the
        train_glm/train_glm_grid boundary the game grid bypasses — all
        three fall back to the sequential path (which routes through
        train_glm and is correct for every layout). ChunkedMatrix
        (streamed-objective) shards fall back the same way — the lane grid
        would multiply the per-pass host→device stream per lane."""
        from photon_tpu.data.dataset import ChunkedMatrix
        from photon_tpu.data.matrix import (BlockedEllRows, HybridRows,
                                            PermutedHybridRows,
                                            ShardedHybridRows)

        for cfg in self.coordinate_configs.values():
            X = data.shards[cfg.feature_shard]
            if isinstance(X, (ShardedHybridRows, PermutedHybridRows,
                              BlockedEllRows, ChunkedMatrix)):
                return False
            if isinstance(X, HybridRows) and (
                    self.mesh is not None
                    or not isinstance(cfg, FixedEffectConfig)):
                return False
        return True

    def _fit_game_grid(self, lanes: dict, data: GameData, validation,
                       evaluator: Evaluator, dataset_cache,
                       coord_cache) -> list:
        """The lane-axis GAME grid (game.grid.fit_game_grid): every grid
        point is a lane of one vectorized coordinate descent."""
        import jax.numpy as jnp

        from photon_tpu.game.grid import fit_game_grid, lane_re_margins
        from photon_tpu.models.glm import _score_many

        configs = self.coordinate_configs
        datasets = {}
        for name, cfg in configs.items():
            key = self._dataset_key(cfg)
            if key not in dataset_cache:
                dataset_cache[key] = self._build_dataset(data, cfg)
            datasets[name] = dataset_cache[key]
        coords = self._build_coordinates(datasets, configs, coord_cache)
        with telemetry.span("game.grid_vectorized",
                            lanes=len(next(iter(lanes.values())))):
            outcome = fit_game_grid(
                coords, lanes, data.y, data.weights, data.offsets,
                self.task, update_sequence=self.update_sequence,
                n_sweeps=self.n_sweeps, mesh=self.mesh)

        G = len(next(iter(lanes.values())))
        val_scores = None
        if validation is not None:
            total = jnp.asarray(validation.offsets, jnp.float32)[None, :]
            for name in outcome.lane_models[0].names():
                cfg = configs[name]
                Xv = validation.shards[cfg.feature_shard]
                if isinstance(cfg, FixedEffectConfig):
                    total = total + _score_many(
                        jnp.asarray(outcome.stacked[name]), Xv, 0.0)
                else:
                    model0 = outcome.lane_models[0].coordinates[name]
                    ids = model0.dense_ids(
                        np.asarray(validation.entity_ids[cfg.entity_name]))
                    total = total + lane_re_margins(
                        jnp.asarray(outcome.stacked[name]), Xv,
                        jnp.asarray(ids))
            val_scores = np.asarray(total)

        results = []
        for g in range(G):
            configs_g = {
                name: dataclasses.replace(
                    cfg, optimizer=dataclasses.replace(
                        cfg.optimizer, reg_weight=lanes[name][g]))
                for name, cfg in configs.items()
            }
            descent = CoordinateDescentResult(
                model=outcome.lane_models[g],
                objective_history=outcome.objective_histories[g],
                coordinate_stats=outcome.coordinate_stats[g],
            )
            r = GameFitResult(outcome.lane_models[g], descent, configs_g)
            if val_scores is not None:
                r.validation_score = self._evaluate(
                    evaluator, val_scores[g], validation)
            results.append(r)
        return results

    def _fixed_only_reg_grid(self, grid):
        """(name, base_config, [reg_weight per grid point]) when the model
        is a single fixed effect and the grid varies ONLY its regularization
        weight; None otherwise (→ sequential path)."""
        if len(self.coordinate_configs) != 1:
            return None
        ((name, base),) = self.coordinate_configs.items()
        if not isinstance(base, FixedEffectConfig):
            return None
        weights = []
        for overrides in grid:
            if set(overrides) - {name}:
                return None
            cfg = {**self.coordinate_configs, **overrides}[name]
            if (not isinstance(cfg, FixedEffectConfig)
                    or cfg.feature_shard != base.feature_shard):
                return None
            if (dataclasses.replace(cfg.optimizer, reg_weight=0.0)
                    != dataclasses.replace(base.optimizer, reg_weight=0.0)):
                return None
            weights.append(float(cfg.optimizer.reg_weight))
        return name, base, weights

    def _fit_fixed_grid(self, probe, data: GameData, validation,
                        evaluator: Evaluator, dataset_cache) -> list:
        """The vectorized fixed-effect grid: one train_glm_grid sweep, one
        batched scoring pass per (train, validation) matrix."""
        import jax.numpy as jnp

        from photon_tpu.game.model import FixedEffectModel
        from photon_tpu.models.glm import score_models
        from photon_tpu.models.training import train_glm_grid
        from photon_tpu.ops.losses import loss_fns

        name, base, weights = probe
        key = self._dataset_key(base)
        if key not in dataset_cache:
            dataset_cache[key] = self._build_dataset(data, base)
        ds = dataset_cache[key]
        norm = self._normalization_for(name, ds)
        with telemetry.span("game.grid_vectorized", lanes=len(weights)):
            grid = train_glm_grid(
                ds.batch(jnp.asarray(data.offsets)), self.task,
                base.optimizer, weights, mesh=self.mesh,
                variance=self.variance, normalization=norm)
        models = [m for m, _ in grid]
        # Per-lane total training objective (unregularized weighted loss —
        # what coordinate_descent's objective_history records), from ONE
        # batched scoring pass.
        loss, _, _ = loss_fns(self.task)
        margins = score_models(models, ds.X, jnp.asarray(data.offsets))
        objectives = np.asarray(
            jnp.sum(ds.weights * loss(margins, ds.y), axis=1))
        val_margins = None
        if validation is not None:
            Xv = validation.shards[base.feature_shard]
            val_margins = np.asarray(score_models(
                models, Xv, jnp.asarray(validation.offsets)))
        results = []
        for i, (model, res) in enumerate(grid):
            cfg_i = FixedEffectConfig(
                base.feature_shard,
                dataclasses.replace(base.optimizer, reg_weight=weights[i]))
            game_model = GameModel(
                {name: FixedEffectModel(model, base.feature_shard)},
                self.task)
            descent = CoordinateDescentResult(
                model=game_model,
                objective_history=[float(objectives[i])],
                coordinate_stats={name: [res]},
            )
            r = GameFitResult(game_model, descent, {name: cfg_i})
            if val_margins is not None:
                r.validation_score = self._evaluate(
                    evaluator, val_margins[i], validation)
            results.append(r)
        return results

    def evaluate_scores(self, evaluator: Evaluator, scores,
                        validation: GameData) -> float:
        """Public alias of the validation-metric computation (used by the
        drivers to report extra evaluators on the best model)."""
        return self._evaluate(evaluator, scores, validation)

    def _evaluate(self, evaluator: Evaluator, scores, validation: GameData) -> float:
        """Run the validation evaluator; sharded evaluators group by the
        estimator's `evaluator_entity` (default: the first random-effect
        coordinate's entity type), as the reference's per-entity validation
        evaluators do."""
        if not evaluator.needs_groups:
            return evaluator.evaluate(scores, validation.y, validation.weights)
        from photon_tpu.evaluation.evaluator import evaluate_with_entity

        entity = self.evaluator_entity
        if entity is None:
            for cfg in self.coordinate_configs.values():
                if isinstance(cfg, RandomEffectConfig):
                    entity = cfg.entity_name
                    break
        return evaluate_with_entity(evaluator, scores, validation.y,
                                    validation.weights,
                                    validation.entity_ids, entity)

    def best_model(self, results: list) -> GameFitResult:
        """Pick by validation metric with the evaluator's direction
        (reference: GameTrainingDriver.selectBestModel); falls back to the
        final training objective when no validation data was given."""
        evaluator = self.evaluator or default_evaluator(self.task)
        best = None
        for r in results:
            if r.validation_score is not None:
                if best is None or evaluator.better_than(
                    r.validation_score, best.validation_score
                ):
                    best = r
            else:
                obj = (r.descent.objective_history[-1]
                       if r.descent.objective_history else float("inf"))
                best_obj = (
                    best.descent.objective_history[-1]
                    if best is not None and best.descent.objective_history
                    else float("inf")
                )
                if best is None or obj < best_obj:
                    best = r
        if best is None:
            raise ValueError("no fit results to select from")
        return best
