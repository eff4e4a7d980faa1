"""Fixed-effect coordinate: one distributed GLM solve over all rows.

Reference parity: com.linkedin.photon.ml.algorithm.FixedEffectCoordinate —
trainModel broadcasts coefficients and treeAggregates gradients; here the
whole solve is `train_glm`'s single SPMD program over the mesh's data axis
(one psum per iteration over the ICI).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh

from photon_tpu.game.dataset import FixedEffectDataset
from photon_tpu.game.model import FixedEffectModel
from photon_tpu.models.training import train_glm
from photon_tpu.models.variance import VarianceComputationType
from photon_tpu.ops.losses import TaskType
from photon_tpu.optim.config import OptimizerConfig
from photon_tpu.optim.tracker import OptResult


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinate:
    """Reference: algorithm.FixedEffectCoordinate."""

    dataset: FixedEffectDataset
    task: TaskType
    config: OptimizerConfig
    mesh: Optional[Mesh] = None
    variance: VarianceComputationType = VarianceComputationType.NONE
    # data.normalization.NormalizationContext for this coordinate's shard;
    # train_glm runs the solve in normalized space and returns original-space
    # coefficients, so score() below needs no changes.
    normalization: Optional[object] = None

    def train(
        self,
        offsets_full,
        warm_start: Optional[FixedEffectModel] = None,
        prior: Optional[FixedEffectModel] = None,
    ) -> tuple[FixedEffectModel, OptResult]:
        """Solve with the other coordinates' scores as offsets
        (reference: FixedEffectCoordinate.trainModel on updated offsets).

        ``prior``: a previous run's model whose coefficients/variances become
        an informative Gaussian prior (incremental training; reference:
        PriorDistribution built from the initial model)."""
        w0 = None
        if (warm_start is not None
                and warm_start.model.weights.shape[0] == self.dataset.dim):
            w0 = warm_start.model.weights
        model, res = train_glm(
            self.dataset.batch(offsets_full),
            self.task,
            self.config,
            mesh=self.mesh,
            w0=w0,
            variance=self.variance,
            normalization=self.normalization,
            prior=self.prior_distribution(prior),
        )
        return FixedEffectModel(model, self.dataset.shard_name), res

    def prior_distribution(self, prior: Optional[FixedEffectModel]):
        """A previous run's model as this coordinate's Gaussian prior
        (`PriorDistribution.from_coefficients` of its means and variances);
        None where there is none, or it is over another feature space."""
        if prior is None or prior.model.weights.shape[0] != self.dataset.dim:
            return None
        from photon_tpu.optim.prior import PriorDistribution

        coeffs = prior.model.coefficients
        return PriorDistribution.from_coefficients(coeffs.means,
                                                   coeffs.variances)

    def score(self, model: FixedEffectModel):
        """Margin contribution of this coordinate alone (no offsets) —
        reference: FixedEffectCoordinate.score / updateOffsets.

        A streamed (ChunkedMatrix) shard scores chunk-by-chunk into a
        HOST (n,) margin cache — row-sharded over the coordinate's mesh
        when one is set — so the full-dataset score vector never
        materializes on device (the pod-scale GAME regime; the descent
        loop sums offsets against the host caches)."""
        from photon_tpu.data.dataset import ChunkedMatrix

        if isinstance(self.dataset.X, ChunkedMatrix):
            from photon_tpu.game.scoring import score_chunked_host

            return score_chunked_host(self.dataset.X,
                                      model.model.weights, self.mesh)
        return model.score(self.dataset.X)
