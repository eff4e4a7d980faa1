"""The central contract registry: importing this module imports every
hot-path module, which registers its ContractSpecs into
`photon_tpu.analysis.contracts.REGISTRY` as a side effect of import (each
spec lives at the bottom of the module whose program it pins — a hot-path
change and its contract change land in the same diff).

Everything here is import + registration only; nothing traces until
`load_registry()`'s caller asks `contracts.check_registry` to.
"""
from __future__ import annotations

import importlib

# Every module that registers ContractSpecs. Order is import order only;
# the registry itself is a flat name -> spec mapping.
HOT_PATH_MODULES = (
    "photon_tpu.data.matrix",         # blocked-ELL scatter-free X passes
    "photon_tpu.data.ingest_plane",   # ingest plane: chunk-program invariance
    "photon_tpu.ops.objective",       # resident evaluation + trial programs
    "photon_tpu.parallel.mesh",       # shard_map value_and_grad (1-D, hybrid)
    "photon_tpu.models.training",     # resident/lane solvers, sharded hybrids
    "photon_tpu.optim.streamed",      # streamed + mesh-streamed chunk regime
    "photon_tpu.game.random_effect",  # vmapped per-entity lane solves
    "photon_tpu.game.coordinate_descent",  # fused GAME coordinate update
    "photon_tpu.game.scoring",        # streamed inter-coordinate scorer
    "photon_tpu.drivers.score",       # chunked scoring driver program
    "photon_tpu.telemetry.taps",      # telemetry-off-is-free guarantee
    "photon_tpu.telemetry.trace",     # request-tracing-off-is-free guarantee
    "photon_tpu.serving.programs",    # online per-request scoring ladder
    "photon_tpu.serving.admission",   # overload policy: program invariance
    "photon_tpu.serving.fleet",       # replica-shard per-request path
    "photon_tpu.checkpoint.taps",     # checkpoint-off-is-free guarantee
    "photon_tpu.profiling.ledger",    # ledger-off-is-free guarantee
    "photon_tpu.evaluation.grouped",  # scatter-free per-entity metrics
    "photon_tpu.continual.refresh",   # delta-refresh compacted solve + no-retrace
    "photon_tpu.tuning.lane_tuner",   # lane-batched tuner dispatch + round budget
)


def load_registry() -> dict:
    """Import all hot-path modules and return {name: ContractSpec}."""
    for mod in HOT_PATH_MODULES:
        importlib.import_module(mod)
    from photon_tpu.analysis.contracts import REGISTRY

    return dict(REGISTRY)
