#
# Continuous-learning plane (docs/design.md §7d): streamed `partial_fit` on
# the out-of-core estimators, drift detection over the convergence plane, and
# governed live promotion through the serving mutate path.
#
# Three layers, composed from finished planes rather than new machinery:
#   partial_fit  persistent sufficient-statistics carries folded by the SAME
#                accumulator kernels the streamed fits run (ops/streaming.py),
#                snapshot/restore via reliability/checkpoint.py, fixed block
#                geometry so a steady update stream adds zero new
#                `device.compile` entries after warm-up
#   drift        median + MAD-floor judgment over per-update inertia/loss/
#                residual, emitting `continual.drift{model=,signal=}` into
#                run reports and the flight recorder
#   promotion    validate-on-holdout then swap through serving.mutate_model
#                under the per-entry exec lock (fleet fan-out, monotone
#                `serving.model_generation` bump, never a recompile)
#

from .drift import DriftDetector, baseline_from_convergence, resolve_drift_mads
from .partial_fit import (
    KMeansUpdater,
    LinearRegressionUpdater,
    LogisticRegressionUpdater,
    PCAUpdater,
    PartialFitUpdater,
    partial_fit_updater,
    resolve_decay,
    resolve_update_batch_rows,
)
from .promote import ContinualLoop, PromotionGovernor

__all__ = [
    "ContinualLoop",
    "DriftDetector",
    "KMeansUpdater",
    "LinearRegressionUpdater",
    "LogisticRegressionUpdater",
    "PCAUpdater",
    "PartialFitUpdater",
    "PromotionGovernor",
    "baseline_from_convergence",
    "partial_fit_updater",
    "resolve_decay",
    "resolve_drift_mads",
    "resolve_update_batch_rows",
]
