"""Per-phase multi-resource telemetry + decomposed cost models.

The paper models total execution time as one scalar; its companion papers
model the CPU and network signals underneath.  This package is the
observability layer that makes both possible on the live engine:

    trace.py     — PhaseStats / JobTrace / PhaseRecorder: per-phase wall
                   times + resource counters with checkable conservation
                   laws; thread a recorder through ``build_job(recorder=)``
    estimator.py — static per-phase flops/bytes via XLA cost_analysis,
                   no execution required
    models.py    — one regression per (phase, resource) on the paper's
                   basis, composed total-time prediction, ModelDatabase
                   storage under resource-qualified keys

Entry points: ``python -m benchmarks.run --sections phases`` (composed vs
monolithic prediction error, per-phase breakdown),
``examples/phase_breakdown.py`` (walkthrough), and the ``predict-resource``
cluster policy (shuffle-bytes-aware dispatch).
"""

from repro.telemetry.trace import (
    PAIR_BYTES,
    TRACE_SCHEMA_VERSION,
    JobTrace,
    PhaseRecorder,
    PhaseStats,
    collect_traced,
)
from repro.telemetry.estimator import (
    estimates_available,
    stage_cost_estimates,
)
from repro.telemetry.models import (
    DEFAULT_COUNTER_TARGETS,
    PHASE_ORDER,
    TIME_RESOURCE,
    PhaseModelSet,
    composed_vs_monolithic,
    fit_phase_models,
    phase_resource_key,
    split_resource_key,
    targets_from_traces,
)

__all__ = [
    "PAIR_BYTES",
    "TRACE_SCHEMA_VERSION",
    "JobTrace",
    "PhaseRecorder",
    "PhaseStats",
    "collect_traced",
    "estimates_available",
    "stage_cost_estimates",
    "DEFAULT_COUNTER_TARGETS",
    "PHASE_ORDER",
    "TIME_RESOURCE",
    "PhaseModelSet",
    "composed_vs_monolithic",
    "fit_phase_models",
    "phase_resource_key",
    "split_resource_key",
    "targets_from_traces",
]
