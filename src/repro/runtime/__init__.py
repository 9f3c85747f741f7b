"""One composable Trainer API over every execution regime.

``repro.runtime`` is the layer that makes launchers, examples, and
benchmarks *thin clients*: a frozen, JSON-round-trippable
:class:`RuntimeConfig` names a registered runtime, one per execution
topology —

=====================  ==================================================
``local``              single-process jit training, no distribution layer
``zero``               DynaComm-bucketed ZeRO over the data mesh
``ps``                 synchronous parameter server, consensus plan
``ps-async``           bounded-staleness asynchronous PS, one plan
``dynamic-ps-async``   async PS, per-worker re-plans per topology epoch
``fleet-async``        async PS over an elastic, churning fleet
``pipeline``           stage-partitioned pipeline parallelism
=====================  ==================================================

— and :func:`build_runtime` turns it into an object implementing the
:class:`Trainer` protocol — ``fit`` / ``step`` / ``events`` /
``timeline`` / ``ledger`` / ``save_state`` / ``restore_state``.  ``zero``
and ``ps`` re-plan at every epoch boundary whenever their schedule can
change the plan (:attr:`RuntimeConfig.is_dynamic`); a static network is
a one-knot schedule, planned once.  New regimes cost one
``@register_runtime`` entry, not a new hand-wired launcher branch.

The run-time re-planning loop (:class:`ReplanMixin`,
:class:`PlanStepCache`, :class:`RescheduleEvent`, the Table I idle-window
bookkeeping) lives in :mod:`repro.runtime.replan`.
"""

from repro.runtime.config import (RUNTIME_REGIMES, CompressionConfig,
                                  ExecutionConfig, FleetConfig,
                                  FleetEventConfig, MeasureConfig,
                                  NetworkConfig, PipelineConfig,
                                  RuntimeConfig, ScheduleConfig,
                                  TopologyConfig)
from repro.runtime.protocol import EvalEvent, Trainer
from repro.runtime.replan import (PlanStepCache, ReplanMixin,
                                  RescheduleEvent, hlo_collective_counts,
                                  sequential_plan)

__all__ = [
    "RuntimeConfig", "ScheduleConfig", "ExecutionConfig", "MeasureConfig",
    "NetworkConfig", "TopologyConfig", "CompressionConfig",
    "FleetConfig", "FleetEventConfig", "PipelineConfig",
    "RUNTIME_REGIMES",
    "Trainer", "EvalEvent",
    "PlanStepCache", "RescheduleEvent", "ReplanMixin",
    "hlo_collective_counts", "sequential_plan",
    "build_runtime", "register_runtime", "runtime_names", "RUNTIMES",
]

_REGISTRY_NAMES = ("build_runtime", "register_runtime", "runtime_names",
                   "RUNTIMES")


def __getattr__(name: str):
    # the registry pulls in the trainer stack (dist/ps); load it lazily so
    # `repro.dist` ← `repro.runtime.replan` stays cycle-free
    if name in _REGISTRY_NAMES:
        from repro.runtime import registry
        return getattr(registry, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
