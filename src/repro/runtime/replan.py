"""The run-time re-planning loop of the synchronous runtimes.

The paper's run-time loop (Section IV-C): at every epoch boundary the
costs are priced anew (pt/gt/Δt from the epoch's network or PS topology,
fc/bc analytic or measured), the scheduler decides, and the compiled step
of the decided plan is swapped in.  ``zero`` and ``ps`` run the loop
whenever their schedule can change the plan
(:attr:`repro.runtime.config.RuntimeConfig.is_dynamic`); a one-knot
analytic schedule is decided once at start-up and has nothing to re-plan.

* :class:`PlanStepCache` — ``BucketPlan``-keyed AOT compiled-step cache:
  each distinct plan is traced and compiled exactly once
  (``.lower().compile()``), revisits are dictionary lookups, and per-plan
  compiled pull/push segment counts are kept for the structural
  assertions;
* :class:`RescheduleEvent` — one scheduling pass (paper Table I
  bookkeeping: scheduling wall time + the overhead-hidden check against
  the Δt + gt¹ idle window);
* :class:`ReplanMixin` — the loop, written once: boundary detection, cost
  refresh (:func:`repro.runtime.measure.measurement_due`), decision, plan
  swap, event record, drift detection and loop-state checkpoints.  A
  runtime supplies its epoch pricing, its scheduler, and what a new epoch
  re-points.
"""

from __future__ import annotations

import dataclasses
import json
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.hlo import collective_counts as _collective_counts
from repro.analysis.hlo import segment_counts
from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
from repro.core.buckets import BucketPlan, plan_from_decision
from repro.core.profiler import EwmaDriftDetector, LayerTimingHook
from repro.runtime.measure import measure_layer_times, measurement_due


def hlo_collective_counts(hlo_text: str) -> Tuple[int, int]:
    """(#all-gathers, #reduce-scatters) in a compiled HLO dump."""
    counts = _collective_counts(hlo_text)
    return counts["all-gather"], counts["reduce-scatter"]


def sequential_plan(num_layers: int) -> BucketPlan:
    """The whole model as one pull and one push bucket (always valid)."""
    return BucketPlan(forward=(tuple(range(num_layers)),),
                      backward=(tuple(range(num_layers - 1, -1, -1)),))


@dataclasses.dataclass(frozen=True)
class RescheduleEvent:
    """One scheduling pass (paper Table I bookkeeping)."""

    step: int                     # global step index at the epoch boundary
    epoch: int
    plan: BucketPlan              # plan active after this pass
    plan_changed: bool            # decision differed from the previous epoch
    retraced: bool                # False ⇒ compiled-step cache hit (or no swap)
    scheduling_seconds: float     # wall time of the DP re-plan
    overhead_hidden: bool         # fits in the Δt + gt¹ idle window (Table I)
    trigger: str = "epoch"        # "epoch" boundary | "drift" detector


#: compiled HLO dumps retained per cache — comfortably above the plan
#: count any smoke config or ``repro.analysis verify`` pass touches, so
#: every live plan stays auditable, while fleet churn (per-worker plans
#: multiplying across membership changes) can no longer grow text dumps
#: without bound.  Compiled steps and collective *counts* are small and
#: stay unbounded: evicting a step would force a retrace.
DEFAULT_HLO_RETENTION = 16


class PlanStepCache:
    """``BucketPlan``-keyed AOT compiled-step cache (see module docstring).

    ``hlo_retention`` bounds how many full HLO text dumps are kept
    (keep-last-N by compile order); ``hlo_evictions`` counts dumps
    dropped over the bound."""

    def __init__(self, *, hlo_retention: int = DEFAULT_HLO_RETENTION):
        if hlo_retention < 1:
            raise ValueError(
                f"hlo_retention must be >= 1, got {hlo_retention}")
        self._steps: Dict[BucketPlan, Callable] = {}
        self._hlo: Dict[BucketPlan, Tuple[int, int]] = {}
        self._hlo_text: "OrderedDict[BucketPlan, str]" = OrderedDict()
        self.hlo_retention = hlo_retention
        self.hlo_evictions = 0         # HLO dumps dropped over the bound
        self.traces = 0                # compile-cache misses
        self.hits = 0                  # plan *swaps* served from the cache

    @property
    def plans(self) -> Tuple[BucketPlan, ...]:
        return tuple(self._steps)

    def hlo_counts(self, plan: BucketPlan) -> Tuple[int, int]:
        """(pull, push) segments compiled in a cached plan's step
        (``repro.analysis.hlo.segment_counts``)."""
        if plan not in self._hlo:
            raise KeyError(f"plan {plan} has no compiled step yet")
        return self._hlo[plan]

    def hlo_text(self, plan: BucketPlan) -> str:
        """The compiled HLO dump of a cached plan's step (kept so the
        conformance pass can audit every plan without recompiling;
        only the last ``hlo_retention`` compiles are retained)."""
        if plan not in self._hlo_text:
            raise KeyError(f"plan {plan} has no retained HLO dump "
                           f"(never compiled, or evicted past the "
                           f"keep-last-{self.hlo_retention} bound)")
        return self._hlo_text[plan]

    def step_for(self, plan: BucketPlan, build_step: Callable[[], Callable],
                 state, batch, *, count_hit: bool) -> Tuple[Callable, bool]:
        """The compiled step for ``plan``, compiling via ``build_step()``
        on a miss.  Returns ``(step_fn, retraced)``; ``count_hit`` tells
        whether a cache hit is an actual plan swap (a post-restore
        recompile of the unchanged plan is not)."""
        if plan in self._steps:
            if count_hit:
                self.hits += 1
            return self._steps[plan], False
        self.traces += 1
        compiled = jax.jit(build_step()).lower(state, batch).compile()
        text = compiled.as_text()
        self._hlo[plan] = segment_counts(text)
        self._hlo_text[plan] = text
        while len(self._hlo_text) > self.hlo_retention:
            self._hlo_text.popitem(last=False)
            self.hlo_evictions += 1
        self._steps[plan] = compiled
        return compiled, True


class ReplanMixin:
    """The run-time loop over a synchronous runtime's compiled step.

    The host runtime holds ``config``, ``trainer`` (the active plan's
    ZeRO or PS trainer), ``_state``, ``_step_fn`` and ``_data_idx`` (steps
    run), calls :meth:`_init_replan` before building its trainer and
    :meth:`_replan` before each step, and supplies ``_price(epoch, fc_bc)``
    (the epoch's costs, with analytic fc/bc where ``fc_bc`` is None) and,
    where a new epoch changes more than the costs, ``_enter_epoch``.
    """

    def _init_replan(self, scheduler, planner) -> None:
        measure = self.config.measure
        self.scheduler, self.planner = scheduler, planner
        self.hook = LayerTimingHook(warmup=measure.measure_warmup)
        self.drift_detector = (EwmaDriftDetector()
                               if self.config.schedule.drift_detect else None)
        self._replans: List[RescheduleEvent] = []
        self._cache = PlanStepCache()
        self._plan: Optional[BucketPlan] = None
        self._step_fn: Optional[Callable] = None
        self._decision = None
        self._costs = None
        self._measured_fc_bc: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._measured_epoch = -1
        self._drift_pending = False

    def _enter_epoch(self, epoch: int) -> None:
        """Re-point what follows the epoch besides its costs (a hook)."""

    # -- introspection ---------------------------------------------------

    @property
    def plan(self) -> Optional[BucketPlan]:
        """The active bucket plan (None before a re-planning run's first
        step)."""
        return self._plan

    @property
    def epoch(self) -> int:
        return self._data_idx // self.config.schedule.reschedule_every

    @property
    def plans_seen(self) -> Tuple[BucketPlan, ...]:
        return self._cache.plans

    @property
    def traces(self) -> int:
        """Compiled-step cache misses (one trace per distinct plan)."""
        return self._cache.traces

    @property
    def cache_hits(self) -> int:
        """Plan swaps served from the compiled-step cache."""
        return self._cache.hits

    def hlo_counts(self, plan: Optional[BucketPlan] = None) -> Tuple[int, int]:
        """(pull, push) segments compiled in a cached plan's step."""
        return self._cache.hlo_counts(self._plan if plan is None else plan)

    # -- costs -----------------------------------------------------------

    def costs_for_epoch(self, epoch: int, state=None, batch=None, *,
                        remeasure: bool = False):
        """The epoch's costs.  With ``cost_source="measured"``, fc/bc are
        timed on this host every ``remeasure_every`` epochs (or now, with
        ``remeasure``), which needs ``state`` and ``batch``; without them
        the last measurement serves."""
        measure = self.config.measure
        if measure.cost_source == "measured" and measurement_due(
                self._measured_fc_bc, self._measured_epoch, epoch,
                measure.remeasure_every, force=remeasure):
            if state is not None and batch is not None:
                # a PS trainer runs the step of its contained ZeroTrainer
                zero = getattr(self.trainer, "_zero", self.trainer)
                measure_layer_times(zero, self.hook, state, batch,
                                    iters=measure.measure_iters)
                L = zero.num_layers
                self._measured_fc_bc = (self.hook.median("fc", L),
                                        self.hook.median("bc", L))
                self._measured_epoch = epoch
            elif self._measured_fc_bc is None:
                raise ValueError("cost_source='measured' needs state and "
                                 "batch for the first measurement")
        return self._price(epoch, self._measured_fc_bc)

    # -- the loop ----------------------------------------------------------

    def _replan(self, batch) -> None:
        """Re-plan before step ``_data_idx``: on an epoch boundary, or
        when the drift detector fired after the previous step."""
        i, every = self._data_idx, self.config.schedule.reschedule_every
        drift, self._drift_pending = self._drift_pending, False
        boundary = i % every == 0 or drift
        if boundary:
            self._costs = self.costs_for_epoch(i // every, self._state, batch,
                                               remeasure=drift)
            self._enter_epoch(i // every)
            if drift:
                self.scheduler.invalidate()
        decision = self.scheduler.decision_for_iteration(self._costs)
        changed = decision != self._decision
        # (``_step_fn is None`` off-boundary ⇒ loop state was just restored
        # from a checkpoint: recompile the active plan, no scheduling event)
        if not boundary and not changed and self._step_fn is not None:
            return
        plan = plan_from_decision(*decision, self.trainer.num_layers)
        prev, retraced = self._plan, False
        if plan != prev or self._step_fn is None:
            trainer = self.trainer.with_plan(plan)
            self._step_fn, retraced = self._cache.step_for(
                plan, trainer.build_train_step, self._state, batch,
                count_hit=plan != prev)
            self.trainer, self._plan = trainer, plan
        self._decision = decision
        if boundary or changed:
            self._replans.append(RescheduleEvent(
                step=i, epoch=i // every, plan=plan,
                plan_changed=prev is not None and plan != prev,
                retraced=retraced,
                scheduling_seconds=self.scheduler.last_scheduling_seconds,
                overhead_hidden=self.scheduler.scheduling_overhead_hidden(
                    self._costs),
                trigger="drift" if drift else "epoch"))
        sched = self.config.schedule
        if boundary and sched.async_planning and \
                self.config.measure.cost_source == "analytic":
            # Phase one of the async protocol: the analytic cost point of
            # epoch e+1 is a pure function of the epoch, so its DP can run
            # now, in this epoch's Δt + gt¹ idle window (Table I), and be
            # collected at the next boundary.  Measured costs aren't
            # predictable ahead of time — they solve inline (the planner's
            # sync fallback).
            nxt = self.costs_for_epoch(i // every + 1)
            for costs in getattr(nxt, "workers", (nxt,)):
                self.planner.submit(costs, sched.strategy)

    # -- (de)serialization for loop-state checkpointing -----------------

    @staticmethod
    def _plan_to_obj(plan: Optional[BucketPlan]):
        if plan is None:
            return None
        return {"forward": [list(b) for b in plan.forward],
                "backward": [list(b) for b in plan.backward]}

    @staticmethod
    def _plan_from_obj(obj) -> Optional[BucketPlan]:
        if obj is None:
            return None
        return BucketPlan(
            forward=tuple(tuple(b) for b in obj["forward"]),
            backward=tuple(tuple(b) for b in obj["backward"]))

    @classmethod
    def _events_to_obj(cls, events) -> List[Dict[str, Any]]:
        return [{
            "step": e.step, "epoch": e.epoch,
            "plan": cls._plan_to_obj(e.plan),
            "plan_changed": e.plan_changed, "retraced": e.retraced,
            "scheduling_seconds": e.scheduling_seconds,
            "overhead_hidden": e.overhead_hidden, "trigger": e.trigger,
        } for e in events]

    @classmethod
    def _events_from_obj(cls, obj) -> List[RescheduleEvent]:
        return [RescheduleEvent(
            step=e["step"], epoch=e["epoch"],
            plan=cls._plan_from_obj(e["plan"]),
            plan_changed=e["plan_changed"], retraced=e["retraced"],
            scheduling_seconds=e["scheduling_seconds"],
            overhead_hidden=e["overhead_hidden"],
            trigger=e.get("trigger", "epoch")) for e in obj]

    # -- loop-state checkpointing ----------------------------------------
    #
    # The *model* state is an ordinary pytree checkpointed separately;
    # this captures the re-planning bookkeeping — scheduler counters,
    # active plan, event history, measurement cache, planner memo, drift
    # detector — so a resumed run replays the same plan sequence.
    # Compiled steps are not serializable: the restored plan recompiles
    # lazily on the first post-restore step (no scheduling event is
    # recorded).

    def loop_state(self) -> Dict[str, np.ndarray]:
        """The re-planning loop bookkeeping as a checkpointable pytree."""
        det = self.drift_detector
        meta = {
            "scheduler": self.scheduler.state_dict(),
            "plan": self._plan_to_obj(self._plan),
            "events": self._events_to_obj(self._replans),
            "measured_epoch": self._measured_epoch,
            "drift_pending": self._drift_pending,
            "drift_detector": (det.state_dict()
                               if hasattr(det, "state_dict") else None),
        }
        if self.planner is not None:
            meta["planner"] = self.planner.state_dict()
        state = {"meta": np.asarray(json.dumps(meta))}
        if self._measured_fc_bc is not None:
            fc, bc = self._measured_fc_bc
            state["measured_fc"] = np.asarray(fc, np.float64)
            state["measured_bc"] = np.asarray(bc, np.float64)
        return state

    def save_loop_state(self, path: str) -> None:
        save_checkpoint(path, self.loop_state(), step=self._data_idx)

    def restore_loop_state(self, path: str) -> None:
        """Restore the loop at the step the model checkpoint restored."""
        Ls = self.trainer.num_layers
        template: Dict[str, np.ndarray] = {"meta": np.asarray("")}
        with np.load(path) as probe:
            if "measured_fc" in probe.files:   # saved after a measurement
                template["measured_fc"] = np.zeros((Ls,), np.float64)
                template["measured_bc"] = np.zeros((Ls,), np.float64)
        tree, _ = load_checkpoint(path, template)
        meta = json.loads(str(tree["meta"]))
        self.scheduler.load_state_dict(dict(meta["scheduler"]))
        self._decision = self.scheduler._decision
        self._plan = self._plan_from_obj(meta["plan"])
        self._measured_epoch = int(meta.get("measured_epoch", -1))
        if "measured_fc" in tree:
            self._measured_fc_bc = (np.asarray(tree["measured_fc"]),
                                    np.asarray(tree["measured_bc"]))
        self._replans = self._events_from_obj(meta["events"])
        if self.planner is not None and meta.get("planner") is not None:
            self.planner.load_state_dict(meta["planner"])
        self._drift_pending = bool(meta.get("drift_pending", False))
        det_state = meta.get("drift_detector")
        if det_state is not None and \
                hasattr(self.drift_detector, "load_state_dict"):
            self.drift_detector.load_state_dict(det_state)
        self._step_fn = None       # recompiled lazily on the next step
        self._costs = None
        self._enter_epoch(self.epoch)
