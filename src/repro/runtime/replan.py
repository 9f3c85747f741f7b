"""Run-time re-planning machinery shared by every dynamic trainer.

PR 2 grew a compiled-step cache and reschedule-event bookkeeping inside
``repro.dist.dynamic``; PR 4 duplicated the pattern for the PS regime in
``repro.ps.dynamic``.  This module is the single home for that machinery:

* :class:`PlanStepCache` — ``BucketPlan``-keyed AOT compiled-step cache:
  each distinct plan is traced and compiled exactly once
  (``.lower().compile()``), revisits are dictionary lookups, and per-plan
  compiled pull/push segment counts are kept for the structural
  assertions;
* :class:`RescheduleEvent` — one scheduling pass (paper Table I
  bookkeeping: scheduling wall time + the overhead-hidden check against
  the Δt + gt¹ idle window);
* :class:`ReplanMixin` — the swap-and-record loop body both drivers
  share: activate a plan (compiling on a miss, counting cache hits only
  for genuine plan swaps), and record the ``RescheduleEvent`` for a
  scheduling pass, including the Table I idle-window check delegated to
  the scheduler;
* plan/event (de)serialization helpers used by the loop-state
  checkpointing of both drivers.

``repro.dist.dynamic`` keeps deprecation shims for the old import paths.
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
from repro.core.buckets import BucketPlan


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
    """Shared plan-swap + event-record body of the dynamic drivers.

    A driver calls :meth:`_init_replan` from its ``__post_init__``, then
    per scheduling pass :meth:`_activate_plan` (compile-or-lookup, swap)
    and :meth:`_record_reschedule` (``RescheduleEvent`` with the paper's
    Table I ``scheduling_overhead_hidden`` check — the scheduler compares
    its last DP wall time against the costs' Δt + gt¹ idle window).
    """

    def _init_replan(self, *, hlo_retention: int = DEFAULT_HLO_RETENTION
                     ) -> None:
        self.events: List[RescheduleEvent] = []
        self._cache = PlanStepCache(hlo_retention=hlo_retention)
        self._plan: Optional[BucketPlan] = None
        self._step_fn: Optional[Callable] = None

    # -- introspection (uniform across drivers) -------------------------

    @property
    def plan(self) -> Optional[BucketPlan]:
        """The currently active bucket plan (None before the first step)."""
        return self._plan

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

    @property
    def hlo_evictions(self) -> int:
        """HLO text dumps dropped past the keep-last-N retention bound."""
        return self._cache.hlo_evictions

    def hlo_counts(self, plan: Optional[BucketPlan] = None) -> Tuple[int, int]:
        """(pull, push) segments compiled in a cached plan's step."""
        return self._cache.hlo_counts(self._plan if plan is None else plan)

    # -- the shared loop body -------------------------------------------

    def _activate_plan(self, plan: BucketPlan,
                       build_step: Callable[[], Callable],
                       state, batch) -> Tuple[Optional[BucketPlan], bool]:
        """Make ``plan`` the active compiled step if it differs from the
        current one (or none is compiled yet).  Returns
        ``(previous_plan, retraced)``."""
        prev = self._plan
        retraced = False
        if plan != prev or self._step_fn is None:
            self._step_fn, retraced = self._cache.step_for(
                plan, build_step, state, batch, count_hit=plan != prev)
            self._plan = plan
        return prev, retraced

    def _record_reschedule(self, *, step: int, epoch: int, plan: BucketPlan,
                           prev: Optional[BucketPlan], retraced: bool,
                           scheduler, costs, trigger: str = "epoch") -> None:
        """Append the ``RescheduleEvent`` for one scheduling pass."""
        self.events.append(RescheduleEvent(
            step=step, epoch=epoch, plan=plan,
            plan_changed=prev is not None and plan != prev,
            retraced=retraced,
            scheduling_seconds=scheduler.last_scheduling_seconds,
            overhead_hidden=scheduler.scheduling_overhead_hidden(costs),
            trigger=trigger))

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

    # -- loop-state checkpointing (shared by both dynamic drivers) ------
    #
    # The *model* state is an ordinary pytree checkpointed separately;
    # this captures the re-planning bookkeeping — step/scheduler
    # counters, active plan, event history, measurement cache — so a
    # resumed run replays the same plan sequence.  Compiled steps are not
    # serializable: the restored plan recompiles lazily on the first
    # post-restore step (no scheduling event is recorded).  Drivers
    # expect the shared attribute set (scheduler, _step_idx, cost_source,
    # _measured_fc_bc, _measured_epoch, base.num_layers) and add their
    # extras through ``extra_meta`` / the returned meta dict.

    def loop_state(self, *, extra_meta: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, np.ndarray]:
        """The re-planning loop bookkeeping as a checkpointable pytree."""
        meta = {
            "scheduler": self.scheduler.state_dict(),
            "plan": self._plan_to_obj(self._plan),
            "events": self._events_to_obj(self.events),
            "measured_epoch": self._measured_epoch,
        }
        planner = getattr(self, "planner", None)
        if planner is not None:
            meta["planner"] = planner.state_dict()
        if extra_meta:
            meta.update(extra_meta)
        state = {"step_idx": np.asarray(self._step_idx, np.int64),
                 "meta": np.asarray(json.dumps(meta))}
        if self._measured_fc_bc is not None:
            fc, bc = self._measured_fc_bc
            state["measured_fc"] = np.asarray(fc, np.float64)
            state["measured_bc"] = np.asarray(bc, np.float64)
        return state

    def save_loop_state(self, path: str) -> None:
        save_checkpoint(path, self.loop_state(), step=self._step_idx)

    def _restore_loop_common(self, path: str) -> Dict[str, Any]:
        """Restore the shared loop state; returns the meta dict so the
        driver can pick up its extras."""
        Ls = self.base.num_layers
        template: Dict[str, np.ndarray] = {
            "step_idx": np.zeros((), np.int64), "meta": np.asarray("")}
        if self.cost_source == "measured":
            with np.load(path) as probe:
                has_measured = "measured_fc" in probe.files
            if has_measured:       # absent ⇒ saved before 1st measurement
                template["measured_fc"] = np.zeros((Ls,), np.float64)
                template["measured_bc"] = np.zeros((Ls,), np.float64)
        tree, _ = load_checkpoint(path, template)
        meta = json.loads(str(tree["meta"]))
        self._step_idx = int(tree["step_idx"])
        self.scheduler.load_state_dict(dict(meta["scheduler"]))
        self._plan = self._plan_from_obj(meta["plan"])
        self._measured_epoch = int(meta.get("measured_epoch", -1))
        if "measured_fc" in tree:
            self._measured_fc_bc = (np.asarray(tree["measured_fc"]),
                                    np.asarray(tree["measured_bc"]))
        self.events = self._events_from_obj(meta["events"])
        planner = getattr(self, "planner", None)
        if planner is not None and meta.get("planner") is not None:
            planner.load_state_dict(meta["planner"])
        self._step_fn = None       # recompiled lazily on the next step
        self._costs = None
        return meta
