"""Drive a built runtime through the conformance passes.

``verify_runtime(config)`` is the engine behind
``python -m repro.analysis verify``: it builds the runtime via
:func:`repro.runtime.build_runtime`, lowers/compiles one training step
(or, for the dynamic regimes, runs just past a re-plan boundary so the
``PlanStepCache`` holds real compiled steps), and checks

* the compiled HLO against the active ``BucketPlan`` and the state
  layout's byte math (:func:`~repro.analysis.conformance.verify_schedule`);
* the compiled-step cache: one compilation per distinct plan
  (:func:`~repro.analysis.conformance.verify_cache`);
* the compressed wire-byte accounting, exact to the integer
  (:func:`~repro.analysis.conformance.verify_wire_model`, and for the
  event-loop regimes the per-worker ledger decomposition of
  :func:`~repro.analysis.conformance.verify_push_ledger`);
* that modules with no scheduled communication (the local step, the
  async trainers' single-jit gradient) compile zero cross-replica
  collectives.

This module imports jax (via ``repro.runtime``); the CLI imports it
lazily so ``lint`` stays jax-free.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from repro.analysis.conformance import (segment_wire_bytes, verify_cache,
                                        verify_fleet_membership,
                                        verify_no_collectives,
                                        verify_push_ledger, verify_schedule,
                                        verify_wire_model)
from repro.analysis.findings import Finding

__all__ = ["verify_runtime"]


def verify_runtime(config: Any, *, steps: Optional[int] = None
                   ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Verify one ``RuntimeConfig``; returns ``(findings, info)``.

    ``steps`` overrides how many units of progress to run where running
    is needed (dynamic regimes default to one step past the first
    re-plan boundary; async regimes to a couple of committed pushes).
    """
    from repro.runtime import build_runtime
    rt = build_runtime(config)
    regime = config.runtime
    if regime == "local":
        return _verify_local(rt)
    if regime in ("zero", "ps"):
        return _verify_static(rt, config, steps)
    if regime in ("dynamic", "dynamic-ps"):
        return _verify_dynamic(rt, config, steps)
    if regime in ("ps-async", "dynamic-ps-async"):
        return _verify_async(rt, config, regime, steps)
    if regime == "fleet-async":
        return _verify_fleet(rt, config, steps)
    if regime == "pipeline":
        return _verify_pipeline(rt, config, steps)
    raise ValueError(f"no conformance driver for runtime {regime!r}")


def _info(regime: str, **extra: Any) -> Dict[str, Any]:
    return {"runtime": regime, **extra}


def _plan_obj(plan: Any) -> Dict[str, Any]:
    return {"forward": [list(b) for b in plan.forward],
            "backward": [list(b) for b in plan.backward]}


def _verify_local(rt: Any) -> Tuple[List[Finding], Dict[str, Any]]:
    batch = rt._batch_fn(0)
    hlo = rt._step_fn.lower(rt._params, rt._opt_state,
                            batch).compile().as_text()
    findings = verify_no_collectives(hlo, context="local step")
    return findings, _info("local", checked=["no-collectives"])


def _verify_static(rt: Any, config: Any, steps: Optional[int]
                   ) -> Tuple[List[Finding], Dict[str, Any]]:
    tr = rt.trainer
    batch = rt._batch_fn(0)
    hlo = rt.compiled_step_text(batch)
    compressor = getattr(tr, "compressor", None)
    zero3 = config.execution.zero3
    findings = verify_schedule(hlo, rt.plan, tr.specs,
                               compressor=compressor, zero3=zero3,
                               context=f"{config.runtime} step")
    # ledger audit over a short run: the adapter's fleet-wide push wire
    # accounting must equal steps x workers x the independent per-segment
    # byte model
    n = steps if steps is not None else 1
    rt.fit(n)
    workers = tr.topology.num_workers if hasattr(tr, "topology") \
        else tr.axis_size
    expected_wire = n * workers * sum(
        segment_wire_bytes(tr.specs, b, compressor)
        for b in rt.plan.backward)
    recorded = rt.ledger["push_wire_bytes"]
    if recorded != expected_wire:
        findings.append(Finding(
            code="SCHED-LEDGER",
            message=f"runtime ledger records {recorded} push wire bytes "
                    f"over {n} step(s) x {workers} worker(s); the "
                    f"independent byte model gives {expected_wire}",
            detail={"recorded": recorded, "expected": expected_wire,
                    "steps": n, "workers": workers}))
    return findings, _info(
        config.runtime, plan=_plan_obj(rt.plan), steps_run=n,
        compression=getattr(compressor, "scheme", "none")
        if compressor else "none",
        checked=["schedule", "wire-model", "ledger"])


def _verify_dynamic(rt: Any, config: Any, steps: Optional[int]
                    ) -> Tuple[List[Finding], Dict[str, Any]]:
    # run one step past the first re-plan boundary so the cache holds at
    # least one (usually two) genuinely compiled plans
    n = steps if steps is not None else config.schedule.reschedule_every + 1
    rt.fit(n)
    tr = rt.trainer
    base = tr.base
    compressor = getattr(tr, "compressor", None)
    zero3 = config.execution.zero3
    findings = verify_cache(tr._cache, specs=base.specs, zero3=zero3,
                            context=f"{config.runtime} cache")
    for i, plan in enumerate(tr.plans_seen):
        # verify_schedule handles axis_size == 1 itself (XLA elides the
        # collectives; only stray + wire-model checks run)
        findings.extend(verify_schedule(
            tr._cache.hlo_text(plan), plan, base.specs,
            compressor=compressor, zero3=zero3,
            context=f"{config.runtime} plan {i}"))
    return findings, _info(
        config.runtime, steps_run=n, plans_seen=len(tr.plans_seen),
        traces=tr.traces, cache_hits=tr.cache_hits,
        compression=getattr(compressor, "scheme", "none")
        if compressor else "none",
        checked=["schedule", "cache", "wire-model"])


def _verify_async(rt: Any, config: Any, regime: str, steps: Optional[int]
                  ) -> Tuple[List[Finding], Dict[str, Any]]:
    async_tr = rt.trainer if regime == "ps-async" else rt.trainer.trainer
    # stay inside the first plan epoch so the per-worker ledger
    # decomposition runs against a single plan sequence per worker
    n = steps if steps is not None else 2
    if regime == "dynamic-ps-async":
        n = min(n, config.schedule.reschedule_every)
    rt.fit(n)

    # the async regimes communicate through explicit server messages;
    # their single-jit gradient must compile zero collectives
    batch = rt._batch_fn(0)
    hlo = async_tr._grad_fn.lower(async_tr.layer_params(),
                                  batch).compile().as_text()
    findings = verify_no_collectives(hlo, context=f"{regime} grad")

    specs = async_tr.specs
    compressor = async_tr.compressor
    plans = async_tr.plans
    if compressor is not None:
        for plan in dict.fromkeys(plans):
            findings.extend(verify_wire_model(specs, plan, compressor,
                                              context=f"{regime} plan"))
    findings.extend(verify_push_ledger(
        async_tr.server.ledger, dict(enumerate(plans)), specs, compressor,
        context=f"{regime} ledger"))
    return findings, _info(
        regime, pushes_run=n, workers=len(plans),
        compression=getattr(compressor, "scheme", "none")
        if compressor else "none",
        checked=["no-collectives", "wire-model", "push-ledger"])


def _verify_pipeline(rt: Any, config: Any, steps: Optional[int]
                     ) -> Tuple[List[Finding], Dict[str, Any]]:
    tr = rt.trainer
    n = steps if steps is not None else 1
    rt.fit(n)

    # each per-stage program must be collective-free: inter-stage bytes
    # move only through the explicit boundary buffers the ledger accounts
    findings: List[Finding] = []
    batch = rt._batch_fn(0)
    for s, (fwd_hlo, bwd_hlo) in enumerate(tr.stage_hlo(batch)):
        findings.extend(verify_no_collectives(
            fwd_hlo, context=f"pipeline stage {s} forward"))
        findings.extend(verify_no_collectives(
            bwd_hlo, context=f"pipeline stage {s} backward"))

    # ledger audit: boundary bytes must equal the independent byte model
    # (per step: M activation flats down + M grad flats up per boundary,
    # plus the tied-embedding flat to/from the head stage)
    S, M = tr.num_stages, tr.num_microbatches
    act = tr.activation_bytes()
    embed_bytes = tr.specs[0].total * 4 if S > 1 else 0
    expected_pull = n * (M * sum(act) + embed_bytes)
    expected_push = n * (M * sum(act) + M * embed_bytes)
    led = rt.ledger
    for direction, expected in (("pull", expected_pull),
                                ("push", expected_push)):
        recorded = led[f"{direction}_bytes"]
        if recorded != expected:
            findings.append(Finding(
                code="PIPE-LEDGER",
                message=f"pipeline ledger records {recorded} {direction} "
                        f"bytes over {n} step(s); the boundary byte model "
                        f"gives {expected}",
                detail={"recorded": recorded, "expected": expected,
                        "steps": n, "stages": S, "microbatches": M}))

    # partition sanity + transfer-plan optimality vs the whole-tensor
    # baseline (the DP can never lose to a feasible decision)
    part = tr.partition
    if abs(max(part.loads) - part.bottleneck) > 1e-9 * max(part.bottleneck,
                                                           1.0):
        findings.append(Finding(
            code="PIPE-PARTITION",
            message=f"partition bottleneck {part.bottleneck} is not the "
                    f"max stage load {max(part.loads)}",
            detail=part.as_dict()))
    plans = tr.transfer_plans() or []
    for p in plans:
        if p.fwd_time > p.whole_fwd_time + 1e-12 or \
                p.bwd_time > p.whole_bwd_time + 1e-12:
            findings.append(Finding(
                code="PIPE-TRANSFER",
                message=f"boundary {p.boundary}: segmented transfer "
                        f"({p.fwd_time + p.bwd_time:.6f}s) loses to the "
                        f"whole-tensor baseline "
                        f"({p.whole_fwd_time + p.whole_bwd_time:.6f}s)",
                detail={"boundary": p.boundary,
                        "segmented": p.fwd_time + p.bwd_time,
                        "whole": p.whole_fwd_time + p.whole_bwd_time}))
    timeline = tr.timeline()
    return findings, _info(
        "pipeline", steps_run=n, stages=S, microbatches=M,
        schedule=tr.schedule_name, partition=part.as_dict(),
        boundary_speedups=[p.speedup for p in plans],
        bubble_fraction=(timeline.bubble_fraction
                         if timeline is not None else None),
        checked=["no-collectives", "ledger", "partition", "transfer-plans"])


def _verify_fleet(rt: Any, config: Any, steps: Optional[int]
                  ) -> Tuple[List[Finding], Dict[str, Any]]:
    tr = rt.trainer
    # run far enough to fire the scripted membership events (the ledger
    # and membership audits are only interesting once churn happened)
    n = steps if steps is not None else 4
    rt.fit(n)

    batch = rt._batch_fn(0)
    hlo = tr._grad_fn.lower(tr.layer_params(),
                            batch).compile().as_text()
    findings = verify_no_collectives(hlo, context="fleet-async grad")

    specs = tr.specs
    compressor = tr.compressor
    history = tr.push_history
    if compressor is not None:
        distinct = dict.fromkeys(p for entries in history.values()
                                 for p, _, _ in entries)
        for plan in distinct:
            findings.extend(verify_wire_model(specs, plan, compressor,
                                              context="fleet-async plan"))
    # the elastic form: each worker's ledger entry decomposes under its
    # own plan *history* (departed workers' entries close cleanly)
    findings.extend(verify_push_ledger(
        tr.server.ledger, history, specs, compressor,
        context="fleet-async ledger"))
    findings.extend(verify_fleet_membership(
        tr.log, tr.membership.joined_at, tr.membership.departed,
        staleness_bound=tr.staleness, context="fleet-async membership"))
    return findings, _info(
        "fleet-async", pushes_run=n, workers=tr.membership.num_active,
        replans=len(tr.replan_events),
        membership_events=len(tr.membership_events),
        compression=getattr(compressor, "scheme", "none")
        if compressor else "none",
        checked=["no-collectives", "wire-model", "push-ledger",
                 "fleet-membership"])
