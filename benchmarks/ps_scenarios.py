"""Parameter-server benchmark scenarios (beyond-paper).

Three benches:

* ``ps_topology`` — DynaComm vs competing strategies in the PS regime:
  the paper's CNN cost tables mapped onto a heterogeneous S×W topology
  (per-worker compute rates, asymmetric per-link bandwidth), comparing
  the synchronous straggler makespan of each strategy's consensus plan
  and the per-worker async plan times — the scenario space the symmetric
  cluster regime (Figs. 5-8) cannot express.
* ``ps_staleness`` — the sync-vs-async trade: simulated time to apply N
  gradient pushes on the smoke CNN as the staleness bound k grows
  (k=0 serializes; larger k reclaims barrier-wait time at the price of
  stale-gradient rejections), under both throttle disciplines — the
  ``wait`` rows show SSP wait-at-barrier keeping every worker
  contributing at small k where ``reject`` starves the slow one.
* ``dynamic_ps_drift`` — the run-time loop's payoff in the PS regime:
  per-epoch uplink degradation over the paper's CNN cost tables,
  comparing each epoch's re-planned consensus makespan against freezing
  the epoch-0 plan (the stale-plan penalty the ``ps`` runtime's
  re-planning loop exists to reclaim), plus the Table I scheduling-overhead-hidden check.
  CI publishes this bench as ``BENCH_dynamic_ps.json``.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.edge_setup import cnn_costs
from repro.core import (Planner, consensus_decision, iteration_time,
                        schedule_topology, simulate_ps_iteration,
                        simulate_ps_replan)
from repro.core.costmodel import TopologyCosts, LayerCosts
from repro.core.scheduler import TopologyScheduler

MODELS = ("vgg19", "googlenet", "inception-v4", "resnet152")
STRATS = ("sequential", "lbl", "ibatch", "dynacomm")


def _hetero_topology_costs(base: LayerCosts, num_workers: int = 4
                           ) -> TopologyCosts:
    """Half the fleet at 1/4 compute behind a 4x-slower asymmetric uplink."""
    workers = []
    for w in range(num_workers):
        slow = w >= num_workers // 2
        comp = 4.0 if slow else 1.0
        comm = 4.0 if slow else 1.0
        c = base.scaled(compute=comp, comm=comm)
        # uplink (push) is 8x the downlink cost for the slow half: gradient
        # pushes dominate, the asymmetric-Δt path is exercised
        workers.append(LayerCosts(pt=c.pt, fc=c.fc, bc=c.bc,
                                  gt=c.gt * 2.0, dt=c.dt,
                                  dt_bwd=c.dt * 1.5))
    return TopologyCosts(workers=tuple(workers))


def ps_topology() -> List[Dict]:
    """Sync makespan + async per-worker times per strategy and model."""
    rows = []
    for model in MODELS:
        topo = _hetero_topology_costs(cnn_costs(model, batch=32))
        seq_makespan = None
        for strat in STRATS:
            decision, makespan = consensus_decision(topo, strat)
            if strat == "sequential":
                seq_makespan = makespan
            tl = simulate_ps_iteration(topo, decision)
            per_worker = schedule_topology(topo, strat)
            async_times = [iteration_time(c, *d)
                           for c, d in zip(topo.workers, per_worker)]
            rows.append({
                "model": model, "strategy": strat,
                "workers": topo.num_workers,
                "fwd_segments": len(decision[0]),
                "bwd_segments": len(decision[1]),
                "sync_makespan_s": round(makespan, 4),
                "straggler": tl.straggler,
                "barrier_wait_mean_s": round(
                    sum(tl.barrier_waits) / tl.num_workers, 4),
                "async_mean_iter_s": round(
                    sum(async_times) / len(async_times), 4),
                "reduced_vs_sequential_pct": round(
                    100 * (1 - makespan / seq_makespan), 2),
            })
    return rows


def ps_staleness() -> List[Dict]:
    """Simulated seconds per accepted push vs the staleness bound k."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import plan_from_decision
    from repro.models.cnn import small_cnn_init, small_cnn_loss
    from repro.optim import sgd
    from repro.ps import AsyncPSTrainer, PSTopology, asymmetric_link

    links = tuple(asymmetric_link(10e9, 1e9) for _ in range(3))
    topo = PSTopology(num_servers=2, links=links,
                      worker_flops=(1e10, 1e10, 5e9))
    params = small_cnn_init(jax.random.PRNGKey(0))
    L = len(params["layers"])
    plan = plan_from_decision(((1, 3), (4, L)), ((4, L), (1, 3)), L)

    def loss_fn(layers, batch):
        return small_cnn_loss({"layers": layers}, batch["images"],
                              batch["labels"])

    def batch_fn(w, i):
        r = np.random.default_rng(100003 * w + i)
        return {"images": jnp.asarray(r.normal(size=(8, 32, 32, 3)),
                                      jnp.float32),
                "labels": jnp.asarray(r.integers(0, 10, size=(8,)),
                                      jnp.int32)}

    rows = []
    pushes = 24
    # ("wait", True) is the BSP aggregation mode: same-version pushes
    # commit as one mean-gradient step.  Under aggregation every worker
    # is admitted in full-fleet cohorts at the head version, so k is
    # inert — one k=0 row, not a fake sweep.
    for throttle, aggregate in (("reject", False), ("wait", False),
                                ("wait", True)):
        for k in ((0,) if aggregate else (0, 1, 2, 4)):
            tr = AsyncPSTrainer(init_layers=params["layers"],
                                loss_fn=loss_fn, optimizer=sgd(0.02),
                                topology=topo, plan=plan, staleness=k,
                                throttle=throttle, aggregate=aggregate)
            log = tr.run(pushes, batch_fn)
            slow_accepted = log.accepted_by_worker().get(2, 0)
            rows.append({
                "throttle": f"{throttle}+agg" if aggregate else throttle,
                "staleness_k": k, "accepted": len(log.accepted),
                "rejected": log.num_rejected,
                "slow_worker_accepted": slow_accepted,
                "max_staleness": log.max_staleness,
                "optimizer_steps": max(e.result.version
                                       for e in log.accepted),
                "barrier_wait_s": round(log.total_wait_s, 4),
                "sim_makespan_s": round(log.makespan, 4),
                "sim_s_per_push": round(log.makespan / pushes, 4),
                "final_loss": round(log.losses[-1], 4),
            })
    return rows


def dynamic_ps_drift() -> List[Dict]:
    """Stale-plan penalty per epoch under uplink degradation.

    Four heterogeneous workers (the ``ps_topology`` fleet); each epoch
    multiplies every worker's gradient-push costs (uplink congestion
    building up 1x → 8x), the consensus plan is re-derived per epoch, and
    ``simulate_ps_replan`` compares it against freezing the epoch-0 plan.
    """
    drift = (1.0, 2.0, 4.0, 8.0)          # uplink slowdown per epoch
    rows = []
    for model in MODELS:
        base = _hetero_topology_costs(cnn_costs(model, batch=32))
        epoch_costs = [
            TopologyCosts(workers=tuple(
                LayerCosts(pt=c.pt, fc=c.fc, bc=c.bc, gt=c.gt * s,
                           dt=c.dt, dt_bwd=c.dt_push)
                for c in base.workers))
            for s in drift]
        sched = TopologyScheduler(strategy="dynacomm", reschedule_every=1,
                                  planner=Planner())
        decisions, hidden, sched_ms = [], [], []
        for costs in epoch_costs:
            # reschedule_every=1: every call re-plans against fresh costs
            decisions.append(sched.decision_for_iteration(costs))
            hidden.append(sched.scheduling_overhead_hidden(costs))
            sched_ms.append(sched.last_scheduling_seconds * 1e3)
        # Second sweep over the same knots — a piecewise-constant
        # ``TopologySchedule`` cycling back to earlier conditions.  With
        # the content-keyed planner every re-plan is a dictionary hit:
        # this is the scheduling-seconds-per-replan "after" column next
        # to the cold "before" above.
        revisit_ms, revisit_decisions = [], []
        for costs in epoch_costs:
            sched.invalidate()
            revisit_decisions.append(sched.decision_for_iteration(costs))
            revisit_ms.append(sched.last_scheduling_seconds * 1e3)
        assert revisit_decisions == decisions   # memoization is exact
        stats = sched.planner.stats
        tl = simulate_ps_replan(epoch_costs, decisions)
        for e, scale in enumerate(drift):
            penalty = tl.stale_plan_penalty(e)
            rows.append({
                "model": model, "epoch": e, "uplink_slowdown": scale,
                "fwd_segments": len(decisions[e][0]),
                "bwd_segments": len(decisions[e][1]),
                "replanned_makespan_s": round(tl.makespans[e], 4),
                "frozen_plan_makespan_s": round(tl.frozen_makespans[e], 4),
                "stale_plan_penalty_s": round(penalty, 4),
                "stale_plan_penalty_pct": round(
                    100 * penalty / tl.frozen_makespans[e], 2),
                "sched_ms": round(sched_ms[e], 3),
                "revisit_sched_ms": round(revisit_ms[e], 3),
                "sched_speedup_on_revisit": round(
                    sched_ms[e] / max(revisit_ms[e], 1e-6), 1),
                "plan_cache_hit_rate": round(stats.hit_rate, 4),
                "overhead_hidden": hidden[e],
            })
    return rows


def runtime_matrix() -> List[Dict]:
    """Every registered runtime, built from its checked-in smoke config
    through ``repro.runtime.build_runtime`` and driven for a few units —
    the registry-as-benchmark view: adding a regime is one config file,
    and this bench (plus CI's smoke step) picks it up with zero wiring."""
    import glob
    import os

    from repro.runtime import RuntimeConfig, build_runtime

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = []
    for path in sorted(glob.glob(
            os.path.join(here, "examples", "runtime_configs", "*.json"))):
        config = RuntimeConfig.load(path)
        rt = build_runtime(config)
        losses = rt.fit(4)
        led = rt.ledger
        rows.append({
            "runtime": config.runtime,
            "regime": config.regime,
            "units": len(losses),
            "first_loss": round(losses[0], 4),
            "final_loss": round(losses[-1], 4),
            "reschedules": len(rt.events),
            "pull_mb": round(led["pull_bytes"] / 1e6, 2),
            "push_mb": round(led["push_bytes"] / 1e6, 2),
        })
    return rows


PS_BENCHES = {
    "ps_topology": ps_topology,
    "ps_staleness": ps_staleness,
    "dynamic_ps_drift": dynamic_ps_drift,
    "runtime_matrix": runtime_matrix,
}
