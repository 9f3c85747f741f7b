"""Subprocess helper: multi-device checks for the ps runtime's re-planning
loop.

Run with 4 forged host devices.  Scenario: every worker's uplink degrades
10 Gbps → 1 Gbps at topology epoch 1 and recovers at epoch 2: the runtime
is built from a configuration with an uplink shift and its topology is
then given the recovery knot as a three-knot ``TopologySchedule``.  Prints one JSON line the parent
asserts on:

1. the consensus re-plan changes the BucketPlan when the uplinks degrade
   and returns to the original plan on recovery;
2. the compiled-step cache serves the revisited plan without re-tracing
   (traces == #distinct plans, cache_hits == #revisits);
3. per distinct plan, compiled-HLO all-gather / reduce-scatter counts
   equal the plan's segment counts (one pull + one push per segment);
4. the dynamic run's losses are bit-identical to statically running each
   epoch's consensus plan with ``PSTrainer.with_plan`` on the same
   batches;
5. every post-warmup boundary's DP fits the *topology's* Δt + gt¹ idle
   window (minimum over workers — Table I).
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import consensus_decision, plan_from_decision
from repro.data.pipeline import SyntheticText
from repro.models import num_sched_layers
from repro.models.profiles import layer_profiles
from repro.optim import adamw
from repro.ps import PSTrainer, TopologySchedule
from repro.runtime import (RuntimeConfig, ScheduleConfig, TopologyConfig,
                           build_runtime)

STEPS_PER_EPOCH, EPOCHS = 3, 3
B, T = 4, 32
FLOPS = 1e10


def main():
    cfg = get_config("granite-3-2b").reduced()
    mesh = Mesh(np.array(jax.devices()).reshape(4,), ("data",))
    pipe = SyntheticText(cfg.vocab_size, T, B, seed=0)
    shape = InputShape("dyn-ps", T, B, "train")
    num_steps = STEPS_PER_EPOCH * EPOCHS

    dyn = build_runtime(RuntimeConfig(
        runtime="ps", batch=B, seq=T, lr=1e-3,
        schedule=ScheduleConfig(
            reschedule_every=STEPS_PER_EPOCH,
            topology=TopologyConfig(servers=2, down_gbps=10.0,
                                    up_gbps=10.0, worker_flops=FLOPS,
                                    up_shift_factor=10.0, shift_epoch=1))),
        model=cfg, data=pipe.batch)
    base, degraded = (dyn.topology.topology_at(e) for e in (0, 1))
    sched = TopologySchedule(knots=((0, base), (1, degraded), (2, base)))
    dyn.topology = sched                # the recovery: a third knot
    losses_dyn = dyn.fit(num_steps)

    plans = []
    for plan in dyn.plans_seen:
        ag, rs = dyn.hlo_counts(plan)
        plans.append({"fwd": len(plan.forward), "bwd": len(plan.backward),
                      "ag": ag, "rs": rs})

    events = [{"step": e.step, "epoch": e.epoch,
               "fwd": len(e.plan.forward), "bwd": len(e.plan.backward),
               "changed": e.plan_changed, "retraced": e.retraced,
               "hidden": e.overhead_hidden,
               "sched_s": e.scheduling_seconds}
              for e in dyn.events]

    # ---- static reference: same plan sequence via PSTrainer.with_plan ----
    profs = layer_profiles(cfg, shape)
    Ls = num_sched_layers(cfg)

    def plan_for(epoch):
        costs = sched.topology_at(epoch).topology_costs(profs)
        decision, _ = consensus_decision(costs, "dynacomm")
        return plan_from_decision(*decision, Ls)

    ref = PSTrainer(cfg=cfg, mesh=mesh, plan=plan_for(0),
                    optimizer=adamw(1e-3), topology=base)
    state_s = ref.init_state(jax.random.PRNGKey(0))
    losses_static = []
    step_fns = {}
    for epoch in range(EPOCHS):
        plan = plan_for(epoch)
        if plan not in step_fns:
            step_fns[plan] = jax.jit(ref.with_plan(plan).build_train_step())
        for i in range(epoch * STEPS_PER_EPOCH,
                       (epoch + 1) * STEPS_PER_EPOCH):
            state_s, loss = step_fns[plan](state_s, pipe.batch(i))
            losses_static.append(float(loss))

    print(json.dumps({
        "losses_dyn": losses_dyn, "losses_static": losses_static,
        "traces": dyn.traces, "cache_hits": dyn.cache_hits,
        "plans": plans, "events": events,
    }))


if __name__ == "__main__":
    main()
