"""Subprocess helper: multi-device checks for the zero runtime's re-planning
loop.

Run with 4 forged host devices.  Scenario: a 10 Gbps → 1 Gbps → 10 Gbps
bandwidth drift over three epochs, analytic cost source (deterministic):
the runtime is built from a configuration with a bandwidth shift and its
network is then given the recovery knot as a three-knot
``NetworkSchedule``.
Prints one JSON line the parent asserts on:

1. the DP re-plans to a *different* BucketPlan when the bandwidth drops,
   and back to the original plan when it recovers;
2. the compiled-step cache serves the revisited plan without re-tracing
   (traces == #distinct plans, cache_hits == #revisits);
3. per distinct plan, compiled-HLO all-gather / reduce-scatter counts
   equal the plan's bucket counts;
4. the dynamic run's losses are bit-identical to statically running each
   epoch's plan with ``ZeroTrainer.with_plan`` on the same batches;
5. every epoch boundary records a RescheduleEvent whose scheduling time
   fits the Δt + gt¹ idle window (Table I "overhead hidden").
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import (EdgeNetworkModel, NetworkSchedule, costs_from_profiles,
                        plan_from_decision, schedule)
from repro.data.pipeline import SyntheticText
from repro.dist.zero import ZeroTrainer
from repro.models import num_sched_layers
from repro.models.profiles import layer_profiles
from repro.optim import adamw
from repro.runtime import (MeasureConfig, NetworkConfig, RuntimeConfig,
                           ScheduleConfig, build_runtime)

BW_HIGH, BW_LOW = 10e9, 1e9
FLOPS = 1e10                 # edge-worker compute rate fed to the profiler
STEPS_PER_EPOCH, EPOCHS = 3, 3
B, T = 8, 32


def main():
    cfg = get_config("granite-3-2b").reduced()
    mesh = Mesh(np.array(jax.devices()).reshape(4,), ("data",))
    pipe = SyntheticText(cfg.vocab_size, T, B, seed=0)
    net = NetworkSchedule(knots=(
        (0, EdgeNetworkModel(bandwidth_bps=BW_HIGH)),
        (1, EdgeNetworkModel(bandwidth_bps=BW_LOW)),
        (2, EdgeNetworkModel(bandwidth_bps=BW_HIGH)),
    ))
    num_steps = STEPS_PER_EPOCH * EPOCHS

    dyn = build_runtime(RuntimeConfig(
        runtime="zero", batch=B, seq=T, lr=1e-3,
        schedule=ScheduleConfig(
            reschedule_every=STEPS_PER_EPOCH,
            network=NetworkConfig(bandwidth_gbps=BW_HIGH / 1e9,
                                  shift_gbps=BW_LOW / 1e9, shift_epoch=1)),
        measure=MeasureConfig(compute_flops_per_s=FLOPS)),
        model=cfg, data=pipe.batch)
    dyn.network = net                   # the recovery: a third knot
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

    # ---- static reference: same plan sequence, one ZeroTrainer per epoch --
    shape = InputShape("dynamic", T, B, "train")
    profs = layer_profiles(cfg, shape)
    Ls = num_sched_layers(cfg)

    def plan_for(epoch):
        costs = costs_from_profiles(profs, net=net.model_at(epoch),
                                    compute_flops_per_s=FLOPS)
        return plan_from_decision(*schedule(costs, "dynacomm"), Ls)

    base = ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan_for(0),
                       optimizer=adamw(1e-3))
    state_s = base.init_state(jax.random.PRNGKey(0))
    losses_static = []
    step_fns = {}
    for epoch in range(EPOCHS):
        plan = plan_for(epoch)
        if plan not in step_fns:
            step_fns[plan] = jax.jit(base.with_plan(plan).build_train_step())
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
