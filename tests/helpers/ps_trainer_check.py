"""Subprocess helper: multi-device checks for the sync PS trainer.

Run with 4 forged host devices.  Prints one JSON line the parent asserts
on:

1. **bit-identity** — sync-mode ``PSTrainer`` losses are bit-identical to
   ``ZeroTrainer`` on the same ``BucketPlan`` (the PS sync path *is* the
   co-located sharded-PS deployment of the ZeRO step);
2. **transfer structure** — per strategy, the compiled HLO carries
   exactly one pull per forward segment and one push per backward
   segment (each the collectives of its leaves, counted by the bucket
   scope in their ``op_name``): total transfers == 2 per (pull, push)
   segment pair;
3. **consensus scheduling** — the heterogeneous topology's consensus plan
   minimizes the synchronous straggler makespan over the per-worker
   candidates.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import (consensus_decision, plan_from_decision,
                        schedule_topology)
from repro.data.pipeline import SyntheticText
from repro.dist.zero import ZeroTrainer
from repro.models import num_sched_layers
from repro.models.profiles import layer_profiles
from repro.optim import adamw
from repro.ps import PSTopology, PSTrainer, asymmetric_link
from repro.analysis import segment_counts

B, T, STEPS = 8, 32, 3


def hlo_counts(step, state, batch):
    """(pull, push) segments compiled: bucket scopes holding collectives."""
    return segment_counts(step.lower(state, batch).compile().as_text())


def main():
    cfg = get_config("granite-3-2b").reduced()
    Ls = num_sched_layers(cfg)
    mesh = Mesh(np.array(jax.devices()).reshape(4,), ("data",))
    pipe = SyntheticText(cfg.vocab_size, T, B, seed=0)
    shape = InputShape("ps-check", T, B, "train")

    # heterogeneous: two fast workers, two slow ones on degraded links
    topo = PSTopology(
        num_servers=2,
        links=(asymmetric_link(10e9, 1e9), asymmetric_link(10e9, 1e9),
               asymmetric_link(2.5e9, 0.25e9), asymmetric_link(2.5e9, 0.25e9)),
        worker_flops=(1e10, 1e10, 2.5e9, 2.5e9))
    topo_costs = topo.topology_costs(layer_profiles(cfg, shape))

    out = {"strategies": {}}
    for strat in ("sequential", "lbl", "ibatch", "dynacomm"):
        decision, makespan = consensus_decision(topo_costs, strat)
        plan = plan_from_decision(*decision, Ls)
        ps = PSTrainer(cfg=cfg, mesh=mesh, plan=plan, optimizer=adamw(1e-3),
                       topology=topo)
        state = ps.init_state(jax.random.PRNGKey(0))
        step = jax.jit(ps.build_train_step())
        pulled, pushed = hlo_counts(step, state, pipe.batch(0))
        losses = []
        for i in range(STEPS):
            state, loss = step(state, pipe.batch(i))
            losses.append(float(loss))

        # the reference: the plain ZeRO trainer on the identical plan
        zt = ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan, optimizer=adamw(1e-3))
        zstate = zt.init_state(jax.random.PRNGKey(0))
        zstep = jax.jit(zt.build_train_step())
        zlosses = []
        for i in range(STEPS):
            zstate, zloss = zstep(zstate, pipe.batch(i))
            zlosses.append(float(zloss))

        pulls, pushes = ps.expected_transfers
        out["strategies"][strat] = {
            "fwd_segments": pulls, "bwd_segments": pushes,
            "pulled": pulled, "pushed": pushed,
            "losses": losses, "zero_losses": zlosses,
            "makespan": makespan,
        }

    # consensus optimality over the per-worker candidate decisions
    candidates = schedule_topology(topo_costs, "dynacomm")
    out["consensus"] = {
        "makespan": out["strategies"]["dynacomm"]["makespan"],
        "candidate_makespans": [topo_costs.makespan(*d) for d in candidates],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
