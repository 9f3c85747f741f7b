"""Subprocess helper: multi-device checks for the DynaComm ZeRO trainer.

Run with 4 forged host devices (XLA_FLAGS set by the parent test).  Prints
one JSON line the parent asserts on.  Checks:

1. collective structure — #all-gathers == |D_f| buckets and
   #reduce-scatters == |D_b| buckets in the compiled HLO, per strategy;
2. "accuracy untouched" (paper Fig. 10, strengthened): losses are
   bit-identical across sequential / LBL / iBatch / DynaComm schedules;
3. ZeRO trainer vs single-device reference: same losses to fp32 roundoff;
4. the state layout follows the mesh and the compressor (``flat`` on four
   devices or with a compressor, ``leaves`` on one device without), and a
   one-device trainer on the same global batch gives the four-device
   losses to fp32 roundoff.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.analysis import collective_counts
from repro.compress import make_compressor
from repro.configs import get_config
from repro.core import plan_from_decision, random_costs, schedule
from repro.dist.zero import ZeroTrainer
from repro.models import init_params, num_sched_layers, train_loss
from repro.optim import adamw


def main():
    cfg = get_config("granite-3-2b").reduced()
    Ls = num_sched_layers(cfg)
    mesh = Mesh(np.array(jax.devices()).reshape(4,), ("data",))
    B, T = 8, 32
    key = jax.random.PRNGKey(3)
    toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

    out = {"strategies": {}}
    costs = random_costs(Ls, seed=0, dt=1e-3)
    for strat in ("sequential", "lbl", "ibatch", "dynacomm"):
        f, b = schedule(costs, strat)
        plan = plan_from_decision(f, b, Ls)
        tr = ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan, optimizer=adamw(1e-3))
        state = tr.init_state(jax.random.PRNGKey(0))
        step = jax.jit(tr.build_train_step())
        hlo = step.lower(state, batch).compile().as_text()
        counts = collective_counts(hlo)
        n_ag, n_rs = counts["all-gather"], counts["reduce-scatter"]
        losses = []
        for _ in range(3):
            state, loss = step(state, batch)
            losses.append(float(loss))
        out["strategies"][strat] = {
            "fwd_buckets": len(plan.forward), "ag": n_ag,
            "bwd_buckets": len(plan.backward), "rs": n_rs,
            "losses": losses,
        }

    # ZeRO-3 re-gather mode: one extra pull per mid-layer backward bucket,
    # bit-identical losses
    f, b = schedule(costs, "dynacomm")
    plan = plan_from_decision(f, b, Ls)
    tr3 = ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan, optimizer=adamw(1e-3),
                      zero3=True)
    state3 = tr3.init_state(jax.random.PRNGKey(0))
    step3 = jax.jit(tr3.build_train_step())
    hlo3 = step3.lower(state3, batch).compile().as_text()
    losses3 = []
    for _ in range(3):
        state3, loss = step3(state3, batch)
        losses3.append(float(loss))
    mid_buckets = sum(1 for bk in plan.backward
                      if any(0 < l < Ls - 1 for l in bk))
    out["zero3"] = {
        "losses": losses3,
        "ag": collective_counts(hlo3)["all-gather"],
        "expected_ag": len(plan.forward) + mid_buckets,
    }

    # the layout follows the mesh and the compressor; one device (leaves)
    # follows the four-device (flat) trajectory
    one = Mesh(np.array(jax.devices()[:1]), ("data",))
    tr1 = ZeroTrainer(cfg=cfg, mesh=one, plan=plan, optimizer=adamw(1e-3))
    state1 = tr1.init_state(jax.random.PRNGKey(0))
    step1 = jax.jit(tr1.build_train_step())
    losses1 = []
    for _ in range(3):
        state1, loss = step1(state1, batch)
        losses1.append(float(loss))
    out["layouts"] = {
        "4dev": tr3.layout, "1dev": tr1.layout,
        "1dev_int8": ZeroTrainer(cfg=cfg, mesh=one, plan=plan,
                                 optimizer=adamw(1e-3),
                                 compressor=make_compressor("int8")).layout}
    out["one_device_losses"] = losses1

    # single-device reference
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw(1e-3)
    ostate = opt.init(params)

    @jax.jit
    def ref_step(params, ostate, batch):
        loss, grads = jax.value_and_grad(
            lambda p: train_loss(cfg, p, batch, aux_weight=0.01))(params)
        params, ostate = opt.update(grads, ostate, params)
        return params, ostate, loss

    ref_losses = []
    for _ in range(3):
        params, ostate, loss = ref_step(params, ostate, batch)
        ref_losses.append(float(loss))
    out["reference_losses"] = ref_losses
    print(json.dumps(out))


if __name__ == "__main__":
    main()
