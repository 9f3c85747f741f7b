"""Subprocess helper: a looped stack (Ouro) on the ZeRO trainer over four
devices, where the state is the flat wire (``flat`` layout).  Prints one
JSON line the parent asserts on:

1. one step of the tiny looped model (T = 2) gives the loss and updated
   parameters of ``jax.value_and_grad`` of ``train_loss`` on the global
   batch (SGD, so the update is the gradient);
2. the compiled step has one all-gather per forward bucket and one
   reduce-scatter per backward bucket: the T applications of a block
   share its pull, and its T gradients one push;
3. the lowered programs of a stack run once (granite-3-2b reduced, ZeRO-2
   and ZeRO-3) by their SHA-256.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.analysis import collective_counts
from repro.configs import get_config
from repro.core import plan_from_decision, random_costs, schedule
from repro.dist.zero import ZeroTrainer
from repro.models import num_sched_layers, train_loss
from repro.optim import adamw, sgd


def tiny_ouro(loops=2):
    return dataclasses.replace(
        get_config("ouro-2.6b").reduced(d_model=64, vocab=256),
        loop_steps=loops)


def plan_for(cfg):
    Ls = num_sched_layers(cfg)
    return plan_from_decision(
        *schedule(random_costs(Ls, seed=0, dt=1e-3), "dynacomm"), Ls)


def main():
    mesh = Mesh(np.array(jax.devices()).reshape(4,), ("data",))
    out = {}

    cfg = tiny_ouro()
    plan = plan_for(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    tr = ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan, optimizer=sgd(0.5),
                     aux_weight=0.0)
    state = tr.init_state(jax.random.PRNGKey(0))
    params = tr.params_from_state(state)
    step = jax.jit(tr.build_train_step())
    counts = collective_counts(step.lower(state, batch).compile().as_text())
    state, loss = step(state, batch)
    ref_loss, grads = jax.jit(jax.value_and_grad(
        lambda p: train_loss(cfg, p, batch, aux_weight=0.0)))(params)
    want = jax.tree_util.tree_map(lambda p, g: p - 0.5 * g, params, grads)
    got = tr.params_from_state(state)
    out["looped"] = {
        "layout": tr.layout, "loss": float(loss), "ref_loss": float(ref_loss),
        "param_gap": max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))),
        "fwd_buckets": len(plan.forward), "ag": counts["all-gather"],
        "bwd_buckets": len(plan.backward), "rs": counts["reduce-scatter"]}

    dense = get_config("granite-3-2b").reduced()
    shapes = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
              "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    out["one_pass_sha256"] = {}
    for zero3 in (False, True):
        trd = ZeroTrainer(cfg=dense, mesh=mesh, plan=plan_for(dense),
                          optimizer=adamw(1e-3), zero3=zero3)
        text = jax.jit(trd.build_train_step()).lower(
            trd.state_structs(), shapes).as_text()
        out["one_pass_sha256"][f"zero3={zero3}"] = \
            hashlib.sha256(text.encode()).hexdigest()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
