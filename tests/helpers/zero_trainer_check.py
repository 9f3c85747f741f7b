"""Subprocess helper: multi-device checks for the DynaComm ZeRO trainer.

Run with 4 forged host devices (XLA_FLAGS set by the parent test).  Prints
one JSON line the parent asserts on.  Checks:

1. collective structure, per strategy, by the bucket scope in each
   collective's ``op_name``: under ``zero.pull.b{i}`` one all-gather per
   leaf of forward bucket ``i`` that the state splits, under
   ``zero.push.b{i}`` one reduce-scatter per such leaf of backward bucket
   ``i`` and one all-reduce per leaf held whole, nothing else but the
   loss mean; with a compressor (the flat wire) one all-gather and one
   reduce-scatter a bucket;
2. "accuracy untouched" (paper Fig. 10, strengthened): losses are
   bit-identical across sequential / LBL / iBatch / DynaComm schedules;
3. ZeRO trainer vs single-device reference: same losses to fp32 roundoff;
4. the state layout follows the compressor (``flat`` with one, ``leaves``
   without, on any number of devices), and a one-device trainer on the
   same global batch gives the four-device losses to fp32 roundoff;
5. the four-device state, copied to the host, reads back as the
   initialisation (``params_from_state``), for each reduced config;
6. a planted fault, a push that exchanges nothing (``zero.
   reduce_scatter_bucket`` replaced by each device's own slice of its own
   gradient), leaves the one-device parameters far behind, where the
   real push follows them to fp32 roundoff.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.analysis import collective_counts, parse_hlo
from repro.compress import make_compressor
from repro.configs import get_config
from repro.core import plan_from_decision, random_costs, schedule
from repro.dist import zero
from repro.dist.zero import ZeroTrainer
from repro.models import init_params, num_sched_layers, train_loss
from repro.optim import adamw
from repro.runtime.replan import sequential_plan


AXIS = 4


def by_scope(hlo):
    """``{"pull.b0": {"all-gather": n}, ..., "other": {...}}``: the
    step's collectives by the bucket scope in their ``op_name``.  The
    CPU compiler may combine the loss mean (a scalar) into a push's
    all-reduce: an all-reduce counts each non-scalar operand in its
    scope, and each scalar one under ``other``."""
    module = parse_hlo(hlo)
    out = {}

    def add(scope, kind):
        kinds = out.setdefault(scope, {})
        kinds[kind] = kinds.get(kind, 0) + 1

    for instr in module.collectives():
        m = re.search(r"zero\.(pull|regather|push)\.(b\d+)", instr.op_name)
        scope = ".".join(m.groups()) if m else "other"
        if instr.base_opcode != "all-reduce":
            add(scope, instr.base_opcode)
            continue
        for name in instr.operand_names():
            scalar = re.match(r"\w+\[\]", module.by_name[name].result_type)
            add("other" if scalar else scope, "all-reduce")
    return out


def expected_scopes(tr, plan, zero3=False, axis=AXIS):
    """What ``by_scope`` must read for a leaves state: a leaf is split
    where some dimension is a multiple of the axis size, and held whole
    otherwise."""
    def leaves(bucket, split):
        return sum(1 for l in bucket for shape in tr.specs[l].shapes
                   if any(n % axis == 0 for n in shape) == split)

    out = {f"pull.b{i}": {"all-gather": leaves(b, True)}
           for i, b in enumerate(plan.forward)}
    for i, b in enumerate(plan.backward):
        kinds = {"reduce-scatter": leaves(b, True),
                 "all-reduce": leaves(b, False)}
        out[f"push.b{i}"] = {k: n for k, n in kinds.items() if n}
        if zero3 and any(0 < l < tr.num_layers - 1 for l in b):
            out[f"regather.b{i}"] = {"all-gather": leaves(b, True)}
    out["other"] = {"all-reduce": 1}                     # the loss mean
    return out


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
        losses = []
        for _ in range(3):
            state, loss = step(state, batch)
            losses.append(float(loss))
        if strat == "dynacomm":
            tr4, state4 = tr, state
        out["strategies"][strat] = {
            "fwd_buckets": len(plan.forward),
            "bwd_buckets": len(plan.backward),
            "scopes": by_scope(hlo),
            "expected_scopes": expected_scopes(tr, plan),
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
    # the step as handed to XLA: the CPU compiler drops the barrier and
    # merges each re-pull with its forward twin (the TPU compiler keeps
    # them apart, tests/test_chip_compile.py)
    hlo3 = step3.lower(state3, batch).as_text(dialect="hlo", debug_info=True)
    losses3 = []
    for _ in range(3):
        state3, loss = step3(state3, batch)
        losses3.append(float(loss))
    out["zero3"] = {
        "losses": losses3,
        "scopes": by_scope(hlo3),
        "expected_scopes": expected_scopes(tr3, plan, zero3=True),
    }

    # the compressor's flat wire: one collective per bucket
    tr8 = ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan, optimizer=adamw(1e-3),
                      compressor=make_compressor("int8"))
    counts8 = collective_counts(jax.jit(tr8.build_train_step()).lower(
        tr8.state_structs(), batch).compile().as_text())
    out["int8"] = {"layout": tr8.layout, "ag": counts8["all-gather"],
                   "rs": counts8["reduce-scatter"],
                   "fwd_buckets": len(plan.forward),
                   "bwd_buckets": len(plan.backward)}

    # the layout follows the compressor; one device follows the
    # four-device trajectory
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

    # the planted fault: a push that exchanges nothing.  Each device keeps
    # its own slice of its own gradient (times the axis size, which the
    # layout divides out), so only the missing exchange differs
    def local_push(grads, specs, bucket, axis_name):
        me = jax.lax.axis_index(axis_name)
        out = {}
        for l in bucket:
            axis = specs[l].axis_size
            out[l] = [axis * (g if d is None else jax.lax.dynamic_slice_in_dim(
                g, me * (g.shape[d] // axis), g.shape[d] // axis, axis=d))
                for g, d in zip(jax.tree_util.tree_leaves(grads[l]),
                                specs[l].dims)]
        return out

    real_push = zero.reduce_scatter_bucket
    zero.reduce_scatter_bucket = local_push
    try:
        trf = ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan,
                          optimizer=adamw(1e-3))
        statef = trf.init_state(jax.random.PRNGKey(0))
        stepf = jax.jit(trf.build_train_step())
        for _ in range(3):
            statef, _ = stepf(statef, batch)
    finally:
        zero.reduce_scatter_bucket = real_push

    def gap(tr_a, state_a):
        """Largest parameter difference from the one-device run."""
        a = tr_a.params_from_state(jax.device_get(state_a))
        b = tr1.params_from_state(jax.device_get(state1))
        return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    out["no_exchange"] = {"four_device_gap": gap(tr4, state4),
                          "fault_gap": gap(trf, statef)}

    # the four-device state read back to the host is the initialisation
    out["round_trip"] = {}
    for arch in ("granite-3-2b", "granite-moe-1b-a400m", "ouro-2.6b"):
        small = get_config(arch).reduced()
        trs = ZeroTrainer(cfg=small, mesh=mesh, optimizer=adamw(1e-3),
                          plan=sequential_plan(num_sched_layers(small)))
        key = jax.random.PRNGKey(7)
        got = trs.params_from_state(jax.device_get(trs.init_state(key)))
        want = jax.jit(lambda k: init_params(small, k))(key)
        out["round_trip"][arch] = (
            jax.tree_util.tree_structure(got) ==
            jax.tree_util.tree_structure(want) and
            all(a.dtype == b.dtype and np.array_equal(a, b) for a, b in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want))))

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
