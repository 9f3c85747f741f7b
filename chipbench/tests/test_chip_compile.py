"""A four-chip dense step, compiled here for a described v5e:2x2.

What the chip's compiler refuses, it refuses here at no chip time: the
ZeRO ``dynacomm`` step of ``granite-3-2b.l4`` under the
``zipf-32x512-dp4`` traffic on four chips (global batch 32 x 512, the
plan its runtime draws), given the described devices as its mesh and
shapes as its state.  This is the step of the cell
``granite-3-2b.l4.zero.4chip``.  Compiling takes one to two minutes.
"""

import json
import os

import numpy as np
import pytest

from chipbench import data, hlo, run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_four_chip_step_compiles_for_v5e(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs.base import InputShape
    from repro.core import (DynaCommScheduler, costs_from_profiles,
                            plan_from_decision)
    from repro.dist.zero import ZeroTrainer
    from repro.models import num_sched_layers
    from repro.models.profiles import layer_profiles
    from repro.runtime.config import NetworkConfig, RuntimeConfig

    with open(os.path.join(BENCH, "configs", "granite-3-2b.l4.json")) as f:
        arch = run.arch_config(json.load(f))
    with open(os.path.join(BENCH, "traffic", "zipf-32x512-dp4.json")) as f:
        traffic = json.load(f)
    batch = data.global_batch(traffic, CHIPS)
    seq = int(traffic["seq"])
    # the plan ZeroRuntime draws for these sizes
    config = RuntimeConfig(runtime="zero", reduced=False, batch=batch,
                           seq=seq)
    costs = costs_from_profiles(
        layer_profiles(arch, InputShape("cell", seq, batch, "train")),
        net=NetworkConfig().build(),
        compute_flops_per_s=config.measure.compute_flops_per_s)
    plan = plan_from_decision(
        *DynaCommScheduler(strategy="dynacomm")
        .decision_for_iteration(costs), num_sched_layers(arch))

    jax.config.update("jax_enable_compilation_cache", False)
    mesh = Mesh(np.array(topo.devices), ("data",))
    trainer = ZeroTrainer(cfg=arch, mesh=mesh, plan=plan,
                          optimizer=config.build_optimizer(), aux_weight=0.0)
    shapes = jax.eval_shape(trainer._make_state, jax.random.PRNGKey(0))
    layout = trainer._state_layout(
        shapes, NamedSharding(mesh, P("data")), NamedSharding(mesh, P()),
        NamedSharding(mesh, P("data", None)))
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, layout)
    rows = NamedSharding(mesh, P("data", None))
    batch_shapes = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                            sharding=rows)
                    for k in ("tokens", "labels")}
    compiled = jax.jit(trainer.build_train_step()).lower(
        state, batch_shapes).compile()

    memory = compiled.memory_analysis()
    per_chip = (memory.argument_size_in_bytes + memory.output_size_in_bytes
                + memory.temp_size_in_bytes)
    assert per_chip < 16e9
    counts = hlo.pulls_and_pushes(compiled.as_text())
    # every planned pull and push is there (XLA may gather again)
    assert counts["pulls"] >= len(plan.forward)
    assert counts["pushes"] == len(plan.backward)
    print(json.dumps({"plan": [plan.forward, plan.backward],
                      "bytes_per_chip": per_chip, **counts}))
