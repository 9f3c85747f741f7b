"""The int8 push-compression kernels and the four-chip ZeRO step compile
for a TPU v5e.

Compiled by the TPU compiler for a described (not attached) v5e chip: the
kernels at the padded flat length of one granite-3-2b block, with
``interpret=False`` (off the chip ``resolve_interpret(None)`` picks
interpret mode, which would lower no Mosaic kernel at all); the ZeRO step
of the reduced granite-3-2b over the four chips of a v5e:2x2, whose pull
and push move the sharded parameter leaves with no relayout.  Nothing
runs, so these tests say nothing about results or times; they catch the
block shapes and VMEM budgets the chip's compiler refuses, and the loops
and whole-bucket all-reduces a flat wire compiles to.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.compress.compress import (dequantize_unpack_pallas,
                                             quantize_pack_pallas)
from repro.kernels.compress.ops import TILE, aligned
from repro.models.model import sched_layer_bytes

# sched layer 1 of granite-3-2b is its first block (60.8M f32 elements)
N = aligned(sched_layer_bytes(get_config("granite-3-2b"))[1] // 4)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no target"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_quantize_pack_compiles_for_v5e(one_chip):
    seg = jax.ShapeDtypeStruct((1, N), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda s: quantize_pack_pallas(s, (N,), interpret=False)
    ).lower(seg).compile()
    assert "tpu_custom_call" in compiled.as_text()
    payload, scales = compiled.out_info
    assert payload.shape == (N,) and payload.dtype == jnp.int8
    assert scales.shape == (N // TILE,) and scales.dtype == jnp.float32


def test_dequantize_unpack_compiles_for_v5e(one_chip):
    payload = jax.ShapeDtypeStruct((N,), jnp.int8, sharding=one_chip)
    scales = jax.ShapeDtypeStruct((N // TILE,), jnp.float32,
                                  sharding=one_chip)
    compiled = jax.jit(
        lambda p, s: dequantize_unpack_pallas(p, s, (N,), N, interpret=False)
    ).lower(payload, scales).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (1, N)


def _four_chip_step(topo, zero3):
    """The compiled text of the reduced granite-3-2b's ZeRO step over the
    described v5e:2x2, batch 8 x 32, the dynacomm plan of
    ``random_costs(seed=0)``."""
    from repro.core import plan_from_decision, random_costs, schedule
    from repro.dist.zero import ZeroTrainer
    from repro.models import num_sched_layers
    from repro.optim import adamw
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = get_config("granite-3-2b").reduced()
    n = num_sched_layers(cfg)
    plan = plan_from_decision(
        *schedule(random_costs(n, seed=0, dt=1e-3), "dynacomm"), n)
    mesh = Mesh(np.array(topo.devices), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32, sharding=rows)
             for k in ("tokens", "labels")}
    trainer = ZeroTrainer(cfg=cfg, mesh=mesh, plan=plan,
                          optimizer=adamw(1e-3), zero3=zero3)
    assert trainer.layout == "leaves"
    return plan, jax.jit(trainer.build_train_step()).lower(
        trainer.state_structs(), batch).compile().as_text()


def _ops(text):
    """``(opcode, op_name, operand bytes)`` of every instruction of every
    computation in a compiled module."""
    from repro.analysis import parse_hlo
    module = parse_hlo(text)
    return [(i.base_opcode, i.op_name, module.operand_bytes(i))
            for i in module.instructions]


def test_four_chip_step_moves_leaves_without_relayout(topo):
    """No loop and no dynamic-update-slice under a pull or push scope (the
    flat wire's relayout of each weight into and out of its 1-D buffer),
    and no all-reduce of an operand above 1 KiB anywhere: a flat bucket's
    reduce-scatter compiles to an all-reduce of the whole bucket, which
    the compiler makes without metadata, so under no scope.  The largest
    vector of the reduced model, a 256-wide norm scale, is 1 KiB; the
    compiler all-reduces such vectors, combined, in place of scattering
    them."""
    from repro.analysis import parse_hlo, type_bytes
    _, text = _four_chip_step(topo, zero3=False)
    ops = _ops(text)
    wire = [(op, name) for op, name, _ in ops
            if re.search(r"/zero\.(pull|push)\.b\d+/", name)]
    assert any(op.startswith("all-gather") for op, _ in wire)
    relayout = [(op, name) for op, name in wire
                if op in ("while", "dynamic-update-slice")]
    assert not relayout, relayout[:5]
    module = parse_hlo(text)
    big = [(i.name, n) for i in module.collectives()
           if i.base_opcode == "all-reduce"
           for n in (type_bytes(module.by_name[o].result_type)
                     for o in i.operand_names()) if n > 1024]
    assert not big, big[:5]


def test_four_chip_zero3_keeps_its_re_pulls(topo):
    """The TPU compiler keeps each ZeRO-3 re-pull apart from the forward
    pull of the same leaves (the CPU compiler merges them)."""
    plan, text = _four_chip_step(topo, zero3=True)
    n = sum(len(b) for b in plan.forward)                  # sched layers
    middle = [i for i, b in enumerate(plan.backward)
              if any(0 < l < n - 1 for l in b)]
    scopes = {m.group(1) for op, name, _ in _ops(text)
              if op == "all-gather"
              for m in [re.search(r"/zero\.(regather\.b\d+)/", name)] if m}
    assert scopes == {f"regather.b{i}" for i in middle}
