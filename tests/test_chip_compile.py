"""The int8 push-compression kernels compile for a TPU v5e.

Compiled by the TPU compiler for a described (not attached) v5e chip, at
the padded flat length of one granite-3-2b block, with
``interpret=False``: off the chip ``resolve_interpret(None)`` picks
interpret mode, which would lower no Mosaic kernel at all.  Nothing runs,
so these tests say nothing about results or times; they catch the block
shapes and VMEM budgets the chip's compiler refuses.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
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
