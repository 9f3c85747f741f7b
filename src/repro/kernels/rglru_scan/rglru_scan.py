"""Pallas TPU kernel: blocked RG-LRU linear recurrence.

``h_t = a_t * h_{t-1} + x_t`` is sequential in t but embarrassingly parallel
over channels — the natural TPU mapping is channels on the 128-lane axis and
time streamed through VMEM in blocks:

* grid ``(B, W/bw, T/bt)`` with the time axis innermost and sequential
  ("arbitrary"); the carry h lives in a VMEM scratch vector per (batch,
  channel-tile) program family;
* each step loads an (bt, bw) tile of a and x, runs the recurrence over the
  tile's bt rows with an in-kernel ``fori_loop`` (each row is a (bw,)
  lane-vector op on the VPU), and writes the (bt, bw) tile of h.

This is the kernel backing recurrentgemma's recurrent blocks; the pure-XLA
fallback is ``jax.lax.associative_scan`` (ref.py / models.ssm).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro._compat.pallas import resolve_interpret

DEFAULT_BT = 128
DEFAULT_BW = 128


def _rglru_kernel(a_ref, x_ref, h_ref, carry_ref, *, bt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[...].astype(jnp.float32)     # (bt, bw)
    x = x_ref[...].astype(jnp.float32)

    def row(t, h):
        h = a[t] * h + x[t]
        h_ref[t, :] = h.astype(h_ref.dtype)
        return h

    h_last = jax.lax.fori_loop(0, bt, row, carry_ref[...])
    carry_ref[...] = h_last


def rglru_scan_pallas(a: jnp.ndarray, x: jnp.ndarray, *,
                      bt: int = DEFAULT_BT, bw: int = DEFAULT_BW,
                      interpret: bool | None = None) -> jnp.ndarray:
    """a, x: (B, T, W); T % bt == 0 == W % bw → h (B, T, W)."""
    b, t, w = a.shape
    assert t % bt == 0 and w % bw == 0
    kernel = functools.partial(_rglru_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(b, w // bw, t // bt),
        in_specs=[
            pl.BlockSpec((None, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((None, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
        ],
        out_specs=pl.BlockSpec((None, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
        out_shape=jax.ShapeDtypeStruct((b, t, w), a.dtype),
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(a, x)
