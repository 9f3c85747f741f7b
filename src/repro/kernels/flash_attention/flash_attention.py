"""Pallas TPU flash attention: blockwise causal/windowed softmax attention.

Grid ``(B*H, Tq/bq, Tk/bk)`` with the kv axis innermost and sequential
("arbitrary" semantics): each (bh, qi) pair streams kv blocks through VMEM,
maintaining the online-softmax state (m, l, acc) in VMEM scratch and writing
the normalized output on the last visited kv block.  Causal and
sliding-window masks are applied blockwise from iota, never materializing a
(Tq, Tk) matrix; fully-masked kv blocks are skipped via ``pl.when``.

Block shapes default to (128, 128): MXU-aligned on both matmul dims, with
the head dim padded to a lane multiple by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro._compat.pallas import resolve_interpret

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_kv: int, causal: bool, window: int,
                  softcap: float, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * bq
    k_lo = ki * bk
    # blockwise mask from iota — no (Tq, Tk) materialization
    qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window

    # skip blocks that are entirely masked (future / out-of-window)
    live = True
    if causal:
        live = k_lo <= q_lo + bq - 1
    if window > 0:
        live = jnp.logical_and(live, k_lo + bk - 1 > q_lo - window)

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32)          # (bq, hd)
        k = k_ref[...].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)          # (bk, hd)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int = 0,
                           softcap: float = 0.0, bq: int = DEFAULT_BQ,
                           bk: int = DEFAULT_BK, scale: float | None = None,
                           interpret: bool | None = None) -> jnp.ndarray:
    """q,k,v: (BH, T, hd) head-major; T % bq == T % bk == 0.

    ``scale`` must be 1/sqrt(true head dim) when hd is lane-padded.
    """
    bh, tq, hd = q.shape
    tk = k.shape[1]
    assert tq % bq == 0 and tk % bk == 0
    n_q, n_kv = tq // bq, tk // bk
    if scale is None:
        scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=n_kv, causal=causal,
        window=window, softcap=softcap, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),       # m
            pltpu.VMEM((bq, 1), jnp.float32),       # l
            pltpu.VMEM((bq, hd), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
