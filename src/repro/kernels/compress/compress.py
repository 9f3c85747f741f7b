"""Pallas kernels: fused gradient compression on the transmission path.

Two payload formats:

* ``quantize_pack``   — fp32 segments → int8 payload + per-TILE fp32
  scales, in one HBM→VMEM→HBM pass.  Per tile: ``scale = absmax *
  INV_127``, ``q = round(x * 127/absmax)``; the inverse
  ``dequantize_unpack`` restores zero-padded (K, Lmax) rows as
  ``q * scale``.  The scale is a product with a constant, not a division
  by 127: compilers may rewrite a division by a constant as a multiply in
  one program and not in another, and the kernel must match its oracle
  bit for bit.  The kernels see each segment as a lane-dense
  ``(tiles, TILE)`` matrix and take up to ``BLOCK_ROWS`` tiles per grid
  step (scales as a ``(tiles, 1)`` column), which is the block layout
  Mosaic accepts for int8 and for one scale per tile; the last block of a
  segment may be partial (its out-of-range rows are never written).  The
  wrappers compact the per-segment results into the flat payload.
* ``sparsify``/``densify`` — magnitude top-k payloads.  Index *selection*
  is data-dependent and happens outside the kernel (shared jnp helper in
  ``ops.py`` so kernel and oracle agree bit-exactly); the kernels do the
  bandwidth-bound gather/scatter as one-hot masked reductions, with -1
  index slots self-masking.

Every entry point takes ``interpret=None`` → backend auto-detect via
``repro._compat.pallas.resolve_interpret``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro._compat.pallas import resolve_interpret
from repro.kernels.bucket_pack.bucket_pack import (TILE, _check_aligned_lengths,
                                                   aligned)

__all__ = ["TILE", "aligned", "quantize_pack_pallas",
           "dequantize_unpack_pallas", "sparsify_pallas", "densify_pallas"]

INV_127 = np.float32(1.0 / 127.0)

# tiles per grid step: a (256, TILE) f32 block is 512 KiB of VMEM
BLOCK_ROWS = 256
# int8 blocks tile as (32, 128): a block's row count is a multiple of 32
_INT8_SUBLANES = 32


def _tiled_call(kernel, ntiles: int, k_count: int, in_widths, out_shape,
                interpret: bool):
    """``kernel`` over (K, ntiles, width) operands, up to ``BLOCK_ROWS``
    tiles of one segment per grid step."""
    rows = min(BLOCK_ROWS, -(-ntiles // _INT8_SUBLANES) * _INT8_SUBLANES)
    block = lambda width: pl.BlockSpec((None, rows, width),
                                       lambda k, i: (k, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(k_count, -(-ntiles // rows)),
        in_specs=[block(w) for w in in_widths],
        out_specs=[block(s.shape[-1]) for s in out_shape],
        out_shape=out_shape,
        interpret=interpret,
    )


def _quantize_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...]                                          # (rows, TILE)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)     # (rows, 1)
    inv = jnp.where(absmax > 0, 127.0 / absmax, 0.0)
    q_ref[...] = jnp.round(x * inv).astype(jnp.int32).astype(jnp.int8)
    scale_ref[...] = absmax * INV_127


def quantize_pack_pallas(segments: jnp.ndarray,
                         aligned_lengths: Sequence[int], *,
                         interpret: Optional[bool] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(K, Lmax) f32 → (int8 payload (total,), f32 scales (total//TILE,))."""
    interpret = resolve_interpret(interpret)
    if segments.ndim != 2:
        raise ValueError(f"segments must be (K, Lmax), got {segments.shape}")
    if segments.dtype != jnp.float32:
        raise ValueError(f"quantize_pack expects float32 segments, got "
                         f"{segments.dtype}")
    k_count, lmax = segments.shape
    if lmax % TILE:
        raise ValueError(f"segment row length {lmax} is not a multiple of "
                         f"TILE={TILE}")
    _check_aligned_lengths(aligned_lengths, k_count)
    ntiles = lmax // TILE
    q, scales = _tiled_call(
        _quantize_kernel, ntiles, k_count, (TILE,),
        [jax.ShapeDtypeStruct((k_count, ntiles, TILE), jnp.int8),
         jax.ShapeDtypeStruct((k_count, ntiles, 1), jnp.float32)],
        interpret)(segments.reshape(k_count, ntiles, TILE))
    payload = jnp.concatenate([q[k, :n // TILE].reshape(-1)
                               for k, n in enumerate(aligned_lengths)])
    scales = jnp.concatenate([scales[k, :n // TILE, 0]
                              for k, n in enumerate(aligned_lengths)])
    return payload, scales


def _dequantize_kernel(q_ref, scale_ref, out_ref):
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    out_ref[...] = q * scale_ref[...]


def dequantize_unpack_pallas(payload: jnp.ndarray, scales: jnp.ndarray,
                             aligned_lengths: Sequence[int], lmax: int, *,
                             interpret: Optional[bool] = None) -> jnp.ndarray:
    """(int8 payload, per-TILE scales) → (K, Lmax) f32 zero-padded rows."""
    interpret = resolve_interpret(interpret)
    if lmax % TILE:
        raise ValueError(f"lmax {lmax} is not a multiple of TILE={TILE}")
    k_count = len(aligned_lengths)
    _check_aligned_lengths(aligned_lengths, k_count)
    total = int(sum(aligned_lengths))
    if payload.shape != (total,):
        raise ValueError(f"payload shape {payload.shape} != ({total},) "
                         f"implied by aligned lengths")
    if scales.shape != (total // TILE,):
        raise ValueError(f"scales shape {scales.shape} != ({total // TILE},) "
                         f"(one per TILE={TILE})")
    ntiles = lmax // TILE
    # tiles past a segment's length carry q = 0 and scale 0, so they
    # decode to exact zeros without a mask in the kernel
    q_rows, s_rows = [], []
    off = 0
    for n in aligned_lengths:
        nt = n // TILE
        q_rows.append(jnp.pad(payload[off:off + n].reshape(nt, TILE),
                              ((0, ntiles - nt), (0, 0))))
        s_rows.append(jnp.pad(scales[off // TILE:off // TILE + nt],
                              (0, ntiles - nt))[:, None])
        off += n

    (out,) = _tiled_call(
        _dequantize_kernel, ntiles, k_count, (TILE, 1),
        [jax.ShapeDtypeStruct((k_count, ntiles, TILE), scales.dtype)],
        interpret)(jnp.stack(q_rows), jnp.stack(s_rows))
    return out.reshape(k_count, lmax)


def _sparsify_kernel(idx_ref, seg_ref, out_ref):
    idx = idx_ref[...]
    seg = seg_ref[...]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], seg.shape[0]),
                                       1) == idx[:, None]).astype(seg.dtype)
    out_ref[...] = jnp.sum(onehot * seg[None, :], axis=1)


def _check_sparse_shapes(indices: jnp.ndarray, k_count: int) -> None:
    if indices.ndim != 2 or indices.shape[0] != k_count:
        raise ValueError(f"indices must be (K, kmax) with K={k_count}, got "
                         f"{indices.shape}")
    if not jnp.issubdtype(indices.dtype, jnp.integer):
        raise ValueError(f"indices must be integer, got {indices.dtype}")


def sparsify_pallas(segments: jnp.ndarray, indices: jnp.ndarray, *,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Gather (K, kmax) values from (K, Lmax) rows; -1 slots yield 0."""
    interpret = resolve_interpret(interpret)
    if segments.ndim != 2:
        raise ValueError(f"segments must be (K, Lmax), got {segments.shape}")
    k_count, lmax = segments.shape
    _check_sparse_shapes(indices, k_count)
    kmax = indices.shape[1]

    return pl.pallas_call(
        _sparsify_kernel,
        grid=(k_count,),
        in_specs=[pl.BlockSpec((None, kmax), lambda k: (k, 0)),
                  pl.BlockSpec((None, lmax), lambda k: (k, 0))],
        out_specs=pl.BlockSpec((None, kmax), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((k_count, kmax), segments.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), segments)


def _densify_kernel(idx_ref, val_ref, out_ref):
    idx = idx_ref[...]
    val = val_ref[...]
    onehot = (jax.lax.broadcasted_iota(jnp.int32,
                                       (idx.shape[0], out_ref.shape[0]), 1)
              == idx[:, None]).astype(val.dtype)
    out_ref[...] = jnp.sum(onehot * val[:, None], axis=0)


def densify_pallas(values: jnp.ndarray, indices: jnp.ndarray, lmax: int, *,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Scatter (K, kmax) values back to dense (K, lmax); -1 slots drop."""
    interpret = resolve_interpret(interpret)
    if values.ndim != 2:
        raise ValueError(f"values must be (K, kmax), got {values.shape}")
    k_count, kmax = values.shape
    _check_sparse_shapes(indices, k_count)
    if indices.shape != values.shape:
        raise ValueError(f"indices shape {indices.shape} != values shape "
                         f"{values.shape}")

    return pl.pallas_call(
        _densify_kernel,
        grid=(k_count,),
        in_specs=[pl.BlockSpec((None, kmax), lambda k: (k, 0)),
                  pl.BlockSpec((None, kmax), lambda k: (k, 0))],
        out_specs=pl.BlockSpec((None, lmax), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((k_count, lmax), values.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), values)
