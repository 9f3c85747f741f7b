"""Pure-jnp oracles for the compression kernels.

Each oracle performs exactly the per-tile / per-row math of its Pallas
kernel on the same partitioning, so interpret-mode kernel outputs must
match bit-for-bit (``np.testing.assert_array_equal``).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp

from repro.kernels.bucket_pack.bucket_pack import TILE
from repro.kernels.compress.compress import INV_127


def quantize_pack_ref(segments: jnp.ndarray,
                      aligned_lengths: Sequence[int]
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(K, Lmax) f32 segments → (int8 payload, per-TILE f32 scales).

    Per tile: scale = absmax * INV_127, q = round(x * 127 / absmax); an
    all-zero tile quantizes to zeros with scale 0.
    """
    qs, scales = [], []
    for k, n in enumerate(aligned_lengths):
        tiles = segments[k, :n].reshape(-1, TILE)
        absmax = jnp.max(jnp.abs(tiles), axis=1)
        inv = jnp.where(absmax > 0, 127.0 / absmax, 0.0)
        qs.append(jnp.round(tiles * inv[:, None]).astype(jnp.int8).reshape(-1))
        scales.append(absmax * INV_127)
    return jnp.concatenate(qs), jnp.concatenate(scales)


def dequantize_unpack_ref(payload: jnp.ndarray, scales: jnp.ndarray,
                          aligned_lengths: Sequence[int],
                          lmax: int) -> jnp.ndarray:
    """(int8 payload, scales) → (K, Lmax) f32, zero-padded past lengths."""
    rows = []
    off = toff = 0
    for n in aligned_lengths:
        tiles = payload[off:off + n].reshape(-1, TILE).astype(jnp.float32)
        s = scales[toff:toff + n // TILE]
        row = (tiles * s[:, None]).reshape(-1)
        rows.append(jnp.pad(row, (0, lmax - n)))
        off += n
        toff += n // TILE
    return jnp.stack(rows)


def sparsify_ref(segments: jnp.ndarray, indices: jnp.ndarray) -> jnp.ndarray:
    """Gather values at per-row ``indices``; -1 index slots yield 0."""
    gathered = jnp.take_along_axis(segments, jnp.maximum(indices, 0), axis=1)
    return jnp.where(indices >= 0, gathered, 0.0).astype(segments.dtype)


def densify_ref(values: jnp.ndarray, indices: jnp.ndarray,
                lmax: int) -> jnp.ndarray:
    """Scatter (values, indices) back to dense (K, Lmax); -1 slots drop."""
    k_count = values.shape[0]
    vals = jnp.where(indices >= 0, values, 0.0)
    out = jnp.zeros((k_count, lmax), values.dtype)
    rows = jnp.arange(k_count)[:, None]
    return out.at[rows, jnp.maximum(indices, 0)].add(vals)
