"""Flat-buffer collectives: one ring collective per DynaComm segment.

A *sched layer*'s parameter pytree is packed into a single padded 1-D
float32 buffer (``FlatSpec`` records the layout), so that a DynaComm
transmission segment — a contiguous group of sched layers — becomes exactly
one ``all-gather`` (the paper's parameter *pull*) or one ``reduce-scatter``
(the gradient *push*) on the data axis, no matter how many tensors the
segment contains.

Layout convention: every per-layer buffer is padded to a multiple of the
data-axis size, stored sharded as ``(padded // axis,)`` per device.  To pull
a bucket, the per-layer shards are concatenated and all-gathered once; row
``i`` of the gathered ``(axis, S)`` result is device ``i``'s slice, so each
layer's full buffer is recovered by slicing columns and flattening rows.
The push is the exact transpose: per-layer full gradients are reshaped to
``(axis, padded // axis)``, concatenated along columns, and reduce-scattered
once along rows.

``gather_bucket`` / ``reduce_scatter_bucket`` must run inside ``shard_map``
(they issue ``jax.lax`` collectives over a named axis).

The flat buffer is the *wire format* of a segment, and the ZeRO trainer
(``repro.dist.zero``) holds its state in it only where a wire exists: more
than one device on the data axis, or a compressor modelling the PS wire.
On a one-device axis with no compressor the state holds the parameter
leaves instead and none of these functions runs — packing there would be
a relayout of every weight each step for nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

FLAT_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Layout of one sched layer's pytree inside its padded flat buffer."""

    treedef: Any                              # pytree structure
    shapes: Tuple[Tuple[int, ...], ...]       # per-leaf shapes
    dtypes: Tuple[Any, ...]                   # per-leaf dtypes (restored)
    offsets: Tuple[int, ...]                  # per-leaf start offset
    sizes: Tuple[int, ...]                    # per-leaf element count
    total: int                                # sum of sizes
    padded: int                               # total rounded up to axis_size
    axis_size: int

    @property
    def num_leaves(self) -> int:
        return len(self.sizes)

    @property
    def shard_size(self) -> int:
        return self.padded // self.axis_size


def make_flat_spec(tree: Any, axis_size: int) -> FlatSpec:
    """Compute the flat layout for ``tree`` sharded ``axis_size`` ways.

    Works on concrete arrays and on ``ShapeDtypeStruct`` trees (only
    ``.shape`` / ``.dtype`` are read).
    """
    if axis_size < 1:
        raise ValueError(f"axis_size must be >= 1, got {axis_size}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("cannot build a FlatSpec for an empty pytree")
    shapes, dtypes, offsets, sizes = [], [], [], []
    off = 0
    for leaf in leaves:
        n = 1
        for d in leaf.shape:
            n *= int(d)
        shapes.append(tuple(int(d) for d in leaf.shape))
        dtypes.append(jnp.dtype(leaf.dtype))
        offsets.append(off)
        sizes.append(n)
        off += n
    padded = max(-(-off // axis_size), 1) * axis_size
    return FlatSpec(treedef=treedef, shapes=tuple(shapes), dtypes=tuple(dtypes),
                    offsets=tuple(offsets), sizes=tuple(sizes), total=off,
                    padded=padded, axis_size=axis_size)


def flatten_tree(tree: Any, spec: FlatSpec) -> jnp.ndarray:
    """Pack ``tree`` into its ``(spec.padded,)`` float32 buffer (zero pad)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != spec.num_leaves:
        raise ValueError(f"tree has {len(leaves)} leaves, spec expects "
                         f"{spec.num_leaves}")
    parts: List[jnp.ndarray] = [
        jnp.ravel(x).astype(FLAT_DTYPE) for x in leaves]
    pad = spec.padded - spec.total
    if pad:
        parts.append(jnp.zeros((pad,), FLAT_DTYPE))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def unflatten_tree(flat: jnp.ndarray, spec: FlatSpec) -> Any:
    """Inverse of :func:`flatten_tree` — restores leaf shapes *and dtypes*."""
    if flat.shape != (spec.padded,):
        raise ValueError(f"flat buffer shape {flat.shape} != ({spec.padded},)")
    leaves = [
        flat[o:o + n].reshape(shape).astype(dtype)
        for o, n, shape, dtype in zip(spec.offsets, spec.sizes, spec.shapes,
                                      spec.dtypes)
    ]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def bucket_bytes(specs: Sequence[FlatSpec], bucket: Sequence[int]) -> int:
    """Unpadded payload bytes of one segment's transmission (f32 flats)."""
    return sum(specs[l].total * jnp.dtype(FLAT_DTYPE).itemsize
               for l in bucket)


# ---------------------------------------------------------------------------
# Bucket collectives (shard_map-internal)
# ---------------------------------------------------------------------------


def _check_bucket(specs: Sequence[FlatSpec], bucket: Sequence[int],
                  op: str) -> None:
    """A bucket must be non-empty, name known layers, and share one
    ``axis_size`` across its specs (one collective ⇒ one shard layout)."""
    if not bucket:
        raise ValueError(f"{op}: empty bucket (a DynaComm segment contains "
                         f"at least one layer)")
    bad = [l for l in bucket if not 0 <= l < len(specs)]
    if bad:
        raise ValueError(f"{op}: bucket {tuple(bucket)} names unknown layers "
                         f"{bad} (have specs for 0..{len(specs) - 1})")
    sizes = {specs[l].axis_size for l in bucket}
    if len(sizes) != 1:
        raise ValueError(f"{op}: bucket {tuple(bucket)} mixes axis sizes "
                         f"{sorted(sizes)}; all specs in a bucket must be "
                         f"sharded over the same axis")


def gather_bucket(shards: Sequence[jnp.ndarray], specs: Sequence[FlatSpec],
                  bucket: Sequence[int], axis_name: str) -> Dict[int, Any]:
    """Pull one bucket with a single ``all-gather``.

    ``shards[l]`` is layer ``l``'s local ``(padded_l // axis,)`` slice.
    Returns ``{layer_id: full parameter pytree}`` for every layer in
    ``bucket``.
    """
    _check_bucket(specs, bucket, "gather_bucket")
    cols = [shards[l] for l in bucket]
    concat = cols[0] if len(cols) == 1 else jnp.concatenate(cols)
    gathered = jax.lax.all_gather(concat, axis_name)      # (axis, sum shards)
    out: Dict[int, Any] = {}
    off = 0
    for l in bucket:
        w = specs[l].shard_size
        full = gathered[:, off:off + w].reshape(-1)        # (padded_l,)
        out[l] = unflatten_tree(full, specs[l])
        off += w
    return out


def reduce_scatter_bucket(grads: Dict[int, Any], specs: Sequence[FlatSpec],
                          bucket: Sequence[int], axis_name: str
                          ) -> Dict[int, jnp.ndarray]:
    """Push one bucket with a single ``reduce-scatter``.

    ``grads[l]`` is the *full* (per-device) gradient pytree of layer ``l``;
    the result maps each layer to this device's summed ``(padded_l // axis,)``
    gradient shard (caller divides by the axis size for the mean).
    """
    _check_bucket(specs, bucket, "reduce_scatter_bucket")
    axis_size = specs[bucket[0]].axis_size
    rows = [flatten_tree(grads[l], specs[l]).reshape(axis_size, -1)
            for l in bucket]
    concat = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
    summed = jax.lax.psum_scatter(concat, axis_name, scatter_dimension=0,
                                  tiled=True)              # (1, sum shards)
    flat = summed.reshape(-1)
    out: Dict[int, jnp.ndarray] = {}
    off = 0
    for l in bucket:
        w = specs[l].shard_size
        out[l] = flat[off:off + w]
        off += w
    return out


def compressed_reduce_scatter_bucket(
        grads: Dict[int, Any], specs: Sequence[FlatSpec],
        bucket: Sequence[int], axis_name: str, compressor: Any,
        residuals: Dict[int, jnp.ndarray] | None = None,
        ) -> Tuple[Dict[int, jnp.ndarray], Dict[int, jnp.ndarray] | None]:
    """Push one bucket with each device's contribution compressed first.

    Models the PS wire: every worker quantizes/sparsifies its *own* flat
    gradient before pushing, the server sums the decompressed payloads —
    so the reduce-scatter operand is ``compressor.roundtrip`` of each
    local flat buffer.  With ``residuals`` (per-layer ``(padded_l,)``
    local buffers), the compression error of this push is carried into
    the next one (error feedback); returns ``(shards, new_residuals)``
    where ``new_residuals`` is ``None`` iff no residuals were given.
    """
    _check_bucket(specs, bucket, "compressed_reduce_scatter_bucket")
    axis_size = specs[bucket[0]].axis_size
    rows, new_residuals = [], None if residuals is None else {}
    for l in bucket:
        flat = flatten_tree(grads[l], specs[l])
        if residuals is None:
            flat = compressor.roundtrip(flat)
        else:
            flat, new_residuals[l] = compressor.feedback_roundtrip(
                flat, residuals[l])
        rows.append(flat.reshape(axis_size, -1))
    concat = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
    summed = jax.lax.psum_scatter(concat, axis_name, scatter_dimension=0,
                                  tiled=True)
    flat = summed.reshape(-1)
    out: Dict[int, jnp.ndarray] = {}
    off = 0
    for l in bucket:
        w = specs[l].shard_size
        out[l] = flat[off:off + w]
        off += w
    return out, new_residuals
