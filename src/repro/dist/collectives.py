"""Bucket collectives: the pull and push of a DynaComm segment.

A *sched layer*'s parameter pytree is described by a ``FlatSpec`` (its
leaves' shapes, dtypes and byte counts).  The ZeRO trainer
(``repro.dist.zero``) holds its state in one of two layouts, and each has
its pair of collectives here:

* **leaves** (no compressor): the parameter leaves in their natural
  shapes, each sharded over the data axis on one dimension
  (:func:`shard_dim`: the first the axis size divides; a leaf that none
  divides is held whole on every device).  A segment's pull
  (:func:`gather_bucket`) all-gathers each of its leaves along that
  dimension, and its push (:func:`reduce_scatter_bucket`)
  reduce-scatters each leaf's gradient along it (sums a whole leaf's).
  No leaf changes its shape or tiling on the way.
* **flat** (a compressor models the PS wire; the flat wire is the
  compressor's alone): the pytree packed into one padded 1-D float32
  buffer, stored sharded as ``(padded // axis,)`` per device, because the
  quantize and top-k kernels work on lane-dense flat blocks.  A segment
  becomes exactly one ``all-gather`` (:func:`gather_flat_bucket`: row
  ``i`` of the gathered ``(axis, S)`` result is device ``i``'s slice, so
  each layer's buffer is recovered by slicing columns and flattening
  rows) or one ``reduce-scatter`` of the compressed per-layer buffers
  reshaped to ``(axis, padded // axis)`` and concatenated along columns
  (:func:`compressed_reduce_scatter_bucket`).

The bucket collectives must run inside ``shard_map`` (they issue
``jax.lax`` collectives over a named axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

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

    @property
    def dims(self) -> Tuple[Optional[int], ...]:
        """Per leaf, the dimension it is sharded on in the leaves layout
        (:func:`shard_dim`)."""
        return tuple(shard_dim(s, self.axis_size) for s in self.shapes)


def shard_dim(shape: Sequence[int], axis_size: int) -> Optional[int]:
    """The dimension a leaf of ``shape`` is split on over a data axis of
    ``axis_size`` devices: the first that ``axis_size`` divides, chosen by
    shape alone; ``None`` where none does (a scalar, or a leaf such as a
    ``(1,)`` bias on more than one device), and the leaf is held whole on
    every device."""
    return next((d for d, n in enumerate(shape) if n % axis_size == 0), None)


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


def gather_bucket(shards: Mapping[int, Sequence[jnp.ndarray]],
                  specs: Sequence[FlatSpec], bucket: Sequence[int],
                  axis_name: str) -> Dict[int, Any]:
    """Pull one bucket of sharded leaves: each leaf ``all-gather``-ed
    along its :func:`shard_dim` (``tiled``, so it comes back in its
    natural shape); a leaf held whole is used as it is.

    ``shards[l]`` is layer ``l``'s local leaf shards in ``tree_flatten``
    order.  Returns ``{layer_id: full parameter pytree}`` for every layer
    in ``bucket``.
    """
    _check_bucket(specs, bucket, "gather_bucket")
    out: Dict[int, Any] = {}
    for l in bucket:
        leaves = [x if d is None else
                  jax.lax.all_gather(x, axis_name, axis=d, tiled=True)
                  for x, d in zip(shards[l], specs[l].dims)]
        out[l] = jax.tree_util.tree_unflatten(specs[l].treedef, leaves)
    return out


def reduce_scatter_bucket(grads: Dict[int, Any], specs: Sequence[FlatSpec],
                          bucket: Sequence[int], axis_name: str
                          ) -> Dict[int, List[jnp.ndarray]]:
    """Push one bucket of sharded leaves: each leaf's gradient
    ``reduce-scatter``-ed along its :func:`shard_dim`; a leaf held whole
    is ``psum``-ed.

    ``grads[l]`` is the *full* (per-device) gradient pytree of layer
    ``l``; the result maps each layer to this device's summed share of
    each leaf, in ``tree_flatten`` order (caller divides by the axis size
    for the mean).
    """
    _check_bucket(specs, bucket, "reduce_scatter_bucket")
    out: Dict[int, List[jnp.ndarray]] = {}
    for l in bucket:
        out[l] = [jax.lax.psum(g, axis_name) if d is None else
                  jax.lax.psum_scatter(g, axis_name, scatter_dimension=d,
                                       tiled=True)
                  for g, d in zip(jax.tree_util.tree_leaves(grads[l]),
                                  specs[l].dims)]
    return out


def gather_flat_bucket(shards: Sequence[jnp.ndarray],
                       specs: Sequence[FlatSpec], bucket: Sequence[int],
                       axis_name: str) -> Dict[int, Any]:
    """Pull one bucket of flat buffers with a single ``all-gather``.

    ``shards[l]`` is layer ``l``'s local ``(padded_l // axis,)`` slice.
    Returns ``{layer_id: full parameter pytree}`` for every layer in
    ``bucket``.
    """
    _check_bucket(specs, bucket, "gather_flat_bucket")
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
