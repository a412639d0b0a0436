"""Coalesced collectives.

Parity with reference ``runtime/comm/coalesced_collectives.py:30``
``reduce_scatter_coalesced``: ZeRO's gradient path reduces MANY tensors of
ragged sizes in ONE collective by packing them into a flat, evenly-divisible
buffer (padding the tail), scattering, and re-slicing each rank's shard.

TPU re-design: the packing math is identical, but the collective is
``lax.psum_scatter`` over a named mesh axis inside shard_map/jit — XLA
already coalesces adjacent collectives it can prove contiguous; this utility
exists for the cases it can't (ragged pytrees) and for API parity. All
shapes are static, so the pack/unpack slicing compiles to free bitcasts.
"""

from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _flatten_pad(tensors: Sequence[jnp.ndarray], world: int
                 ) -> Tuple[jnp.ndarray, List[Tuple[int, Any, Any]]]:
    """Concat raveled tensors; pad total to a multiple of ``world``.
    Returns (flat, [(numel, shape, dtype), ...])."""
    meta = [(int(t.size), t.shape, t.dtype) for t in tensors]
    flat = jnp.concatenate([t.ravel() for t in tensors])
    total = flat.size
    pad = (-total) % world
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, meta


def reduce_scatter_coalesced(tensors: Sequence[jnp.ndarray], axis: str
                             ) -> jnp.ndarray:
    """Sum-reduce a list of tensors across ``axis`` and return THIS rank's
    flat shard of the packed buffer (reference coalesced_collectives.py:30).

    Must run inside shard_map/jit with ``axis`` bound. The caller unpacks
    shard-local slices with :func:`shard_layout`.
    """
    world = lax.axis_size(axis)
    flat, _ = _flatten_pad(tensors, world)
    return lax.psum_scatter(flat, axis, tiled=True)


def all_gather_coalesced(shards: Sequence[jnp.ndarray], axis: str
                         ) -> List[jnp.ndarray]:
    """Reassemble full tensors from per-rank shards in ONE collective
    (reference ZeRO-3 ``all_gather_coalesced``,
    partition_parameters.py:806): each rank holds an equal-size flat shard
    of every tensor; pack -> one tiled all_gather -> reslice.

    ``shards[i]`` is this rank's flat shard; the result's ``out[i]`` is the
    full flat tensor of size ``world * shards[i].size`` (rank-major, the
    partitioning ZeRO-3 uses — the caller reshapes/unpads). Memory is 1x
    the gathered size; the reslice compiles to static slices of the single
    gathered buffer."""
    world = lax.axis_size(axis)
    sizes = [int(s.size) for s in shards]
    flat = jnp.concatenate([s.ravel() for s in shards])
    per = flat.size
    gathered = lax.all_gather(flat, axis, tiled=True)  # [world * per]
    packs = gathered.reshape(world, per)
    out: List[jnp.ndarray] = []
    offset = 0
    for n, s in zip(sizes, shards):
        # rank-major reassembly: [world, n] -> [world * n]
        out.append(packs[:, offset:offset + n].reshape(world * n)
                   .astype(s.dtype))
        offset += n
    return out


def shard_layout(tensors: Sequence[Any], world: int
                 ) -> List[Tuple[int, int]]:
    """(start, length) of each tensor inside the packed flat buffer —
    callers intersect these with a rank's [rank*shard, (rank+1)*shard)
    window to locate their slice of each tensor (the bookkeeping the
    reference does with partition offsets in stage_1_and_2.py:74)."""
    spans = []
    offset = 0
    for t in tensors:
        n = int(t.size) if hasattr(t, "size") else int(t)
        spans.append((offset, n))
        offset += n
    return spans
