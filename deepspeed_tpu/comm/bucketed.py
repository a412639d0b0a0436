"""Bucketed gradient exchange (GAS-boundary bucketing, T3-style).

The engine's compressed step keeps PER-WORKER gradients through the
accumulation window and exchanges once at the optimizer boundary
(``runtime/grad_exchange.py`` ``update_core``). Historically that
exchange was one collective per gradient leaf, issued in a serial chain:
each int8 exchange is a quantize -> all_to_all -> sum -> requantize ->
all_gather pipeline whose phases depend on each other, so leaf N+1's
quantize cannot start until leaf N's all_gather returns.

This module re-buckets the exchange ("T3: Transparent Tracking &
Triggering", PAPERS.md): leaves are packed — in deterministic tree order —
into size-bounded buckets and exchanged one collective per bucket. The
buckets are mutually independent dataflow chains, so XLA's latency-hiding
scheduler is free to overlap bucket N+1's compute phases (quantize /
dequant-sum) with bucket N's in-flight collectives, and small leaves
amortize collective launch latency by riding in a shared payload.

Three entry points, all trace-level (call inside ``shard_map``/``jit``
over a named mesh axis):

- :func:`assign_buckets` / :func:`plan_for_tree` — deterministic bucket
  assignment by leaf size (greedy, fixed tree order; a byte budget of 0
  degenerates to one leaf per bucket, a huge budget to one monolithic
  bucket).
- :func:`bucketed_all_reduce` — fp32/bf16-wire bucketed psum. With an
  fp32 wire this is BIT-FOR-BIT identical to the per-leaf exchange
  (psum is elementwise; concatenation order cannot change any element's
  reduction).
- :func:`bucketed_quantized_all_reduce` — the int8 EQuARX path
  (``comm.compressed.quantized_all_reduce``) per bucket, with the
  worker/server error-feedback residuals carried PER BUCKET on the flat
  concatenated payload and per-bucket wire accounting
  (``<log_name>.bucket<i>`` payload + ``.scales`` sideband).
- :func:`hierarchical_all_reduce` — the two-level ICI/DCN exchange
  ("Scale MLPerf-0.6 models on Google TPU-v3 Pods" posture with the
  EQuARX inter-slice wire, PAPERS.md): bf16 reduce-scatter within each
  slice over ICI, int8 quantized exchange of the 1/P reduced shard
  across slices over DCN, bf16 all-gather back within the slice. DCN
  moves ~2(G-1)/G x N/P int8 bytes instead of 2(W-1)/W x 2N bf16 bytes.
"""

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepspeed_tpu.comm.compressed import (quantized_all_reduce,
                                           server_shard_length)
from deepspeed_tpu.comm.logging import comms_logger


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Deterministic leaf -> bucket assignment for one gradient tree.

    ``bucket_leaves[b]`` holds the flat-leaf indices (``jax.tree.flatten``
    order) exchanged in bucket ``b``; concatenation inside a bucket follows
    that order. The plan depends only on leaf sizes and the byte budget, so
    every rank computes the identical plan from the identical param tree —
    no coordination needed.
    """

    bucket_leaves: Tuple[Tuple[int, ...], ...]
    leaf_sizes: Tuple[int, ...]

    @property
    def num_buckets(self) -> int:
        return len(self.bucket_leaves)

    def bucket_sizes(self) -> Tuple[int, ...]:
        """Element count of each bucket's flat concatenated payload."""
        return tuple(sum(self.leaf_sizes[i] for i in idxs)
                     for idxs in self.bucket_leaves)


def assign_buckets(leaf_sizes: Sequence[int], bucket_bytes: int,
                   itemsize: int = 4) -> BucketPlan:
    """Greedy fixed-order packing of leaves into ``bucket_bytes`` buckets.

    Leaves keep tree order (reproducible across ranks and runs). A bucket
    closes when adding the next leaf would exceed the budget; a single
    leaf larger than the budget gets a bucket of its own. ``bucket_bytes
    <= 0`` yields one leaf per bucket (the legacy per-leaf exchange
    expressed as a plan). ``itemsize`` is the accumulation dtype's width —
    gradients exchange from f32 accumulators, hence the default 4.
    """
    buckets, cur, cur_bytes = [], [], 0
    for i, n in enumerate(leaf_sizes):
        nbytes = int(n) * itemsize
        if cur and (bucket_bytes <= 0 or cur_bytes + nbytes > bucket_bytes):
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(tuple(cur))
    return BucketPlan(tuple(buckets), tuple(int(n) for n in leaf_sizes))


def plan_for_tree(tree: Any, bucket_mb: float, itemsize: int = 4
                  ) -> BucketPlan:
    """Bucket plan for a pytree of arrays / ShapeDtypeStructs."""
    sizes = [int(np.prod(leaf.shape)) if leaf.shape else 1
             for leaf in jax.tree.leaves(tree)]
    plan = assign_buckets(sizes, int(bucket_mb * 1024 * 1024), itemsize)
    # plans are built at trace/compile time, never per step — a plan
    # change mid-run (retrace) is exactly what forensics wants to see
    from deepspeed_tpu.telemetry.bus import KIND_BUCKET_PLAN, publish

    publish(KIND_BUCKET_PLAN, num_buckets=plan.num_buckets,
            num_leaves=len(sizes), bucket_mb=float(bucket_mb),
            total_bytes=int(sum(sizes)) * int(itemsize))
    return plan


def _concat_bucket(leaves, idxs, dtype=None):
    parts = [leaves[i].ravel() if dtype is None
             else leaves[i].astype(dtype).ravel() for i in idxs]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _split_bucket(flat, leaves, idxs, out):
    off = 0
    for i in idxs:
        n = leaves[i].size
        out[i] = flat[off:off + n].reshape(
            leaves[i].shape).astype(leaves[i].dtype)
        off += n


def bucketed_all_reduce(tree: Any, axis: str,
                        plan: Optional[BucketPlan] = None, *,
                        wire_dtype=None, mean: bool = False,
                        log_name: str = "bucketed_all_reduce") -> Any:
    """Bucketed sum (or mean) all-reduce of a gradient tree.

    One ``psum`` per bucket; ``wire_dtype`` (e.g. ``jnp.bfloat16``) casts
    the concatenated payload before the collective and back after, halving
    wire bytes at ~3 decimal digits of mantissa. With the native (f32)
    wire the result is bit-for-bit the per-leaf exchange. ``plan=None``
    degenerates to one bucket per leaf. Wire bytes log under
    ``<log_name>.bucket<i>`` (one record per bucket, mirroring the
    quantized path) so benchmarks can meter each bucket's payload.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if plan is None:
        plan = assign_buckets([l.size for l in leaves], 0)
    w = int(lax.psum(1, axis))
    out = [None] * len(leaves)
    for b, idxs in enumerate(plan.bucket_leaves):
        flat = _concat_bucket(leaves, idxs)
        payload = (flat if wire_dtype is None
                   or flat.dtype == jnp.dtype(wire_dtype)
                   else flat.astype(wire_dtype))
        comms_logger.append("all_reduce", payload, axis,
                            log_name=f"{log_name}.bucket{b}", world=w)
        reduced = lax.psum(payload, axis).astype(flat.dtype)
        if mean:
            reduced = reduced / w
        _split_bucket(reduced, leaves, idxs, out)
    return jax.tree.unflatten(treedef, out)


def hierarchy_groups(world: int, num_slices: int
                     ) -> Tuple[Tuple[Tuple[int, ...], ...],
                                Tuple[Tuple[int, ...], ...]]:
    """ICI/DCN ``axis_index_groups`` for a dp axis of ``world`` ranks laid
    out over ``num_slices`` slices.

    Assumes the slice dimension is the SLOW (outer) dimension of the axis:
    rank = slice_idx * per_slice + ici_idx. That is exactly what
    ``mesh_utils.create_hybrid_device_mesh`` produces (the dcn mesh shape
    stacks outside each per-slice mesh — ``parallel/mesh.py:_arrange``),
    and what ``tpu.grad_exchange.dcn_slices`` emulates on the virtual CPU
    mesh. ICI groups are the contiguous per-slice runs; DCN groups take
    one rank at the same ICI position from every slice.
    """
    if num_slices < 1 or world % num_slices:
        raise ValueError(
            f"cannot split a dp axis of {world} ranks into {num_slices} "
            f"equal slices")
    per = world // num_slices
    ici = tuple(tuple(s * per + i for i in range(per))
                for s in range(num_slices))
    dcn = tuple(tuple(s * per + i for s in range(num_slices))
                for i in range(per))
    return ici, dcn


def hierarchical_all_reduce(tree: Any, axis: str, num_slices: int,
                            plan: Optional[BucketPlan] = None, *,
                            block: int = 512, wire_dtype=jnp.bfloat16,
                            mean: bool = False,
                            log_name: str = "hierarchical_grad_exchange"
                            ) -> Any:
    """Two-level ICI/DCN sum (or mean) all-reduce of a gradient tree.

    Per bucket, with W ranks in ``num_slices`` slices of P ranks each:

    1. ``psum_scatter`` the bucket within each slice (ICI, ``wire_dtype``
       — bf16 by default): every rank ends with its slice's sum of a
       1/P shard.
    2. :func:`quantized_all_reduce` of the shard ACROSS slices (DCN,
       int8 + per-block fp32 scales) via ``axis_index_groups`` — the
       EQuARX wire format on the expensive interconnect, at 1/P of the
       tensor. No error feedback: the deferred exchange is stateless
       (one exchange per optimizer step; residuals would need optimizer
       state the bf16/fp32 deferred family deliberately does not carry).
    3. ``all_gather`` the globally reduced shard back within each slice
       (ICI, ``wire_dtype``).

    Wire accounting tags the intra-slice legs ``level="ici"`` and the
    inter-slice leg ``level="dcn"`` (``Comm/ici_bytes`` /
    ``Comm/dcn_bytes``). ``num_slices=1`` degenerates to a single-level
    scatter/gather psum (no DCN leg, everything metered as ICI).
    """
    leaves, treedef = jax.tree.flatten(tree)
    if plan is None:
        plan = assign_buckets([l.size for l in leaves], 0)
    w = int(lax.psum(1, axis))
    ici_groups, dcn_groups = hierarchy_groups(w, num_slices)
    per_slice = w // num_slices
    out = [None] * len(leaves)
    for b, idxs in enumerate(plan.bucket_leaves):
        flat = _concat_bucket(leaves, idxs)
        n = flat.size
        pad = (-n) % per_slice
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        payload = (flat if wire_dtype is None
                   or flat.dtype == jnp.dtype(wire_dtype)
                   else flat.astype(wire_dtype))
        comms_logger.append("reduce_scatter", payload, axis,
                            log_name=f"{log_name}.bucket{b}.ici",
                            world=per_slice, level="ici")
        shard = lax.psum_scatter(
            payload, axis, scatter_dimension=0, tiled=True,
            axis_index_groups=list(map(list, ici_groups))
        ).astype(flat.dtype)
        if num_slices > 1:
            shard = quantized_all_reduce(
                shard, axis, block=block,
                axis_index_groups=list(map(list, dcn_groups)),
                log_name=f"{log_name}.bucket{b}.dcn", level="dcn")
        gathered = (shard if wire_dtype is None
                    or shard.dtype == jnp.dtype(wire_dtype)
                    else shard.astype(wire_dtype))
        comms_logger.append("all_gather", gathered, axis,
                            log_name=f"{log_name}.bucket{b}.ici",
                            world=per_slice, level="ici")
        full = lax.all_gather(
            gathered, axis, tiled=True,
            axis_index_groups=list(map(list, ici_groups))
        ).astype(flat.dtype)
        if pad:
            full = full[:n]
        if mean:
            full = full / w
        _split_bucket(full, leaves, idxs, out)
    return jax.tree.unflatten(treedef, out)


def bucketed_quantized_all_reduce(
        tree: Any, axis: str, plan: Optional[BucketPlan] = None, *,
        block: int = 512,
        worker_errors: Optional[Sequence[jnp.ndarray]] = None,
        server_errors: Optional[Sequence[jnp.ndarray]] = None,
        log_name: str = "quantized_all_reduce"
) -> Tuple[Any, Tuple[jnp.ndarray, ...], Tuple[jnp.ndarray, ...]]:
    """Per-bucket int8 EQuARX exchange with per-bucket error feedback.

    ``worker_errors[b]`` (``[bucket_len]`` f32) is added into bucket
    ``b``'s payload before quantization; ``server_errors[b]``
    (``[server_shard_length(bucket_len, W, block)]`` f32) compensates the
    phase-2 requantization. Either may be ``None`` for a cold start.
    Returns ``(sum_tree, new_worker_errors, new_server_errors)`` — the SUM
    over the axis (divide by W for the mean), residuals as per-bucket
    tuples in bucket order. Wire bytes log under
    ``<log_name>.bucket<i>`` / ``...bucket<i>.scales`` so the comm
    benchmarks can report each bucket's payload and sideband.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if plan is None:
        plan = assign_buckets([l.size for l in leaves], 0)
    w = int(lax.psum(1, axis))
    out = [None] * len(leaves)
    new_we, new_se = [], []
    for b, idxs in enumerate(plan.bucket_leaves):
        flat = _concat_bucket(leaves, idxs, dtype=jnp.float32)
        if worker_errors is not None:
            flat = flat + worker_errors[b]
        se = (server_errors[b] if server_errors is not None
              else jnp.zeros((server_shard_length(flat.size, w, block),),
                             jnp.float32))
        reduced, err, new_server = quantized_all_reduce(
            flat, axis, block=block, return_error=True, server_error=se,
            log_name=f"{log_name}.bucket{b}")
        _split_bucket(reduced, leaves, idxs, out)
        new_we.append(err)
        new_se.append(new_server)
    return jax.tree.unflatten(treedef, out), tuple(new_we), tuple(new_se)
