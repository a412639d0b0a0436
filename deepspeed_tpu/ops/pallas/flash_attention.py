"""Flash attention as Pallas TPU kernels.

The TPU equivalent of the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, triton ``triton_flash_attn``,
``ops/transformer/inference/triton_ops.py:103``): blockwise online-softmax
attention that never materializes the [T, T] score matrix in HBM.

Layout: q/k/v are ``[batch, seq, heads, head_dim]`` (the model's natural
layout). The kernel grid is (batch*heads, q_blocks); each program streams K/V
blocks from VMEM with running max/sum rescaling. The backward pass is the
standard two-kernel recompute formulation (dq; then dk/dv) using the saved
logsumexp — O(T) memory like the forward.

On non-TPU backends the kernels run in Pallas interpreter mode, so the CPU
test mesh exercises the exact same code path.

Packed sequences (``segment_ids``): when the data pipeline bin-packs
several documents into one row (``deepspeed_tpu/data/packing.py``),
attention must be restricted to *causal AND same-segment* for the packed
loss to be exact vs running each document alone (docs/data.md). The
segment mask rides into the kernels in two pre-broadcast layouts chosen
to match TPU tiling with no in-kernel transpose:

* ``seg_r [bh, t, LSE_LANES]`` — row layout, sliced like q/lse blocks to
  give the query-side segment id column;
* ``seg_c [bh, LSE_LANES, t]`` — column layout: the key-side segment id
  row. The dkv kernel takes its own k block of it through a BlockSpec; the
  fwd and dq kernels, which walk the k blocks in a loop, get it regrouped
  as ``[bh, t/block_k, LSE_LANES, block_k]`` and index the block on the
  leading axis (Mosaic lowers no ``dynamic_slice`` of a value, and a
  leading-axis ref index needs no lane alignment proof).

Masking uses the same finite ``NEG_INF`` as the causal path: a masked
score contributes ``exp(-1e30) == 0.0`` exactly to both softmax and its
gradient, so cross-segment leakage is zero, and pad rows (segment 0)
still see their own diagonal so no row is ever fully masked.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas.common import (
    LSE_LANES,
    NEG_INF,
    interpret as _interpret,
    largest_divisor_block as _block,
)

# the kernels' names in a profiler trace and in the lowered HLO
# (``kernel_name`` of the tpu_custom_call): a reader tells forward from
# backward by these, not by the call's signature
KERNEL_FWD = "flash_fwd"
KERNEL_BWD_DQ = "flash_bwd_dq"
KERNEL_BWD_DKV = "flash_bwd_dkv"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_k,
                has_seg=False):
    if has_seg:
        sq_ref, sk_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    bq, d = q_ref.shape
    t = k_ref.shape[0]
    nk = t // block_k
    qi = pl.program_id(1)

    # keep MXU operands in the input dtype (bf16): f32xf32 dots fall off the
    # systolic array's fast path; accumulate in f32 via preferred_element_type
    q = q_ref[...]  # [bq, d]
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
    if has_seg:
        q_seg = sq_ref[...][:, :1]  # [bq, 1]

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, block_k]
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if has_seg:
            k_seg = sk_ref[j][:1, :]  # [1, block_k]
            s = jnp.where(q_seg == k_seg, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    if causal:
        # only blocks with k_start <= q_end contribute
        nk_eff = jnp.minimum((qi * bq + bq + block_k - 1) // block_k, nk)
    else:
        nk_eff = nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m, l, acc))

    o_ref[...] = (acc / l).astype(o_ref.dtype)
    # lse carries 8 broadcast sublane copies to satisfy TPU tiling
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), (bq, LSE_LANES))


def _seg_by_k_block(seg_c, block_k):
    """``[bh, LSE_LANES, t]`` -> ``[bh, t/block_k, LSE_LANES, block_k]``
    with its whole-array BlockSpec (see the module docstring)."""
    bh, lanes, t = seg_c.shape
    nk = t // block_k
    grouped = seg_c.reshape(bh, lanes, nk, block_k).transpose(0, 2, 1, 3)
    return grouped, pl.BlockSpec((None, nk, lanes, block_k),
                                 lambda i, j: (i, 0, 0, 0))


def _fwd(q, k, v, seg, scale, causal, block_q, block_k):
    b, t, h, d = q.shape
    bh = b * h
    qf = q.transpose(0, 2, 1, 3).reshape(bh, t, d)
    kf = k.transpose(0, 2, 1, 3).reshape(bh, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(bh, t, d)
    nq = t // block_q

    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
    ]
    operands = [qf, kf, vf]
    if seg is not None:
        seg_r, seg_c = seg
        seg_ck, seg_ck_spec = _seg_by_k_block(seg_c, block_k)
        in_specs += [
            pl.BlockSpec((None, block_q, LSE_LANES), lambda i, j: (i, j, 0)),
            seg_ck_spec,
        ]
        operands += [seg_r, seg_ck]

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, has_seg=seg is not None),
        grid=(bh, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, LSE_LANES), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, LSE_LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name=KERNEL_FWD,
    )(*operands)
    return o, lse


# ---------------------------------------------------------------------------
# backward (recompute with saved lse)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, block_k, has_seg=False):
    if has_seg:
        sq_ref, sk_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    bq, d = q_ref.shape
    t = k_ref.shape[0]
    nk = t // block_k
    qi = pl.program_id(1)

    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[...][:, :1]
    delta = delta_ref[...][:, :1]
    dq = jnp.zeros((bq, d), jnp.float32)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
    if has_seg:
        q_seg = sq_ref[...][:, :1]  # [bq, 1]

    def body(j, dq):
        k_blk = k_ref[pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if has_seg:
            k_seg = sk_ref[j][:1, :]  # [1, block_k]
            s = jnp.where(q_seg == k_seg, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k_blk.dtype)
        return dq + scale * jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    nk_eff = (jnp.minimum((qi * bq + bq + block_k - 1) // block_k, nk)
              if causal else nk)
    dq = jax.lax.fori_loop(0, nk_eff, body, dq)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, block_q, has_seg=False):
    if has_seg:
        sr_ref, sc_ref, dk_ref, dv_ref = rest
    else:
        dk_ref, dv_ref = rest
    bk, d = k_ref.shape
    t = q_ref.shape[0]
    nq = t // block_q
    ki = pl.program_id(1)

    k = k_ref[...]
    v = v_ref[...]
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
    if has_seg:
        k_seg = sc_ref[...][:1, :]  # [1, bk]

    def body(i, carry):
        dk, dv = carry
        j = i + (ki * bk) // block_q if causal else i
        q_blk = q_ref[pl.ds(j * block_q, block_q), :]
        do_blk = do_ref[pl.ds(j * block_q, block_q), :]
        lse_blk = lse_ref[pl.ds(j * block_q, block_q), :1]
        delta_blk = delta_ref[pl.ds(j * block_q, block_q), :1]
        s = scale * jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [block_q, bk]
        if causal:
            q_pos = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if has_seg:
            q_seg_blk = sr_ref[pl.ds(j * block_q, block_q), :1]  # [block_q, 1]
            s = jnp.where(q_seg_blk == k_seg, s, NEG_INF)
        p = jnp.exp(s - lse_blk)
        pb = p.astype(do_blk.dtype)
        dv = dv + jax.lax.dot_general(
            pb, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_blk)).astype(q_blk.dtype)
        dk = dk + scale * jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # q blocks entirely before this k block's diagonal contribute nothing
        n_eff = nq - (ki * bk) // block_q
    else:
        n_eff = nq
    dk, dv = jax.lax.fori_loop(0, n_eff, body, (dk, dv))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd_impl(scale, causal, block_q, block_k, q, k, v, o, lse, do,
              seg=None):
    b, t, h, d = q.shape
    bh = b * h

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(bh, t, d)

    qf, kf, vf = map(flat, (q, k, v))
    of, dof = o, do  # already [bh, t, d] (the op's internal layout)
    delta = jnp.sum(of.astype(jnp.float32) * dof.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LSE_LANES,))

    nq, nk = t // block_q, t // block_k
    dq_in_specs = [
        pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, block_q, LSE_LANES), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, block_q, LSE_LANES), lambda i, j: (i, j, 0)),
    ]
    dq_operands = [qf, kf, vf, dof, lse, delta]
    dkv_in_specs = [
        pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, t, LSE_LANES), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, t, LSE_LANES), lambda i, j: (i, 0, 0)),
    ]
    dkv_operands = [qf, kf, vf, dof, lse, delta]
    if seg is not None:
        seg_r, seg_c = seg
        seg_ck, seg_ck_spec = _seg_by_k_block(seg_c, block_k)
        dq_in_specs += [
            pl.BlockSpec((None, block_q, LSE_LANES), lambda i, j: (i, j, 0)),
            seg_ck_spec,
        ]
        dq_operands += [seg_r, seg_ck]
        # dkv slices the row layout by q block in-kernel and takes its own
        # k block from the column layout
        dkv_in_specs += [
            pl.BlockSpec((None, t, LSE_LANES), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, LSE_LANES, block_k), lambda i, j: (i, 0, j)),
        ]
        dkv_operands += [seg_r, seg_c]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, has_seg=seg is not None),
        grid=(bh, nq),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=_interpret(),
        name=KERNEL_BWD_DQ,
    )(*dq_operands)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, has_seg=seg is not None),
        grid=(bh, nk),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        ],
        interpret=_interpret(),
        name=KERNEL_BWD_DKV,
    )(*dkv_operands)

    def unflat(x):
        return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    return unflat(dq), unflat(dk), unflat(dv)


def _bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, o, lse = res
    return _bwd_impl(scale, causal, block_q, block_k, q, k, v, o, lse, g)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    o, _ = _fwd(q, k, v, None, scale, causal, block_q, block_k)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _fwd(q, k, v, None, scale, causal, block_q, block_k)
    # under remat, tagging the kernel outputs lets a names-aware policy keep
    # them (o: 2 bytes/elem, lse: 1/head_dim of that) instead of re-running
    # the whole forward kernel to regenerate residuals in the backward pass
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_seg(q, k, v, seg_r, seg_c, scale, causal, block_q, block_k):
    o, _ = _fwd(q, k, v, (seg_r, seg_c), scale, causal, block_q, block_k)
    return o


def _flash_seg_fwd(q, k, v, seg_r, seg_c, scale, causal, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _fwd(q, k, v, (seg_r, seg_c), scale, causal, block_q, block_k)
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, seg_r, seg_c, o, lse)


def _flash_seg_bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, seg_r, seg_c, o, lse = res
    dq, dk, dv = _bwd_impl(scale, causal, block_q, block_k, q, k, v, o, lse,
                           g, seg=(seg_r, seg_c))
    # integer operands take symbolic-zero (float0) cotangents
    dseg_r = np.zeros(seg_r.shape, jax.dtypes.float0)
    dseg_c = np.zeros(seg_c.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg_r, dseg_c


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


def flash_attention(q, k, v, *, causal: bool = True, scale: float = None,
                    segment_ids=None, block_q: int = None,
                    block_k: int = None, autotune: bool = None):
    """Blockwise attention over ``[batch, seq, heads, head_dim]`` inputs.

    Memory is O(seq) per program instead of O(seq^2); the [T, T] score matrix
    only ever exists one [block_q, block_k] tile at a time in VMEM.

    ``segment_ids`` (``[batch, seq]`` int, 0 = padding) restricts attention
    to *causal AND same-segment* for packed-sequence batches
    (``deepspeed_tpu/data/``): position i attends j iff ``j <= i`` and
    ``seg[i] == seg[j]``, which makes the packed forward/backward exact vs
    per-document unpacked attention (docs/data.md).

    ``block_q``/``block_k`` default to the shape-tuned resolution in
    ``ops/pallas/autotune.py`` (disk cache -> pretuned table -> optional
    live benchmark gated by ``autotune``/``DS_TPU_FLASH_AUTOTUNE`` -> the
    historical want-512 divisor heuristic); pass them explicitly to pin.
    """
    b, t, h, d = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    if block_q is None or block_k is None:
        from deepspeed_tpu.ops.pallas.autotune import get_flash_blocks

        tuned_q, tuned_k = get_flash_blocks(
            t, d, q.dtype, causal, autotune=autotune)
        block_q = tuned_q if block_q is None else block_q
        block_k = tuned_k if block_k is None else block_k
    block_q = _block(t, block_q)
    block_k = _block(t, block_k)
    if segment_ids is None:
        of = _flash(q, k, v, float(scale), bool(causal), block_q, block_k)
    else:
        if segment_ids.shape != (b, t):
            raise ValueError(
                f"segment_ids must be [batch, seq] = {(b, t)}, got "
                f"{segment_ids.shape}")
        # head-replicated [b*h, t] matches the kernels' batch-major
        # flattening (program i = b_idx * h + h_idx)
        segf = jnp.repeat(segment_ids.astype(jnp.int32), h, axis=0)
        seg_r = jnp.broadcast_to(segf[:, :, None], (b * h, t, LSE_LANES))
        seg_c = jnp.broadcast_to(segf[:, None, :], (b * h, LSE_LANES, t))
        of = _flash_seg(q, k, v, seg_r, seg_c, float(scale), bool(causal),
                        block_q, block_k)
    return of.reshape(b, h, t, d).transpose(0, 2, 1, 3)
