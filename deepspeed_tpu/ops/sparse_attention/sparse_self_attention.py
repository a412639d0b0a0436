"""Splash-style block-sparse attention kernel + module.

TPU-native replacement for the reference Triton block-sparse path
(``ops/sparse_attention/matmul.py:212`` SDD/DSD/DDS, ``softmax.py:142``,
``sparse_self_attention.py:11``). Instead of materializing block-sparse
score matrices through three separate matmul/softmax launches, one Pallas
kernel streams only the ACTIVE key blocks of each query row (their indices
are static host-side data derived from the layout) with online-softmax
rescaling — the sparse analogue of flash attention, O(active_blocks) compute
and O(seq) memory.

Inputs are ``[batch, seq, heads, head_dim]``. The layout is a
``[heads, num_blocks, num_blocks]`` 0/1 array from a
:class:`~deepspeed_tpu.ops.sparse_attention.SparsityConfig`.
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas.common import (
    LSE_LANES,
    NEG_INF,
    interpret as _interpret,
)


def _pad_lanes(n: int, mult: int = 128) -> int:
    return ((n + mult - 1) // mult) * mult


# index-table rows are replicated over 8 sublanes so their [8, width] tiles
# satisfy TPU Mosaic lowering (same trick as the LSE_LANES rows)
IDX_SUBLANES = 8


def _build_index_tables(layout: np.ndarray, num_heads: int):
    """Static per-row active-block index lists, padded with -1.

    Returns ``(kidx [H, nq, IDX_SUBLANES, width_k], n_k)`` — active key
    blocks per query row and the true max active count bounding the kernel
    loop — and the analogous ``(qidx [H, nk, IDX_SUBLANES, width_q], n_q)``
    for the dkv iteration order. Table width is lane-padded to 128; only the
    first n_* entries are real.
    """
    h_layout, nq, nk = layout.shape
    if h_layout not in (1, num_heads):
        raise ValueError(
            f"layout has {h_layout} head layouts; expected 1 or {num_heads}")
    layout = np.broadcast_to(layout, (num_heads, nq, nk)) \
        if h_layout == 1 else layout

    def tables(mat_rows):
        counts = mat_rows.sum(axis=-1)
        n_iter = max(int(counts.max()), 1)
        width = _pad_lanes(n_iter, 128)
        out = np.full((num_heads, mat_rows.shape[1], width), -1,
                      dtype=np.int32)
        for h in range(num_heads):
            for r in range(mat_rows.shape[1]):
                idx = np.nonzero(mat_rows[h, r])[0]
                out[h, r, :len(idx)] = idx
        out = np.repeat(out[:, :, None, :], IDX_SUBLANES, axis=2)
        return out, n_iter

    kidx, n_k = tables(layout)
    qidx, n_q = tables(layout.transpose(0, 2, 1))
    return kidx, n_k, qidx, n_q


def _select_idx(row, a, width):
    """Scalar row[a] from a [1, width] vector without dynamic lane indexing."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return jnp.sum(jnp.where(lane == a, row, 0))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, kidx_ref, o_ref, lse_ref, *, scale,
                causal, block, width_k, n_k):
    bq, d = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale
    row = kidx_ref[...][0:1, :]  # [1, width_k]

    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 0)

    def body(a, carry):
        m, l, acc = carry
        j = _select_idx(row, a, width_k)
        valid = j >= 0
        jc = jnp.maximum(j, 0)
        k_blk = k_ref[pl.ds(jc * block, block), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(jc * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            k_pos = jc * block + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # rows with no visible key yet (m_new still -inf) must contribute
        # nothing: exp(-inf - -inf) would be 1, leaking masked blocks
        p = jnp.where(m_new > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m, l, acc))
    l_safe = jnp.where(l == 0, 1.0, l)
    o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
    lse = jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF)
    lse_ref[...] = jnp.broadcast_to(lse, (bq, LSE_LANES))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kidx_ref,
               dq_ref, *, scale, causal, block, width_k, n_k):
    bq, d = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...][:, :1]
    delta = delta_ref[...][:, :1]
    row = kidx_ref[...][0:1, :]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 0)
    dq = jnp.zeros((bq, d), jnp.float32)

    def body(a, dq):
        j = _select_idx(row, a, width_k)
        valid = j >= 0
        jc = jnp.maximum(j, 0)
        k_blk = k_ref[pl.ds(jc * block, block), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(jc * block, block), :].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            k_pos = jc * block + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.where(lse > 0.5 * NEG_INF, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + scale * jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, n_k, body, dq)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qidx_ref,
                dk_ref, dv_ref, *, scale, causal, block, width_q, n_q):
    bk, d = k_ref.shape
    ki = pl.program_id(1)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    row = qidx_ref[...][0:1, :]
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (block, bk), 1)
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)

    def body(a, carry):
        dk, dv = carry
        i = _select_idx(row, a, width_q)
        valid = i >= 0
        ic = jnp.maximum(i, 0)
        q_blk = q_ref[pl.ds(ic * block, block), :].astype(jnp.float32)
        do_blk = do_ref[pl.ds(ic * block, block), :].astype(jnp.float32)
        lse_blk = lse_ref[pl.ds(ic * block, block), :][:, :1]
        delta_blk = delta_ref[pl.ds(ic * block, block), :][:, :1]
        s = scale * jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            q_pos = ic * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, bk), 0)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.where(lse_blk > 0.5 * NEG_INF, jnp.exp(s - lse_blk), 0.0)
        dv = dv + jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk)
        dk = dk + scale * jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(0, n_q, body, (dk, dv))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# op factory (tables are trace-time constants; cached per layout, bounded)
# ---------------------------------------------------------------------------
_OP_CACHE = collections.OrderedDict()
_OP_CACHE_MAX = 64


def _build_op(layout, num_heads, scale, causal, block):
    kidx, n_k, qidx, n_q = _build_index_tables(layout, num_heads)
    h, nq, _, width_k = kidx.shape
    _, nk, _, width_q = qidx.shape
    # keep the index tables as NUMPY in the closure: ops are cached across
    # traces, and a jnp conversion done while some jit is tracing would bake
    # that trace's tracer into the cache (leaks into every later trace)

    def fwd(q, k, v):
        b, t, heads, d = q.shape
        bh = b * heads

        def flat(x):
            return x.transpose(0, 2, 1, 3).reshape(bh, t, d)

        o, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, causal=causal,
                              block=block, width_k=width_k, n_k=n_k),
            grid=(bh, nq),
            in_specs=[
                pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, None, IDX_SUBLANES, width_k),
                             lambda i, j: (i % h, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block, LSE_LANES),
                             lambda i, j: (i, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                jax.ShapeDtypeStruct((bh, t, LSE_LANES), jnp.float32),
            ],
            interpret=_interpret(),
            name="sparse_flash_fwd",
        )(flat(q), flat(k), flat(v), jnp.asarray(kidx))
        return o, lse

    @jax.custom_vjp
    def op(q, k, v):
        b, t, heads, d = q.shape
        o, _ = fwd(q, k, v)
        return o.reshape(b, heads, t, d).transpose(0, 2, 1, 3)

    def op_fwd(q, k, v):
        b, t, heads, d = q.shape
        o, lse = fwd(q, k, v)
        return (o.reshape(b, heads, t, d).transpose(0, 2, 1, 3),
                (q, k, v, o, lse))

    def op_bwd(res, g):
        q, k, v, of, lse = res
        b, t, heads, d = q.shape
        bh = b * heads

        def flat(x):
            return x.transpose(0, 2, 1, 3).reshape(bh, t, d)

        qf, kf, vf = map(flat, (q, k, v))
        dof = flat(g)
        delta = jnp.sum(of.astype(jnp.float32) * dof.astype(jnp.float32),
                        axis=-1)
        delta = jnp.broadcast_to(delta[..., None],
                                 delta.shape + (LSE_LANES,))

        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal,
                              block=block, width_k=width_k, n_k=n_k),
            grid=(bh, nq),
            in_specs=[
                pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block, LSE_LANES),
                             lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block, LSE_LANES),
                             lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, None, IDX_SUBLANES, width_k),
                             lambda i, j: (i % h, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            interpret=_interpret(),
            name="sparse_flash_bwd_dq",
        )(qf, kf, vf, dof, lse, delta, jnp.asarray(kidx))

        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, causal=causal,
                              block=block, width_q=width_q, n_q=n_q),
            grid=(bh, nk),
            in_specs=[
                pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, t, LSE_LANES), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, t, LSE_LANES), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, None, IDX_SUBLANES, width_q),
                             lambda i, j: (i % h, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            ],
            interpret=_interpret(),
            name="sparse_flash_bwd_dkv",
        )(qf, kf, vf, dof, lse, delta, jnp.asarray(qidx))

        def unflat(x):
            return x.reshape(b, heads, t, d).transpose(0, 2, 1, 3)

        return unflat(dq), unflat(dk), unflat(dv)

    op.defvjp(op_fwd, op_bwd)
    return op


def block_sparse_attention(q, k, v, layout, *, block: int,
                           causal: bool = False, scale: float = None):
    """Attention over ``[batch, seq, heads, head_dim]`` restricted to the
    active blocks of ``layout`` ([heads or 1, nq, nk] 0/1 array)."""
    b, t, heads, d = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    layout = np.asarray(layout)
    if t != layout.shape[1] * block:
        raise ValueError(
            f"layout covers {layout.shape[1] * block} positions, "
            f"inputs have {t}")
    key = (layout.tobytes(), layout.shape, str(layout.dtype), heads,
           float(scale), bool(causal), int(block))
    op = _OP_CACHE.get(key)
    if op is None:
        op = _build_op(layout, heads, float(scale), bool(causal), int(block))
        _OP_CACHE[key] = op
        while len(_OP_CACHE) > _OP_CACHE_MAX:
            _OP_CACHE.popitem(last=False)
    else:
        _OP_CACHE.move_to_end(key)
    return op(q, k, v)


def _partition_rows(counts: np.ndarray, nk: int):
    """Split query-block rows into a LIGHT set (narrow, gather path) and a
    HEAVY set (wide, dense path) minimizing total key-block work.

    Sparsity layouts are bimodal: banded rows touch a handful of blocks
    while "global" rows (BigBird/Longformer global tokens, fixed-pattern
    summary blocks) touch every block. A single gather table padded to the
    max row width silently degenerates to dense-everything, so pick the
    width cutoff that minimizes ``W_light * n_light + nk * n_heavy``,
    where ``nk`` is the TOTAL key-block count a dense-path row pays for.
    ``counts`` is the per-row active-block count, max-reduced over head
    layouts. Returns (light_rows, heavy_rows) as sorted index arrays.
    """
    nq = counts.shape[0]
    order = np.argsort(counts)           # ascending width
    sorted_counts = counts[order]
    best_cost, best_split = None, nq     # split = first heavy position
    for split in range(nq + 1):
        w_light = int(sorted_counts[split - 1]) if split else 0
        cost = w_light * split + (nq - split) * nk
        if best_cost is None or cost < best_cost:
            best_cost, best_split = cost, split
    light = np.sort(order[:best_split])
    heavy = np.sort(order[best_split:])
    return light, heavy


def _compact_index_tables(layout: np.ndarray, rows: np.ndarray):
    """Active key-block lists for the given rows, at their TRUE max width
    (no lane padding — the gather path's cost is linear in this width).
    ``layout`` is [hL, nq, nk]; returns ``idx [hL, len(rows), W]`` int32,
    -1 padded."""
    h_layout = layout.shape[0]
    width = max(int(layout[:, rows].sum(axis=-1).max()), 1) if len(rows) \
        else 1
    out = np.full((h_layout, len(rows), width), -1, dtype=np.int32)
    for h in range(h_layout):
        for j, r in enumerate(rows):
            nz = np.nonzero(layout[h, r])[0]
            out[h, j, :len(nz)] = nz
    return out


def gathered_blocksparse_attention(q, k, v, layout, *, block: int,
                                   causal: bool = False, scale: float = None,
                                   key_padding_mask=None, attn_mask=None,
                                   key_padding_mask_mode: str = "add",
                                   attn_mask_mode: str = "mul"):
    """XLA-native block-sparse attention: gather each query row's active
    K/V blocks with STATIC indices, then dense batched einsums over the
    gathered width; wide "global" rows are split off and computed densely.

    The TPU-first formulation of the reference's Triton SDD/DSD launches
    (``ops/sparse_attention/matmul.py:212``): on TPU the win comes from
    keeping the contraction on the MXU — a static gather feeding batched
    [block, W*block] matmuls runs at matmul rate, while a hand-scheduled
    streaming kernel is DMA-latency-bound. Autodiff works through it (XLA
    emits the gather transpose), element masks fold in by gathering mask
    blocks with the same indices, and the light/heavy row split keeps one
    BigBird global row from padding the whole table to dense.
    """
    b, t, heads, d = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    layout = np.asarray(layout)
    h_layout, nq, nk = layout.shape
    if h_layout not in (1, heads):
        raise ValueError(
            f"layout has {h_layout} head layouts; expected 1 or {heads}")
    if t != nq * block:
        raise ValueError(
            f"layout covers {nq * block} positions, inputs have {t}")

    counts = layout.sum(axis=-1).max(axis=0)          # [nq], max over heads
    light_rows, heavy_rows = _partition_rows(counts, nk)

    dtype = q.dtype
    neg = jnp.float32(NEG_INF)
    # block views: [B, H, n, block, D]
    qb = q.reshape(b, nq, block, heads, d).transpose(0, 3, 1, 2, 4)
    kb = k.reshape(b, nq, block, heads, d).transpose(0, 3, 1, 2, 4)
    vb = v.reshape(b, nq, block, heads, d).transpose(0, 3, 1, 2, 4)
    kpb = None
    if key_padding_mask is not None:
        kpb = jnp.asarray(key_padding_mask).reshape(b, nq, block)
    amp = None
    if attn_mask is not None:
        am = jnp.asarray(attn_mask)                   # [T, T]
        amp = am.reshape(nq, block, nq, block)

    def softmax_rows(s, row_shape):
        """Masked softmax over the flattened key axes, NaN-safe for rows
        whose every key is masked (possible under padding masks)."""
        sf = s.reshape(row_shape)
        m = jnp.max(sf, axis=-1, keepdims=True)
        e = jnp.exp(sf - jax.lax.stop_gradient(jnp.maximum(m, neg / 2)))
        denom = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        return (e / denom).astype(dtype).reshape(s.shape)

    def apply_kpm(s, kp):                              # kp: [B, ..., block]
        if key_padding_mask_mode == "mul":
            return jnp.where(kp > 0, s, neg)
        return s + kp.astype(jnp.float32)

    def apply_am(s, am_part):
        if attn_mask_mode == "mul":
            return jnp.where(am_part > 0, s, neg)
        return s + am_part.astype(jnp.float32)

    out_parts, out_rows = [], []

    if len(light_rows):
        idx = _compact_index_tables(layout, light_rows)  # [hL, nL, W] static
        w = idx.shape[-1]
        nl = len(light_rows)
        gidx = jnp.asarray(np.maximum(idx, 0))
        ql = qb[:, :, light_rows]                     # [B, H, nL, block, D]
        if h_layout == 1:
            kg = kb[:, :, gidx[0]]                    # [B, H, nL, W, block, D]
            vg = vb[:, :, gidx[0]]
        else:
            gather = jax.vmap(lambda xb_h, idx_h: xb_h[:, idx_h],
                              in_axes=(1, 0), out_axes=1)
            kg = gather(kb, gidx)
            vg = gather(vb, gidx)
        s = jnp.einsum("bhqid,bhqwjd->bhqiwj", ql, kg,
                       preferred_element_type=jnp.float32) * scale
        valid = idx >= 0                              # [hL, nL, W] static
        s = jnp.where(jnp.asarray(valid)[None, :, :, None, :, None], s, neg)
        if causal:
            q_pos = (light_rows[:, None] * block
                     + np.arange(block)[None, :])     # [nL, block]
            k_pos = idx[..., None] * block + np.arange(block)
            cm = (k_pos[:, :, None, :, :]
                  <= q_pos[None, :, :, None, None])   # [hL,nL,block,W,block]
            s = jnp.where(jnp.asarray(cm)[None], s, neg)
        if amp is not None:
            flat = amp.transpose(0, 2, 1, 3).reshape(nq * nq, block, block)
            pair = light_rows[None, :, None] * nq + np.maximum(idx, 0)
            am_g = flat[jnp.asarray(pair)]            # [hL,nL,W,block,block]
            s = apply_am(s, am_g.transpose(0, 1, 3, 2, 4)[None])
        if kpb is not None:
            if h_layout == 1:
                kp_g = kpb[:, gidx[0]][:, None]       # [B,1,nL,W,block]
            else:
                kp_g = jax.vmap(lambda idx_h: kpb[:, idx_h])(gidx)
                kp_g = kp_g.transpose(1, 0, 2, 3, 4)
            s = apply_kpm(s, kp_g[:, :, :, None])
        p = softmax_rows(s, (b, heads, nl, block, w * block))
        o = jnp.einsum("bhqiwj,bhqwjd->bhqid", p, vg)
        out_parts.append(o)
        out_rows.append(light_rows)

    if len(heavy_rows):
        nh = len(heavy_rows)
        qh = qb[:, :, heavy_rows]                     # [B, H, nH, block, D]
        s = jnp.einsum("bhrid,bhnjd->bhrinj", qh, kb,
                       preferred_element_type=jnp.float32) * scale
        row_mask = layout[:, heavy_rows].astype(bool)  # [hL, nH, nk] static
        s = jnp.where(jnp.asarray(row_mask)[None, :, :, None, :, None],
                      s, neg)
        if causal:
            q_pos = (heavy_rows[:, None] * block
                     + np.arange(block)[None, :])     # [nH, block]
            k_pos = (np.arange(nk)[:, None] * block
                     + np.arange(block)[None, :])     # [nk, block]
            cm = (k_pos[None, None, :, :]
                  <= q_pos[:, :, None, None])         # [nH, block, nk, block]
            s = jnp.where(jnp.asarray(cm)[None, None], s, neg)
        if amp is not None:
            am_h = amp[heavy_rows]                    # [nH, block, nq, block]
            s = apply_am(s, am_h[None, None])
        if kpb is not None:
            s = apply_kpm(s, kpb[:, None, None, None])
        p = softmax_rows(s, (b, heads, nh, block, nk * block))
        o = jnp.einsum("bhrinj,bhnjd->bhrid", p, vb)
        out_parts.append(o)
        out_rows.append(heavy_rows)

    o = out_parts[0] if len(out_parts) == 1 else \
        jnp.concatenate(out_parts, axis=2)
    order = np.concatenate(out_rows)
    if not np.array_equal(order, np.arange(nq)):
        o = jnp.take(o, jnp.asarray(np.argsort(order)), axis=2)
    return o.transpose(0, 2, 3, 1, 4).reshape(b, t, heads, d).astype(dtype)


def dense_blocksparse_attention(q, k, v, layout, *, block: int,
                                causal: bool = False, scale: float = None,
                                key_padding_mask=None, attn_mask=None,
                                key_padding_mask_mode: str = "add",
                                attn_mask_mode: str = "mul"):
    """XLA-native reference path: expands the block layout to an element mask.

    Used for correctness testing and for the mask-bearing cases
    (key_padding_mask / attn_mask, reference sparse_self_attention.py:103)
    the streaming kernel does not take.
    """
    b, t, heads, d = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    layout = np.asarray(layout)
    mask = np.kron(layout, np.ones((block, block), dtype=layout.dtype))
    mask = jnp.asarray(np.broadcast_to(mask, (heads,) + mask.shape[1:]))

    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    neg = jnp.float32(NEG_INF)
    s = jnp.where(mask[None] > 0, s, neg)
    if causal:
        cm = jnp.tril(jnp.ones((t, t), dtype=bool))
        s = jnp.where(cm[None, None], s, neg)
    if attn_mask is not None:
        am = jnp.asarray(attn_mask)
        if attn_mask_mode == "mul":
            s = jnp.where(am[None, None] > 0, s, neg)
        else:
            s = s + am[None, None]
    if key_padding_mask is not None:
        kpm = jnp.asarray(key_padding_mask)  # [b, t]
        if key_padding_mask_mode == "mul":
            s = jnp.where(kpm[:, None, None, :] > 0, s, neg)
        else:
            s = s + kpm[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


class SparseSelfAttention:
    """Module-level API of reference ``sparse_self_attention.py:11``.

    Computes scaled dot-product attention under the config's block-sparsity
    layout through the selected ``impl`` (see ``__init__``).
    """

    def __init__(self, sparsity_config, key_padding_mask_mode: str = "add",
                 attn_mask_mode: str = "mul", max_seq_length: int = 2048,
                 impl: str = None):
        self.sparsity_config = sparsity_config
        if key_padding_mask_mode not in ("add", "mul"):
            raise ValueError("key_padding_mask_mode must be 'add' or 'mul'")
        if attn_mask_mode not in ("add", "mul"):
            raise ValueError("attn_mask_mode must be 'add' or 'mul'")
        self.key_padding_mask_mode = key_padding_mask_mode
        self.attn_mask_mode = attn_mask_mode
        self.max_seq_length = max_seq_length
        # "gather" (default): static-index K/V block gather + dense batched
        # einsums — keeps the contraction on the MXU and measures ~an order
        # of magnitude faster than the streaming Pallas kernel on real
        # chips (benchmarks/sparse_attention_results.json). "pallas": the
        # streaming kernel (O(seq) memory, no gathered buffer — the choice
        # when W*block activations don't fit). "dense": masked full
        # attention, for testing.
        if impl is None:
            impl = getattr(sparsity_config, "kernel_impl", None) or "gather"
        if impl not in ("gather", "pallas", "dense"):
            raise ValueError("impl must be 'gather', 'pallas' or 'dense'")
        self.impl = impl
        self._layouts = {}

    def get_layout(self, seq_len: int) -> np.ndarray:
        if seq_len > self.max_seq_length:
            raise ValueError(
                f"seq_len {seq_len} exceeds max_seq_length "
                f"{self.max_seq_length}")
        if seq_len not in self._layouts:
            self._layouts[seq_len] = \
                self.sparsity_config.make_layout(seq_len)
        return self._layouts[seq_len]

    def __call__(self, query, key, value, key_padding_mask=None,
                 attn_mask=None, causal=None):
        b, t, h, d = query.shape
        layout = self.get_layout(t)
        if causal is None:
            causal = getattr(self.sparsity_config, "attention",
                             "bidirectional") == "unidirectional"
        block = self.sparsity_config.block
        if self.impl == "gather":
            return gathered_blocksparse_attention(
                query, key, value, layout, block=block, causal=causal,
                key_padding_mask=key_padding_mask, attn_mask=attn_mask,
                key_padding_mask_mode=self.key_padding_mask_mode,
                attn_mask_mode=self.attn_mask_mode)
        if self.impl == "pallas":
            if key_padding_mask is None and attn_mask is None:
                return block_sparse_attention(
                    query, key, value, layout, block=block, causal=causal)
            # the streaming kernel takes no element-level masks, and a
            # kernel entry point does not give way to the quadratic
            # masked-dense reference (O(T^2) scores at long seq)
            raise ValueError(
                "sparse_attention kernel='pallas' takes no element mask "
                "(key_padding_mask / attn_mask); use the default 'gather' "
                "impl for masked inputs")
        return dense_blocksparse_attention(
            query, key, value, layout, block=block,
            causal=causal, key_padding_mask=key_padding_mask,
            attn_mask=attn_mask,
            key_padding_mask_mode=self.key_padding_mask_mode,
            attn_mask_mode=self.attn_mask_mode)
