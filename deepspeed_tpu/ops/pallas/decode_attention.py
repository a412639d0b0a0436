"""Decode attention over a dense KV cache as one Pallas TPU kernel that
reads, for each lane, only the position blocks the lane holds.

A decode step has one query token per lane; its keys are the lane's cached
rows ``valid & (position <= clock)``. In a serving batch most of the cache is
dead at any moment: rows past a lane's clock, left padding before its first
token, lanes that hold no request. The einsum path reads every position of
every lane twice a step (``QK^T``, ``PV``) whatever is live; this kernel's
grid has one step for each (lane, live block): ``live_blocks`` says which
blocks of a lane hold a visible row, ``work_items`` lays the lanes' blocks
end to end, and the index maps read the item's lane and block from
prefetched scalars, so a block outside every lane's range is never named
and no DMA fetches it. The grid's length is a run-time value (the lanes'
clocks are), the program one specialisation. Inside a fetched block the
mask is still ``valid & (position <= clock)``, so every lane gets what the
einsum path gives it.

A grid over (lane, every block) with the block index clamped into the
lane's range, which skips the arithmetic of a dead step and re-names the
resident block, was measured first (PERF.md, PR 34): a skipped step still
cost 0.35-0.9 us, and a lane's first block could not be fetched under the
skipped steps before it: 116 us a layer against 85 for the same lanes.

**The leaf is read where it lies.** Input is the whole stacked
``[n_layer, B, S, Hkv, D]`` leaf the layer loop carries (or one layer's
``[B, S, Hkv, D]``), indexed at ``layer`` by the index map: no per-layer
slice is materialised. It is viewed as ``[n_layer, B, S * Hkv, D]``, which
moves no byte (the last two dimensions of the tiled layout are ``(Hkv, D)``
before and ``(S * Hkv, D)`` after, row for row), so a block of ``block``
positions is a plain ``[block * Hkv, D]`` matrix whose row ``p * Hkv + h`` is
head ``h`` of position ``p``.

**Full heads and grouped heads in one body.** All ``H = Hkv * G`` query heads
of a lane meet the block in ONE matmul, ``[H, D] x [block * Hkv, D]^T``, and a
column ``p * Hkv + h`` counts for query head ``r`` only where ``h == r // G``:
the other columns are masked like dead positions, so the second matmul,
``[H, block * Hkv] x [block * Hkv, D]``, sums each head's own rows. That
spends ``Hkv`` times the arithmetic the heads need, on a step that is bound
by the bytes it reads, and needs no relayout of the block. Scores, running
maximum, sum and accumulator are float32 (online softmax across blocks);
probabilities meet ``V`` in the cache's dtype, as on the einsum path.

Not taken into the kernel, and left on the einsum path by the caller
(``models/transformer_lm.py``): T > 1 (prefill, chunked continuation,
verification), a window's ring cache, int8 KV storage and ALiBi. A second
caller, ops/indexed_attention.py, hands ``valid & chosen`` as ``valid``.

On every backend but the TPU the kernel runs in Pallas interpreter mode
(``ops/pallas/common.py``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import NEG_INF, interpret as _interpret

# the kernel's name in a profiler trace and in the lowered HLO
KERNEL_NAME = "decode_attn"
# a block of keys is at most this many bytes. Measured on the v5e (PERF.md,
# PR 34) over lanes as the closed serve cell's: 16 heads of 128 in bf16 took
# 84.9 / 96.0 / 118.9 us a layer at 128 / 256 / 512 positions a block (the
# finer block skips more), and no block size mattered with every position
# live (195.8 / 196.0 / 196.9)
_BLOCK_BYTES = 1 << 19
_LANE = 128


def live_blocks(first, clock, block):
    """``(lo, hi)``: the position blocks, both ends included, that a decode
    step reads of a lane whose first valid position is ``first`` and whose
    query sits at position ``clock`` (the row this step wrote). Every
    visible position ``valid & (position <= clock)`` lies in one of them.
    A lane with nothing visible (``first > clock``) still names one block,
    ``hi``, masked whole: every lane has an output to write. The single
    statement of what is read: ``work_items`` uses it on the device, the
    scheduler on the clocks it keeps on the host
    (``kv_blocks_read_share``). Scalars or arrays, NumPy or JAX."""
    minimum = jnp.minimum if isinstance(clock, jax.Array) else np.minimum
    hi = clock // block
    return minimum(first // block, hi), hi


def block_positions(n_positions, kv_heads, head_dim, itemsize):
    """Positions in a block, from the shapes a call sees: the largest
    multiple of 128 that divides the cache's length and keeps a block of
    keys within ``_BLOCK_BYTES`` (128 for 16 heads of 128 in bf16 over
    1,024 positions; 128 for 4 KV heads over 1,408 = 11 x 128); the whole
    length where no multiple of 128 divides it (small test models)."""
    want = max(_LANE, _BLOCK_BYTES // (kv_heads * head_dim * itemsize))
    fits = [b for b in range(_LANE, min(want, n_positions) + 1, _LANE)
            if n_positions % b == 0]
    return fits[-1] if fits else n_positions


def work_items(first, clock, block, n_blocks):
    """The kernel's grid, one step a (lane, live block): ``(count, lane of
    each item, block of each item)`` for ``[B]`` vectors of first valid
    positions and (clamped) clocks, lanes in order and each lane's blocks
    ``live_blocks`` in order. The two item vectors have ``B * n_blocks``
    places (every block of every lane live); past ``count`` they hold
    places no grid step visits."""
    B = first.shape[0]
    lo, hi = live_blocks(first, clock, block)
    counts = hi - lo + 1
    # a running sum as a masked sum: XLA's expansion of cumsum leaves
    # operations without an op_name, which the scope table cannot place
    ends = jnp.sum(jnp.where(np.tri(B, dtype=bool), counts[None, :], 0),
                   axis=1)
    # item i is lane b's where starts[b] <= i < ends[b]: lanes down the
    # rows, items along the lanes, everything one elementwise pass and a
    # sum over the rows (a gather ``lo[lane]`` becomes a chain of selects)
    item = jnp.arange(B * n_blocks, dtype=jnp.int32)[None, :]
    starts = (ends - counts)[:, None]
    mine = (item >= starts) & (item < ends[:, None])

    def of_lane(per_lane):
        return jnp.sum(jnp.where(mine, per_lane, 0), axis=0)

    lane = of_lane(jnp.arange(B, dtype=jnp.int32)[:, None])
    blk = of_lane(lo[:, None] - starts) + item[0]
    return ends[-1], lane.astype(jnp.int32), \
        jnp.clip(blk, 0, n_blocks - 1).astype(jnp.int32)


def _valid_rows(valid, kv_heads):
    """``[B, S]`` booleans -> ``[B, 1, S * Hkv]`` float32: one flag a cache
    ROW, position ``p``'s once for each of its ``Hkv`` heads. Where ``Hkv``
    divides 128 the repeat is a product with a 0/1 matrix that lays 128 //
    Hkv positions over 128 lanes, whose result is the flat layout already
    (a plain ``repeat`` makes a ``[B, S, Hkv]`` array of 16-wide rows first
    and then relays it out: 0.28 s of a 7.7 s trace, PERF.md, PR 34)."""
    B, S = valid.shape
    flags = valid.astype(jnp.float32)
    per = _LANE // kv_heads if _LANE % kv_heads == 0 else 0
    if not per or S % per:
        return jnp.repeat(flags, kv_heads, axis=1)[:, None, :]
    spread = (np.arange(_LANE)[None, :] // kv_heads
              == np.arange(per)[:, None]).astype(np.float32)
    return jnp.einsum("bcj,jl->bcl", flags.reshape(B, S // per, per),
                      spread).reshape(B, 1, S * kv_heads)


def _kernel(layer_ref, lane_ref, blk_ref, clock_ref, q_ref, k_ref, v_ref,
            valid_ref, head_ref, pos_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block, scale):
    del layer_ref  # the index maps' alone
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    lane = lane_ref[i]
    opens = (i == 0) | (lane_ref[jnp.maximum(i - 1, 0)] != lane)
    closes = (i == last) | (lane_ref[jnp.minimum(i + 1, last)] != lane)

    @pl.when(opens)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k, v = k_ref[...], v_ref[...]               # [block * Hkv, D]
    # operands stay in the cache's dtype (bf16 on the MXU's fast path),
    # the product is float32
    s = scale * jax.lax.dot_general(
        q_ref[...], k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # [Hp, block * Hkv]
    visible = ((head_ref[...] > 0)
               & (pos_ref[...] <= clock_ref[lane] - blk_ref[i] * block)
               & (valid_ref[...] > 0))
    s = jnp.where(visible, s, NEG_INF)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # a row with nothing visible yet has m_new == NEG_INF and p == 1 on
    # every column: finite, and scaled to nothing (alpha == 0) by the
    # first block that holds a visible key
    p = jnp.exp(s - m_new[:, :1])
    alpha = jnp.exp(m - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(closes)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, valid, clock, layer=None, *,
                     block=None, scale=None):
    """Attention of one query token per lane over a dense KV cache.

    ``q``: ``[B, H, D]``. ``k_cache`` / ``v_cache``: the stacked
    ``[n_layer, B, S, Hkv, D]`` leaves with ``layer`` the (traced) index
    of this call's layer, or one layer's ``[B, S, Hkv, D]`` with ``layer``
    None; ``H`` is a multiple of ``Hkv`` and query head ``r`` reads KV head
    ``r // (H // Hkv)``. ``valid``: ``[B, S]`` booleans, this layer's.
    ``clock``: ``[B]``, the position of each lane's query: the row this
    step wrote, which is visible. Returns ``[B, H, D]`` in ``q``'s dtype:
    softmax over ``valid & (position <= clock)`` of ``q . k / sqrt(D)``,
    times ``v``. A lane with nothing visible gets finite numbers that
    mean nothing. ``block``: positions in a block, dividing ``S`` (the
    model passes its ``decode_attention_block``); left out it is
    ``block_positions`` of the shapes. ``scale``: the factor on the
    scores where it is not ``1 / sqrt(D)`` (heads narrower than ``D``
    stored side by side in one row: models/transformer_lm.py
    ``kv_lane_pack``)."""
    if layer is None:
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    n_layer, B, S, Hkv, D = k_cache.shape
    H = q.shape[1]
    G = H // Hkv
    if block is None:
        block = block_positions(S, Hkv, D, k_cache.dtype.itemsize)
    if S % block:
        raise ValueError(f"block {block} does not divide the cache's "
                         f"{S} positions")
    n_blocks, N = S // block, block * Hkv
    # query rows in whole (16, 128) tiles; the rows past H match no head
    Hp = -(-H // 16) * 16
    q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    kv_shape = (n_layer, B, S * Hkv, D)
    clock = jnp.minimum(clock, S - 1).astype(jnp.int32)
    first = jnp.argmax(valid, axis=1).astype(jnp.int32)
    valid_rows = _valid_rows(valid, Hkv)                # [B, 1, S*Hkv]
    col = np.arange(N)
    head_ok = (col[None, :] % Hkv == np.arange(Hp)[:, None] // G) \
        & (np.arange(Hp)[:, None] < H)
    col_pos = (col // Hkv)[None, :].astype(np.int32)

    count, item_lane, item_block = work_items(first, clock, block, n_blocks)

    def kv_map(i, layer_ref, lane_ref, blk_ref, clock_ref):
        return layer_ref[0], lane_ref[i], blk_ref[i], 0

    def valid_map(i, layer_ref, lane_ref, blk_ref, clock_ref):
        return lane_ref[i], 0, blk_ref[i]

    def lane_map(i, layer_ref, lane_ref, blk_ref, clock_ref):
        return lane_ref[i], 0, 0

    def fixed_map(i, *_):
        return 0, 0

    out = pl.pallas_call(
        functools.partial(_kernel, block=block,
                          scale=1.0 / np.sqrt(D) if scale is None else scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(count,),
            in_specs=[
                pl.BlockSpec((None, Hp, D), lane_map),
                pl.BlockSpec((None, None, N, D), kv_map),
                pl.BlockSpec((None, None, N, D), kv_map),
                pl.BlockSpec((None, 1, N), valid_map),
                pl.BlockSpec((Hp, N), fixed_map),
                pl.BlockSpec((1, N), fixed_map),
            ],
            out_specs=pl.BlockSpec((None, Hp, D), lane_map),
            scratch_shapes=[
                pltpu.VMEM((Hp, _LANE), jnp.float32),
                pltpu.VMEM((Hp, _LANE), jnp.float32),
                pltpu.VMEM((Hp, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hp, D), q.dtype),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), item_lane,
      item_block, clock, q, k_cache.reshape(kv_shape),
      v_cache.reshape(kv_shape), valid_rows,
      jnp.asarray(head_ok, jnp.float32), jnp.asarray(col_pos))
    return out[:, :H]
