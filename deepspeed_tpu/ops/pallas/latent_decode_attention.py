"""The absorbed form of latent attention (models/latent_attention.py) for
one query token per lane, as one Pallas TPU kernel that reads, for each
lane, only the position blocks the lane holds, each block once.

In the absorbed form all ``H`` query heads of a lane attend over ONE key a
position, kept in two leaves of unlike width (the latent ``c_kv``,
``kv_rank`` wide, and the rotary key, ``rope_dim`` wide), and the value IS
the key's first part, the latent:

    score_h(s) = (q_lat_h . c_kv(s) + q_rope_h . k_rope(s)) * scale
    o_lat_h    = sum_s softmax_s(score_h)(s) c_kv(s)

so a block of latents fetched for the scores is the block the
probabilities meet: each live latent crosses HBM once a layer. The einsum
path reads every position of every lane twice (scores, weighted sum)
whatever is live and passes float32 scores ``[B, H, S]`` through memory
around the softmax; here scores, running maximum, sum and accumulator stay
on the chip in float32 (online softmax across a lane's blocks), and the
probabilities meet the latents in the cache's dtype, as on the einsum
path.

The grid is ``ops/pallas/decode_attention.py``'s: one step a (lane, live
block), ``live_blocks`` saying which blocks of a lane hold a visible row
and ``work_items`` laying the lanes' blocks end to end, the item's lane
and block read from prefetched scalars by the index maps, the grid's
length a run-time value. The body is another (no per-head mask, one key in
two leaves, no second leaf of values), which is why it is another kernel.

**The leaves are read where they lie**: the whole stacked ``[layers, B, S,
kv_rank]`` and ``[layers, B, S, rope_dim]``, indexed at ``layer`` (a
prefetched scalar, static or traced) by the index map; no layer's slice is
materialised. The rotary leaf is handed over as ``[layers, B, rope_dim,
S]``: the TPU lays out an array whose last dimension is narrower than its
128 lanes with the dimension before it along the lanes (here the
positions: ``{2,3,1,0}``, no padding), so that view moves no byte there
and a block of it is the ``[rope_dim, block]`` right-hand side of a plain
matmul. Asked for as it is declared, every layer's call copied the whole
leaf into the padded row-major form first (seen in the decode program
compiled for a described v5e: 0.96 GB of temporaries).

**What is the same for every layer of a step is made once a step**
(:func:`step_plan`): the first valid row and the clamped clock of each
lane, the work items, and the mask ``valid & (position <= clock)`` as
float32 flags. The layers' calls share it. A layer whose rows are its own
(:func:`mask_plan`: a window kind's ring, 64 heads over 1,024 + 64 at
dots3's widths, or the rows an indexer chose of a dense latent leaf) makes
its plan from its mask; the body takes any head count, rank and rotary
width that its blocks fit beside.

A block need not divide the cache's length: the last block of a cache is
then ragged, its rows past the end masked and their (unfetched) values
zeroed before the probabilities meet them. That is what lets a cache of
2,944 = 23 x 128 positions be walked in blocks of 512
(:func:`block_positions`).

On every backend but the TPU the kernel runs in Pallas interpreter mode
(``ops/pallas/common.py``).
"""

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import struct
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import decode_attention as _per_head
from deepspeed_tpu.ops.pallas.common import NEG_INF, interpret as _interpret

# the kernel's name in a profiler trace and in the lowered HLO
KERNEL_NAME = "mla_decode_attn"
_LANE = 128


def block_positions(n_positions, kv_rank, itemsize):
    """Positions in a block, from the shapes a call sees: as many whole
    128s as keep a block of latents within the per-head kernel's
    ``_BLOCK_BYTES`` (512 positions of 512 in bf16), or the whole length
    of a cache no longer than that. Measured on the v5e (PERF.md, PR 40)
    over 256 lanes as the latent serve cell's, 128 heads over 512 + 64 in
    bf16, ms a layer at 128 / 256 / 512 / 1,024 positions a block: 1.50 /
    1.05 / 0.84 / 0.82 (the einsums: 5.30). A grid step costs ~0.55 us
    whatever its block and ~0.23 us more for every 128 positions, so the
    coarser block wins although it fetches more dead rows (1.11 / 1.19 /
    1.32 / 1.59 of the live ones); past 512 the two cancel."""
    want = max(_LANE, _per_head._BLOCK_BYTES // (kv_rank * itemsize)
               // _LANE * _LANE)
    return min(want, n_positions)


@struct.dataclass
class StepPlan:
    """What one decode step's calls of the kernel share (one a layer)."""
    count: Any        # scalar: grid steps, one a (lane, live block)
    item_lane: Any    # [B * n_blocks] the lane of each item
    item_block: Any   # [B * n_blocks] the block of each item
    visible: Any      # [B, 1, S] float32: valid & (position <= clock)
    # static: positions in a block
    block: int = struct.field(pytree_node=False)


def step_plan(valid, index, block: int) -> StepPlan:
    """The grid and the mask of a decode step whose lanes' queries sit at
    ``index`` ``[B]`` (the row this step writes, which is visible) over
    ``valid`` ``[B, S]``, this step's row included, in blocks of ``block``
    positions."""
    S = valid.shape[1]
    clock = jnp.minimum(index, S - 1).astype(jnp.int32)
    first = jnp.argmax(valid, axis=1).astype(jnp.int32)
    count, item_lane, item_block = _per_head.work_items(
        first, clock, block, -(-S // block))
    visible = valid & (jnp.arange(S, dtype=jnp.int32)[None, :]
                       <= clock[:, None])
    return StepPlan(count, item_lane, item_block,
                    visible.astype(jnp.float32)[:, None, :], block)


def mask_plan(visible, block: int) -> StepPlan:
    """The grid and the mask of a step whose lanes each see the rows
    ``visible`` ``[B, S]`` marks, wherever they lie: a window layer's ring
    (rows in no order of position: ``valid &`` what the window holds, by
    ``slot_pos``) or the set an indexer chose. A lane's blocks run from its
    first visible row to its last; a lane that sees nothing still names one
    block, masked whole."""
    S = visible.shape[1]
    first = jnp.argmax(visible, axis=1).astype(jnp.int32)
    last = jnp.where(jnp.any(visible, axis=1),
                     S - 1 - jnp.argmax(visible[:, ::-1], axis=1),
                     first).astype(jnp.int32)
    count, item_lane, item_block = _per_head.work_items(
        first, last, block, -(-S // block))
    return StepPlan(count, item_lane, item_block,
                    visible.astype(jnp.float32)[:, None, :], block)


def _kernel(layer_ref, lane_ref, blk_ref, ql_ref, qr_ref, lat_ref, rk_ref,
            vis_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, positions):
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

    lat = lat_ref[...]                          # [block, kv_rank]
    visible = vis_ref[...] > 0                  # [1, block]
    block = lat.shape[0]
    if positions % block:
        # the cache's last block ends inside the fetched tile: what lies
        # past the end is whatever the buffer held
        left = positions - blk_ref[i] * block   # rows of the cache from here
        visible &= jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) < left
        lat = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) < left,
            lat, jnp.zeros_like(lat))
    # operands stay in the cache's dtype (bf16 on the MXU's fast path),
    # the products are float32
    s = scale * (
        jax.lax.dot_general(ql_ref[...], lat, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + jnp.dot(qr_ref[...], rk_ref[...],     # [rope_dim, block]
                  preferred_element_type=jnp.float32))
    s = jnp.where(visible, s, NEG_INF)          # [Hp, block]
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # a row with nothing visible yet has m_new == NEG_INF and p == 1 on
    # every column: finite, and scaled to nothing (alpha == 0) by the
    # first block that holds a visible key
    p = jnp.exp(s - m_new[:, :1])
    alpha = jnp.exp(m - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    # the block fetched for the scores is the value too
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p.astype(lat.dtype), lat, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(closes)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


def latent_decode_attention(q_lat, q_rope, latent, rope_key, plan, layer=None,
                            *, scale):
    """Absorbed latent attention of one query token per lane.

    ``q_lat``: ``[B, H, kv_rank]`` (the query's content part through
    ``W_UK``), ``q_rope``: ``[B, H, rope_dim]`` (after rotary).
    ``latent`` / ``rope_key``: the stacked ``[layers, B, S, kv_rank]`` /
    ``[layers, B, S, rope_dim]`` leaves with ``layer`` the (static or
    traced) index of this call's layer, or one layer's ``[B, S, .]`` with
    ``layer`` None. ``plan``: this step's :func:`step_plan`. Returns
    ``o_lat`` ``[B, H, kv_rank]`` in ``q_lat``'s dtype: softmax over the
    plan's visible positions of ``(q_lat . c_kv + q_rope . k_rope) *
    scale``, times ``c_kv``. A lane with nothing visible gets finite
    numbers that mean nothing."""
    if layer is None:
        latent, rope_key, layer = latent[None], rope_key[None], 0
    _, B, S, r = latent.shape
    H, dr = q_rope.shape[1:]
    block = plan.block
    # query rows in whole (16, 128) tiles; the rows past H are nobody's
    Hp = -(-H // 16) * 16
    if Hp != H:
        q_lat = jnp.pad(q_lat, ((0, 0), (0, Hp - H), (0, 0)))
        q_rope = jnp.pad(q_rope, ((0, 0), (0, Hp - H), (0, 0)))

    def latent_map(i, layer_ref, lane_ref, blk_ref):
        return layer_ref[0], lane_ref[i], blk_ref[i], 0

    def rope_key_map(i, layer_ref, lane_ref, blk_ref):
        return layer_ref[0], lane_ref[i], 0, blk_ref[i]

    def visible_map(i, layer_ref, lane_ref, blk_ref):
        return lane_ref[i], 0, blk_ref[i]

    def lane_map(i, layer_ref, lane_ref, blk_ref):
        return lane_ref[i], 0, 0

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, positions=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(plan.count,),
            in_specs=[
                pl.BlockSpec((None, Hp, r), lane_map),
                pl.BlockSpec((None, Hp, dr), lane_map),
                pl.BlockSpec((None, None, block, r), latent_map),
                pl.BlockSpec((None, None, dr, block), rope_key_map),
                pl.BlockSpec((None, 1, block), visible_map),
            ],
            out_specs=pl.BlockSpec((None, Hp, r), lane_map),
            scratch_shapes=[
                pltpu.VMEM((Hp, _LANE), jnp.float32),
                pltpu.VMEM((Hp, _LANE), jnp.float32),
                pltpu.VMEM((Hp, r), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hp, r), q_lat.dtype),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), plan.item_lane,
      plan.item_block, q_lat, q_rope, latent, jnp.swapaxes(rope_key, 2, 3),
      plan.visible)
    return out[:, :H] if Hp != H else out
