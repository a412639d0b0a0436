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

Schedule (:class:`KernelBlocks`, one per kernel). A program's strip of
scores (``block_q`` rows by all keys; for dK/dV ``block_k`` keys by all
rows) falls into tiles wholly under the causal diagonal, which a loop walks
with no mask arithmetic at all, and the square the diagonal crosses, which
is cut into ``granule``-wide slices that stop at the diagonal: only the
last ``granule x granule`` corner of each slice is masked, and nothing
above the diagonal but those corners' halves is computed. ``scale`` is
folded into q (dK/dV: into k) once a program, and into dq / dk once after
the loops. The fold costs one rounding: the scaled operand goes back to the
input dtype for the matrix unit, so where ``scale`` is no power of two
(head 128: 128 ** -0.5) every score carries one bf16 rounding more than
``scale * dot`` in float32 gave it. On the v5e the results stand 0.0030-
0.0037 of their norm from the kernels before PR 45 (lse: 0.005 at most) and
0.0023-0.0032 from float32 attention at 1,024 positions, where those stood
0.0019-0.0026 (PERF.md section 6, PR 45); ``chip_smoke.py`` holds them to
twice that. A strip of the whole sequence has no loop at all: on the v5e
that static schedule is what the kernels gain most from (PERF.md section
6, PR 45).

A window (``window``: key j for query i where ``0 <= i - j < window``) is
three kernels of their own (``window_flash_fwd``, ``window_flash_bwd_dq``,
``window_flash_bwd_dkv``; the section "under a window" below): the loop's
tiles are bounded on both sides, the square the window's edge crosses is
cut in ``granule`` slices as the diagonal's is, and the loop's operands
reach VMEM as the band a strip reads. With or without a window K and V may
hold fewer heads than q (grouped queries): a group's query heads read
their KV head where it lies, through the BlockSpec's index map.

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
  fwd and dq kernels, which walk the keys tile by tile, slice the whole
  row's ref along lanes (Mosaic lowers that for offsets it can prove
  128-aligned: ``fit_blocks(lane_aligned=True)`` keeps them so).

Masking uses the same finite ``NEG_INF`` as the causal path: a masked
score contributes ``exp(-1e30) == 0.0`` exactly to both softmax and its
gradient, so cross-segment leakage is zero, and pad rows (segment 0)
still see their own diagonal so no row is ever fully masked.
"""

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import (
    LSE_LANES,
    NEG_INF,
    interpret as _interpret,
    largest_divisor_block as _block,
)
from deepspeed_tpu.telemetry.bus import KIND_FLASH_PLAN, publish

# the kernels' names in a profiler trace and in the lowered HLO
# (``kernel_name`` of the tpu_custom_call): a reader tells forward from
# backward by these, not by the call's signature
KERNEL_FWD = "flash_fwd"
KERNEL_BWD_DQ = "flash_bwd_dq"
KERNEL_BWD_DKV = "flash_bwd_dkv"
KERNELS = (KERNEL_FWD, KERNEL_BWD_DQ, KERNEL_BWD_DKV)
# a launch under a window carries this before its kernel's name
# (``window_flash_fwd``, ...): other kernels, counted by other functions
WINDOW_PREFIX = "window_"


def kernel_name(kernel: str, window: Optional[int] = None) -> str:
    """The name a launch of ``kernel`` has in a trace and in the HLO."""
    return kernel if window is None else WINDOW_PREFIX + kernel

# past this much of blocks and tiles a call asks for more VMEM than the
# 16 MB a kernel may use unasked (a whole strip of 4,096 positions does)
_VMEM_UNASKED = 12 << 20
_VMEM_V5E = 128 << 20


def _vmem_asked() -> int:
    """Three quarters of the chip's VMEM: 96 MiB on the v5e, what the
    table's whole strips were measured under. A host with no TPU (a
    compile for a described chip) asks as the v5e does."""
    try:
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:  # no TPU here, or one Pallas has no entry for
        capacity = _VMEM_V5E
    return capacity * 3 // 4


class KernelBlocks(NamedTuple):
    """One kernel's schedule: the tile, and the width of the slices the
    diagonal square is cut into."""
    block_q: int
    block_k: int
    granule: int

    def strip(self, kernel: str) -> int:
        """The block a program of ``kernel`` owns (the other is its loop's
        tile): ``block_q`` rows, for dK/dV ``block_k`` keys."""
        return self.block_k if kernel == KERNEL_BWD_DKV else self.block_q


def _compiler_params(kernel, t, d, itemsize, blocks):
    """None, or the VMEM limit raised (to :func:`_vmem_asked`; blocks that
    need more than the chip has are the compiler's to refuse) where the
    blocks and six float32 temporaries of the largest tile need more than
    a kernel gets unasked.
    The blocks, double-buffered: the whole-sequence operands (K, V; for
    dK/dV q, do and the lse and delta rows, whose 8 lanes lie padded to
    128) and the strip's own (q, o and lse; q, do, dq and two rows; k, v,
    dk, dv)."""
    block_q, block_k, granule = blocks
    strip = blocks.strip(kernel)
    row, lanes = d * itemsize, 128 * 4
    need = 2 * {
        KERNEL_FWD: 2 * t * row + strip * (2 * row + lanes),
        KERNEL_BWD_DQ: 2 * t * row + strip * (3 * row + 2 * lanes),
        KERNEL_BWD_DKV: 2 * t * (row + lanes) + strip * 4 * row}[kernel]
    need += 24 * strip * max(
        granule, 0 if strip == t else block_q + block_k - strip)
    if need <= _VMEM_UNASKED:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_asked())


def fit_blocks(kernel: str, t: int, causal: bool, block_q: int, block_k: int,
               granule: Optional[int] = None,
               lane_aligned: bool = False,
               window: Optional[int] = None) -> KernelBlocks:
    """``wanted`` made a launch of ``kernel`` whose shapes are valid (its
    VMEM is :func:`_compiler_params`'s to ask for): blocks that divide
    ``t``; under the causal mask the loop's tile dividing the program's
    strip (so the diagonal square is the strip's own); a granule dividing
    the strip (256 or 128 where one divides it, else the strip whole: on
    the v5e slices of 128 leave least above the diagonal and feed the
    matrix unit worst, PERF.md section 6, PR 45). ``lane_aligned`` (segment
    ids: the key side's row of them is sliced along lanes) keeps blocks of
    a 128-aligned ``t`` multiples of 128. Under a ``window`` the strip
    divides the window too: a strip's window then starts at a strip's edge,
    and its lower edge is one square, cut as the diagonal's is."""
    block_q, block_k = _block(t, block_q), _block(t, block_k)
    if lane_aligned and t % 128 == 0:
        block_q, block_k = (
            b if b % 128 == 0 else 128 * _block(t // 128, max(1, b // 128))
            for b in (block_q, block_k))
    dkv = kernel == KERNEL_BWD_DKV
    if window is not None:
        if dkv:
            block_k = math.gcd(block_k, window)
        else:
            block_q = math.gcd(block_q, window)
    if causal:
        if dkv and block_k % block_q:
            block_q = math.gcd(block_q, block_k)
        elif not dkv and block_q % block_k:
            block_k = math.gcd(block_q, block_k)
    strip = block_k if dkv else block_q
    if not granule or strip % granule:
        granule = next((g for g in (256, 128) if strip % g == 0), strip)
    return KernelBlocks(block_q, block_k, granule)


def tile_counts(kernel: str, t: int, causal: bool, blocks: KernelBlocks,
                window: Optional[int] = None) -> Dict[str, float]:
    """Scores one head computes, needs (the causal half; under a ``window``
    the band's part of it) and runs mask arithmetic on, in tiles of
    ``block_q x block_k``."""
    bq, bk, g = blocks
    tile = float(bq * bk)
    if window is not None:
        # strips whose window reaches past the sequence's end (forward, dQ:
        # its start) are causal strips; the others hold the window's edge
        # square, cut as the diagonal's is, and the tiles between the two
        strip = blocks.strip(kernel)
        n, r = t // strip, strip // g
        square = g * g * r * (r + 1) / 2
        short, whole = window // strip, n - window // strip
        return {"tiles_computed": (
                    n * square + whole * (square + strip * (window - strip))
                    + strip * strip * short * (short - 1) / 2) / tile,
                "tiles_needed": window * (t - window / 2) / tile,
                "tiles_masked": (n + whole) * r * g * g / tile}
    if not causal:
        n = t * t / tile
        return {"tiles_computed": n, "tiles_needed": n, "tiles_masked": 0.0}
    strip = blocks.strip(kernel)
    step = bq + bk - strip
    n = t // strip
    interior = (strip // step) * n * (n - 1) / 2
    r = strip // g
    return {"tiles_computed": interior + n * g * g * r * (r + 1) / 2 / tile,
            "tiles_needed": t * t / 2 / tile,
            "tiles_masked": n * r * g * g / tile}


def _scaled(x, scale):
    """``x * scale`` in float32, back in x's dtype: once a program."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _lower_triangle(g):
    """``[g, g]`` bool: row i of a diagonal corner sees its column j."""
    return (jax.lax.broadcasted_iota(jnp.int32, (g, g), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (g, g), 1))


def _mask_corner(s, keep, axis):
    """Mask the corner of a slice that the diagonal crosses: its last
    ``keep.shape[1]`` columns (``axis`` 1) or first ``keep.shape[0]`` rows
    (``axis`` 0). ``keep`` None: an interior tile, left as it is."""
    if keep is None:
        return s
    if keep.shape == s.shape:
        return jnp.where(keep, s, NEG_INF)
    if axis == 1:
        w = s.shape[1] - keep.shape[1]
        return jnp.concatenate(
            [s[:, :w], jnp.where(keep, s[:, w:], NEG_INF)], axis=1)
    g = keep.shape[0]
    return jnp.concatenate(
        [jnp.where(keep, s[:g], NEG_INF), s[g:]], axis=0)


def _least(a, b):
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _most(a, b):
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _walk(n, body, init):
    """The loop over a strip's interior tiles; ``n`` None: a strip of the
    whole sequence under the causal mask has none, and no loop is emitted
    (its body's tile would still claim VMEM)."""
    return init if n is None else jax.lax.fori_loop(0, n, body, init)


def interior_tiles(kernel: str, t: int, causal: bool, blocks: KernelBlocks,
                   start, window: Optional[int] = None):
    """``(first, n)``: the tiles a strip's loop walks with no mask, ``n``
    of the loop's tile from position ``first`` of the loop's axis on, for
    the strip that starts at ``start`` (a program's offset, or a Python
    int). Under the causal mask they are the keys before the strip's own
    square (forward, dQ) or the rows after it (dK/dV); ``n`` None: a strip
    of the whole sequence has none, and its kernel no loop. Under a
    ``window`` they stop short of the square its far edge crosses: ``window
    - strip`` positions at most, fewer where the sequence ends first."""
    strip = blocks.strip(kernel)
    tile = blocks.block_q + blocks.block_k - strip
    if window is not None:
        if kernel == KERNEL_BWD_DKV:
            first = start + strip
            return first, (_least(start + window, t) - first) // tile
        first = _most(start + strip - window, 0)
        return first, (start - first) // tile
    if not causal:
        return 0, t // tile
    if strip == t:
        return 0, None
    if kernel == KERNEL_BWD_DKV:
        first = start + strip
        return first, (t - first) // tile
    return 0, start // tile


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, blocks, has_seg):
    if has_seg:
        sq_ref, sk_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    bq, block_k, granule = blocks
    t, d = k_ref.shape
    q0 = pl.program_id(1) * bq
    # tiles wholly under the diagonal (all of them without a mask)
    _, n_interior = interior_tiles(KERNEL_FWD, t, causal, blocks, q0)

    # keep MXU operands in the input dtype (bf16): f32xf32 dots fall off the
    # systolic array's fast path; accumulate in f32
    q = _scaled(q_ref[...], scale)  # [bq, d]
    if has_seg:
        q_seg = sq_ref[...][:, :1]  # [bq, 1]

    def tile(rows, carry, k0, width, keep):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(k0, width), :]
        v_blk = v_ref[pl.ds(k0, width), :]
        s = _mask_corner(_dot(q[rows], k_blk, (1, 1)), keep, 1)
        if has_seg:
            k_seg = sk_ref[:1, pl.ds(k0, width)]  # [1, width]
            s = jnp.where(q_seg[rows] == k_seg, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + _dot(p.astype(v_blk.dtype), v_blk, (1, 0))
        return m_new, l_new, acc_new

    def interior(j, carry):
        k0 = pl.multiple_of(j * block_k, block_k)
        return tile(slice(None), carry, k0, block_k, None)

    def finish(rows, carry):
        m, l, acc = carry
        o_ref[rows, :] = (acc / l).astype(o_ref.dtype)
        # lse carries 8 broadcast sublane copies to satisfy TPU tiling
        lse_ref[rows, :] = jnp.broadcast_to(m + jnp.log(l),
                                            (m.shape[0], LSE_LANES))

    carry = _walk(n_interior, interior,
                  (jnp.full((bq, 1), NEG_INF, jnp.float32),
                   jnp.zeros((bq, 1), jnp.float32),
                   jnp.zeros((bq, d), jnp.float32)))
    if not causal:
        finish(slice(None), carry)
        return
    # the diagonal square: slice r's rows see the square's columns up to
    # their own corner, and are done after it
    corner = _lower_triangle(granule)
    for r in range(bq // granule):
        rows = slice(r * granule, (r + 1) * granule)
        finish(rows, tile(rows, tuple(x[rows] for x in carry), q0,
                          (r + 1) * granule, corner))


def _specs(block, d, t, groups=1):
    """BlockSpecs of one kernel: a strip's block of a ``[bh, t, d]`` tensor,
    a whole one, the same two of a ``[bh, t, LSE_LANES]`` row tensor, and
    a whole ``[bh, LSE_LANES, t]`` column tensor. ``groups`` query heads to
    a KV head: the whole tensor (K, V) is then the ``[bh / groups, t, d]``
    one, read where it lies by every head of its group."""
    return (pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, t, d), (lambda i, j: (i, 0, 0)) if groups == 1
                         else (lambda i, j: (i // groups, 0, 0))),
            pl.BlockSpec((None, block, LSE_LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, t, LSE_LANES), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, LSE_LANES, t), lambda i, j: (i, 0, 0)))


# jitted: a step program traces each call a dozen times (forward, recomputed
# forward, backward, several programs), and a whole strip's unrolled slices
# are hundreds of operations to trace; jit's cache hands the later traces
# the first one's jaxpr (``setup_s``)
# (``interpret`` is among the keys: what ran interpreted on the CPU is not
# what a compile for a described chip may be handed)
_jit_call = functools.partial(
    jax.jit, static_argnames=("scale", "causal", "blocks", "interpret"))


@_jit_call
def _call_fwd(qf, kf, vf, seg, scale, causal, blocks, interpret=None):
    """``flash_fwd`` on ``[bh, t, d]`` operands: ``(o, lse)`` (K and V of
    ``[bh / groups, t, d]``: grouped queries)."""
    bh, t, d = qf.shape
    interpret = _interpret() if interpret is None else interpret
    strip, whole, strip_rows, _, whole_cols = _specs(
        blocks.block_q, d, t, bh // kf.shape[0])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          blocks=blocks, has_seg=seg is not None),
        grid=(bh, t // blocks.block_q),
        in_specs=[strip, whole, whole] + (
            [] if seg is None else [strip_rows, whole_cols]),
        out_specs=[strip, strip_rows],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), qf.dtype),
            jax.ShapeDtypeStruct((bh, t, LSE_LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(
            KERNEL_FWD, t, d, qf.dtype.itemsize, blocks),
        name=KERNEL_FWD,
    )(qf, kf, vf, *(seg or ()))


def _flat(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _fwd(q, k, v, seg, scale, causal, blocks):
    return _call_fwd(_flat(q), _flat(k), _flat(v), seg, scale, causal,
                     blocks, interpret=_interpret())


# ---------------------------------------------------------------------------
# backward (recompute with saved lse)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, blocks, has_seg):
    if has_seg:
        sq_ref, sk_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    bq, block_k, granule = blocks
    t, d = k_ref.shape
    q0 = pl.program_id(1) * bq
    _, n_interior = interior_tiles(KERNEL_BWD_DQ, t, causal, blocks, q0)

    q = _scaled(q_ref[...], scale)
    do = do_ref[...]
    lse = lse_ref[...][:, :1]
    delta = delta_ref[...][:, :1]
    if has_seg:
        q_seg = sq_ref[...][:, :1]  # [bq, 1]

    def tile(rows, dq, k0, width, keep):
        k_blk = k_ref[pl.ds(k0, width), :]
        v_blk = v_ref[pl.ds(k0, width), :]
        s = _mask_corner(_dot(q[rows], k_blk, (1, 1)), keep, 1)
        if has_seg:
            k_seg = sk_ref[:1, pl.ds(k0, width)]  # [1, width]
            s = jnp.where(q_seg[rows] == k_seg, s, NEG_INF)
        p = jnp.exp(s - lse[rows])
        dp = _dot(do[rows], v_blk, (1, 1))
        ds = (p * (dp - delta[rows])).astype(k_blk.dtype)
        return dq + _dot(ds, k_blk, (1, 0))

    def interior(j, dq):
        k0 = pl.multiple_of(j * block_k, block_k)
        return tile(slice(None), dq, k0, block_k, None)

    dq = _walk(n_interior, interior, jnp.zeros((bq, d), jnp.float32))
    if not causal:
        dq_ref[...] = (dq * scale).astype(dq_ref.dtype)
        return
    corner = _lower_triangle(granule)
    for r in range(bq // granule):
        rows = slice(r * granule, (r + 1) * granule)
        dq_r = tile(rows, dq[rows], q0, (r + 1) * granule, corner)
        dq_ref[rows, :] = (dq_r * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, blocks, has_seg):
    if has_seg:
        sr_ref, sc_ref, dk_ref, dv_ref = rest
    else:
        dk_ref, dv_ref = rest
    block_q, bk, granule = blocks
    t, d = q_ref.shape
    k0 = pl.program_id(1) * bk
    # q tiles wholly under the diagonal start where this strip's square ends
    first, n_interior = interior_tiles(KERNEL_BWD_DKV, t, causal, blocks, k0)

    k = _scaled(k_ref[...], scale)
    v = v_ref[...]
    if has_seg:
        k_seg = sc_ref[...][:1, :]  # [1, bk]

    def tile(cols, carry, q0, height, keep):
        dk, dv = carry
        q_blk = q_ref[pl.ds(q0, height), :]
        do_blk = do_ref[pl.ds(q0, height), :]
        lse_blk = lse_ref[pl.ds(q0, height), :1]
        delta_blk = delta_ref[pl.ds(q0, height), :1]
        s = _mask_corner(_dot(q_blk, k[cols], (1, 1)), keep, 0)
        if has_seg:
            q_seg = sr_ref[pl.ds(q0, height), :1]  # [height, 1]
            s = jnp.where(q_seg == k_seg[:, cols], s, NEG_INF)
        p = jnp.exp(s - lse_blk)
        dv = dv + _dot(p.astype(do_blk.dtype), do_blk, (0, 0))
        dp = _dot(do_blk, v[cols], (1, 1))
        ds = (p * (dp - delta_blk)).astype(q_blk.dtype)
        return dk + _dot(ds, q_blk, (0, 0)), dv

    def interior(i, carry):
        q0 = pl.multiple_of(first + i * block_q, block_q)
        return tile(slice(None), carry, q0, block_q, None)

    def finish(cols, carry):
        dk, dv = carry
        dk_ref[cols, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[cols, :] = dv.astype(dv_ref.dtype)

    carry = _walk(n_interior, interior,
                  (jnp.zeros((bk, d), jnp.float32),
                   jnp.zeros((bk, d), jnp.float32)))
    if not causal:
        finish(slice(None), carry)
        return
    # the diagonal square: slice c's columns are seen by the square's rows
    # from their own corner down
    corner = _lower_triangle(granule)
    for c in range(bk // granule):
        cols = slice(c * granule, (c + 1) * granule)
        finish(cols, tile(cols, tuple(x[cols] for x in carry),
                          k0 + c * granule, bk - c * granule, corner))


@_jit_call
def _call_dq(operands, seg, scale, causal, blocks, interpret=None):
    """``flash_bwd_dq`` on ``(q, k, v, do, lse, delta)``, flat: dq."""
    bh, t, d = operands[0].shape
    interpret = _interpret() if interpret is None else interpret
    strip, whole, strip_rows, _, whole_cols = _specs(
        blocks.block_q, d, t, bh // operands[1].shape[0])
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          blocks=blocks, has_seg=seg is not None),
        grid=(bh, t // blocks.block_q),
        in_specs=[strip, whole, whole, strip, strip_rows, strip_rows] + (
            [] if seg is None else [strip_rows, whole_cols]),
        out_specs=strip,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), operands[0].dtype),
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(
            KERNEL_BWD_DQ, t, d, operands[0].dtype.itemsize, blocks),
        name=KERNEL_BWD_DQ,
    )(*operands, *(seg or ()))


@_jit_call
def _call_dkv(operands, seg, scale, causal, blocks, interpret=None):
    """``flash_bwd_dkv`` on ``(q, k, v, do, lse, delta)``, flat: (dk, dv),
    a QUERY head's each (grouped queries: the group's sum is the caller's)."""
    bh, t, d = operands[0].shape
    interpret = _interpret() if interpret is None else interpret
    strip, whole, _, whole_rows, _ = _specs(blocks.block_k, d, t)
    groups = bh // operands[1].shape[0]
    kv_strip = strip if groups == 1 else pl.BlockSpec(
        (None, blocks.block_k, d), lambda i, j: (i // groups, j, 0))
    # dkv slices the segments' row layout by q tile in-kernel and takes its
    # own k block from the column layout
    seg_specs = [] if seg is None else [
        whole_rows, pl.BlockSpec((None, LSE_LANES, blocks.block_k),
                                 lambda i, j: (i, 0, j))]
    out = jax.ShapeDtypeStruct((bh, t, d), operands[0].dtype)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          blocks=blocks, has_seg=seg is not None),
        grid=(bh, t // blocks.block_k),
        in_specs=[whole, kv_strip, kv_strip, whole, whole_rows, whole_rows]
        + seg_specs,
        out_specs=[strip, strip],
        out_shape=[out, out],
        interpret=interpret,
        compiler_params=None if interpret else _compiler_params(
            KERNEL_BWD_DKV, t, d, operands[0].dtype.itemsize, blocks),
        name=KERNEL_BWD_DKV,
    )(*operands, *(seg or ()))


def row_delta(of, dof):
    """``sum(o * do)`` a row, in the rows' lane-broadcast layout."""
    delta = jnp.sum(of.astype(jnp.float32) * dof.astype(jnp.float32), axis=-1)
    return jnp.broadcast_to(delta[..., None], delta.shape + (LSE_LANES,))


def _bwd_impl(scale, causal, schedule, q, k, v, o, lse, do, seg=None):
    b, t, h, d = q.shape
    # o and do are already [bh, t, d] (the op's internal layout)
    operands = (_flat(q), _flat(k), _flat(v), do, lse, row_delta(o, do))
    dq = _call_dq(operands, seg, scale, causal, schedule[1],
                  interpret=_interpret())
    dk, dv = _call_dkv(operands, seg, scale, causal, schedule[2],
                       interpret=_interpret())

    def unflat(x):
        return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    return unflat(dq), unflat(dk), unflat(dv)


def _bwd(scale, causal, schedule, res, g):
    q, k, v, o, lse = res
    return _bwd_impl(scale, causal, schedule, q, k, v, o, lse, g)


# ---------------------------------------------------------------------------
# under a window: key j is seen by query i where 0 <= i - j < window
# ---------------------------------------------------------------------------
# A strip divides the window (``fit_blocks``), so the keys a strip of
# queries at ``q0`` sees are, in order: the square the window's edge
# crosses (at ``q0 - window``; row r of it sees the columns AFTER r: the
# diagonal's mask, negated), ``window - strip`` keys that every row sees
# (the loop's tiles), and the strip's own square under the diagonal. Both
# squares are cut in ``granule`` slices and only a slice's corner is
# masked. A strip before ``window`` has no edge yet and is a causal strip.
# dK/dV mirrors it: a strip of keys at ``k0`` is seen by its own square's
# rows, by ``window - strip`` rows whole, and by the square at ``k0 +
# window``, whose row r sees the columns after r; a strip whose window runs
# past the sequence's end is a causal strip.
# K and V (dK/dV: q, do, lse and delta) reach VMEM as the BAND a strip
# reads, ``window + strip`` positions, not whole: an element-indexed block
# that starts where the strip's window does, held to the sequence's ends.
def band_rows(kernel: str, blocks: KernelBlocks, window: int) -> int:
    """Positions of the loop's operands a strip under a window holds."""
    return window + blocks.strip(kernel)


def _band_start(kernel, t, blocks, window, start):
    """Where the band of the strip at ``start`` begins: at the window's
    start (forward, dQ) or at the strip (dK/dV), held inside the
    sequence."""
    if kernel == KERNEL_BWD_DKV:
        return _least(start, t - band_rows(kernel, blocks, window))
    return _most(start - window, 0)


def _band_spec(kernel, t, width, blocks, window, groups=1):
    """The element-indexed block of a ``[bh / groups, t, width]`` tensor
    that holds a strip's band."""
    strip = blocks.strip(kernel)
    return pl.BlockSpec(
        (None, pl.Element(band_rows(kernel, blocks, window)),
         pl.Element(width)),
        lambda i, j: (i // groups, pl.multiple_of(_band_start(
            kernel, t, blocks, window, j * strip), strip), 0))


def _upper_triangle(g):
    """``[g, g]`` bool: row i of a corner on the window's edge sees its
    column j."""
    return jnp.logical_not(_lower_triangle(g))


def _mask_edge(s, keep, axis):
    """Mask the corner of a slice that the window's edge crosses: its first
    ``keep.shape[1]`` columns (``axis`` 1) or last ``keep.shape[0]`` rows
    (``axis`` 0): where :func:`_mask_corner` masks the other end."""
    if keep.shape == s.shape:
        return jnp.where(keep, s, NEG_INF)
    if axis == 1:
        g = keep.shape[1]
        return jnp.concatenate(
            [jnp.where(keep, s[:, :g], NEG_INF), s[:, g:]], axis=1)
    h = s.shape[0] - keep.shape[0]
    return jnp.concatenate(
        [s[:h], jnp.where(keep, s[h:], NEG_INF)], axis=0)


def _by_slice(granule, n, carry, one):
    """``one(r, rows, carry's rows)`` for each ``granule`` slice of a
    strip's ``n`` rows (columns), put together again."""
    parts = []
    for r in range(n // granule):
        rows = slice(r * granule, (r + 1) * granule)
        parts.append(one(r, rows, tuple(x[rows] for x in carry)))
    return tuple(jnp.concatenate(xs, axis=0) for xs in zip(*parts))


def _window_strip(kernel, blocks, window, t):
    """Where a program's strip finds its squares in its band: ``(edge,
    diagonal, first, n)``: whether the window's edge square is there (at
    the band's start, forward and dQ; at ``window``, dK/dV), the offset of
    the strip's own square, and the loop's tiles between them
    (:func:`interior_tiles`, from the band's start)."""
    strip = blocks.strip(kernel)
    start = pl.program_id(1) * strip
    band = _band_start(kernel, t, blocks, window, start)
    first, n = interior_tiles(kernel, t, True, blocks, start, window)
    edge = start + window + strip <= t if kernel == KERNEL_BWD_DKV \
        else start >= window
    return edge, pl.multiple_of(start - band, strip), first - band, n


def _fwd_window_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, window,
                       blocks, t):
    bq, block_k, granule = blocks
    d = q_ref.shape[-1]
    edge, diagonal, first, n_interior = _window_strip(
        KERNEL_FWD, blocks, window, t)
    q = _scaled(q_ref[...], scale)

    def tile(rows, carry, k0, width, mask):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(k0, width), :]
        v_blk = v_ref[pl.ds(k0, width), :]
        s = mask(_dot(q[rows], k_blk, (1, 1)))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + _dot(p.astype(v_blk.dtype), v_blk, (1, 0))
        return m_new, l_new, acc_new

    def interior(j, carry):
        k0 = pl.multiple_of(first + j * block_k, block_k)
        return tile(slice(None), carry, k0, block_k, lambda s: s)

    upper, corner = _upper_triangle(granule), _lower_triangle(granule)
    # the window's edge: slice r's rows see the square's columns from their
    # own corner on (a row that sees none of them is put right by its
    # diagonal, as a row of another segment is)
    carry = jax.lax.cond(
        edge, lambda carry: _by_slice(
            granule, bq, carry, lambda r, rows, c: tile(
                rows, c, r * granule, bq - r * granule,
                lambda s: _mask_edge(s, upper, 1))),
        lambda carry: carry,
        (jnp.full((bq, 1), NEG_INF, jnp.float32),
         jnp.zeros((bq, 1), jnp.float32), jnp.zeros((bq, d), jnp.float32)))
    carry = jax.lax.fori_loop(0, n_interior, interior, carry)
    for r in range(bq // granule):
        rows = slice(r * granule, (r + 1) * granule)
        m, l, acc = tile(rows, tuple(x[rows] for x in carry), diagonal,
                         (r + 1) * granule,
                         lambda s: _mask_corner(s, corner, 1))
        o_ref[rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[rows, :] = jnp.broadcast_to(m + jnp.log(l),
                                            (m.shape[0], LSE_LANES))


def _bwd_dq_window_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, *, scale, window, blocks, t):
    bq, block_k, granule = blocks
    d = q_ref.shape[-1]
    edge, diagonal, first, n_interior = _window_strip(
        KERNEL_BWD_DQ, blocks, window, t)
    q = _scaled(q_ref[...], scale)
    do = do_ref[...]
    lse = lse_ref[...][:, :1]
    delta = delta_ref[...][:, :1]

    def tile(rows, dq, k0, width, mask):
        k_blk = k_ref[pl.ds(k0, width), :]
        v_blk = v_ref[pl.ds(k0, width), :]
        p = jnp.exp(mask(_dot(q[rows], k_blk, (1, 1))) - lse[rows])
        dp = _dot(do[rows], v_blk, (1, 1))
        ds = (p * (dp - delta[rows])).astype(k_blk.dtype)
        return dq + _dot(ds, k_blk, (1, 0))

    def interior(j, dq):
        k0 = pl.multiple_of(first + j * block_k, block_k)
        return tile(slice(None), dq, k0, block_k, lambda s: s)

    upper, corner = _upper_triangle(granule), _lower_triangle(granule)
    (dq,) = jax.lax.cond(
        edge, lambda carry: _by_slice(
            granule, bq, carry, lambda r, rows, c: (tile(
                rows, c[0], r * granule, bq - r * granule,
                lambda s: _mask_edge(s, upper, 1)),)),
        lambda carry: carry, (jnp.zeros((bq, d), jnp.float32),))
    dq = jax.lax.fori_loop(0, n_interior, interior, dq)
    for r in range(bq // granule):
        rows = slice(r * granule, (r + 1) * granule)
        dq_r = tile(rows, dq[rows], diagonal, (r + 1) * granule,
                    lambda s: _mask_corner(s, corner, 1))
        dq_ref[rows, :] = (dq_r * scale).astype(dq_ref.dtype)


def _bwd_dkv_window_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, *, scale, window, blocks, t):
    block_q, bk, granule = blocks
    d = k_ref.shape[-1]
    edge, diagonal, first, n_interior = _window_strip(
        KERNEL_BWD_DKV, blocks, window, t)
    k = _scaled(k_ref[...], scale)
    v = v_ref[...]

    def tile(cols, carry, q0, height, mask):
        dk, dv = carry
        q_blk = q_ref[pl.ds(q0, height), :]
        do_blk = do_ref[pl.ds(q0, height), :]
        lse_blk = lse_ref[pl.ds(q0, height), :1]
        delta_blk = delta_ref[pl.ds(q0, height), :1]
        p = jnp.exp(mask(_dot(q_blk, k[cols], (1, 1))) - lse_blk)
        dv = dv + _dot(p.astype(do_blk.dtype), do_blk, (0, 0))
        dp = _dot(do_blk, v[cols], (1, 1))
        ds = (p * (dp - delta_blk)).astype(q_blk.dtype)
        return dk + _dot(ds, q_blk, (0, 0)), dv

    def interior(i, carry):
        q0 = pl.multiple_of(first + i * block_q, block_q)
        return tile(slice(None), carry, q0, block_q, lambda s: s)

    upper, corner = _upper_triangle(granule), _lower_triangle(granule)
    # the window's edge: slice c's columns are seen by the square's rows up
    # to their own corner
    carry = jax.lax.cond(
        edge, lambda carry: _by_slice(
            granule, bk, carry, lambda c, cols, x: tile(
                cols, x, window, (c + 1) * granule,
                lambda s: _mask_edge(s, upper, 0))),
        lambda carry: carry,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    carry = jax.lax.fori_loop(0, n_interior, interior, carry)
    for c in range(bk // granule):
        cols = slice(c * granule, (c + 1) * granule)
        dk, dv = tile(cols, tuple(x[cols] for x in carry),
                      diagonal + c * granule, bk - c * granule,
                      lambda s: _mask_corner(s, corner, 0))
        dk_ref[cols, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[cols, :] = dv.astype(dv_ref.dtype)


def _window_call(kernel, body, operands, banded, outs, scale, window, blocks,
                 interpret):
    """One launch under a window. ``operands``: flat ``[bh, t, ...]`` (K
    and V ``[bh / groups, t, d]``); ``banded``: which of them a program
    holds as its band, the others as its strip; ``outs``: the last
    dimension of each result, a strip's block of ``[bh, t, ...]``."""
    bh, t, d = operands[0].shape
    groups = bh // operands[1].shape[0]
    strip = blocks.strip(kernel)

    def spec(x, band, kv):
        if band:
            return _band_spec(kernel, t, x.shape[-1], blocks, window,
                              groups if kv else 1)
        return pl.BlockSpec(
            (None, strip, x.shape[-1]),
            (lambda i, j: (i // groups, j, 0)) if kv and groups > 1
            else (lambda i, j: (i, j, 0)))

    out_specs = [pl.BlockSpec((None, strip, w), lambda i, j: (i, j, 0))
                 for w, _ in outs]
    out_shape = [jax.ShapeDtypeStruct((bh, t, w), dtype) for w, dtype in outs]
    return pl.pallas_call(
        functools.partial(body, scale=scale, window=window, blocks=blocks,
                          t=t),
        grid=(bh, t // strip),
        in_specs=[spec(x, band, n in (1, 2))
                  for n, (x, band) in enumerate(zip(operands, banded))],
        out_specs=out_specs, out_shape=out_shape, interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_asked()),
        name=kernel_name(kernel, window),
    )(*operands)


_jit_window_call = functools.partial(
    jax.jit, static_argnames=("scale", "window", "blocks", "interpret"))


@_jit_window_call
def _call_fwd_window(qf, kf, vf, scale, window, blocks, interpret):
    """``window_flash_fwd``: ``(o, lse)``."""
    return _window_call(
        KERNEL_FWD, _fwd_window_kernel, (qf, kf, vf), (False, True, True),
        ((qf.shape[-1], qf.dtype), (LSE_LANES, jnp.float32)), scale, window,
        blocks, interpret)


@_jit_window_call
def _call_dq_window(operands, scale, window, blocks, interpret):
    """``window_flash_bwd_dq`` on ``(q, k, v, do, lse, delta)``: dq."""
    q = operands[0]
    return _window_call(
        KERNEL_BWD_DQ, _bwd_dq_window_kernel, operands,
        (False, True, True, False, False, False),
        ((q.shape[-1], q.dtype),), scale, window, blocks, interpret)[0]


@_jit_window_call
def _call_dkv_window(operands, scale, window, blocks, interpret):
    """``window_flash_bwd_dkv`` on ``(q, k, v, do, lse, delta)``: (dk,
    dv), a query head's each."""
    q = operands[0]
    return _window_call(
        KERNEL_BWD_DKV, _bwd_dkv_window_kernel, operands,
        (True, False, False, True, True, True),
        ((q.shape[-1], q.dtype),) * 2, scale, window, blocks, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_kv(q, k, v, scale, window, schedule):
    """Causal attention of ``q [b, t, h, d]`` over ``k`` / ``v`` ``[b, t,
    h_kv, d]`` (``h / h_kv`` query heads read one KV head where it lies),
    under a ``window`` or (None) none: ``o [b * h, t, d]``."""
    return _flash_kv_fwd(q, k, v, scale, window, schedule)[0]


def _flash_kv_fwd(q, k, v, scale, window, schedule):
    from jax.ad_checkpoint import checkpoint_name

    if window is None:
        o, lse = _call_fwd(_flat(q), _flat(k), _flat(v), None, scale, True,
                           schedule[0], interpret=_interpret())
    else:
        o, lse = _call_fwd_window(_flat(q), _flat(k), _flat(v), scale,
                                  window, schedule[0], _interpret())
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


def _flash_kv_bwd(scale, window, schedule, res, do):
    q, k, v, o, lse = res
    b, t, h, d = q.shape
    operands = (_flat(q), _flat(k), _flat(v), do, lse, row_delta(o, do))
    if window is None:
        dq = _call_dq(operands, None, scale, True, schedule[1],
                      interpret=_interpret())
        dk, dv = _call_dkv(operands, None, scale, True, schedule[2],
                           interpret=_interpret())
    else:
        dq = _call_dq_window(operands, scale, window, schedule[1],
                             _interpret())
        dk, dv = _call_dkv_window(operands, scale, window, schedule[2],
                                  _interpret())

    def of_kv_head(x):
        """A query head's each to their KV head's sum, ``[b, t, h_kv, d]``."""
        x = x.reshape(b, k.shape[2], h // k.shape[2], t, d)
        return x.astype(jnp.float32).sum(2).astype(x.dtype).transpose(
            0, 2, 1, 3)

    return (dq.reshape(b, h, t, d).transpose(0, 2, 1, 3), of_kv_head(dk),
            of_kv_head(dv))


_flash_kv.defvjp(_flash_kv_fwd, _flash_kv_bwd)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale, causal, schedule):
    o, _ = _fwd(q, k, v, None, scale, causal, schedule[0])
    return o


def _flash_fwd(q, k, v, scale, causal, schedule):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _fwd(q, k, v, None, scale, causal, schedule[0])
    # under remat, tagging the kernel outputs lets a names-aware policy keep
    # them (o: 2 bytes/elem, lse: 1/head_dim of that) instead of re-running
    # the whole forward kernel to regenerate residuals in the backward pass
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_seg(q, k, v, seg_r, seg_c, scale, causal, schedule):
    o, _ = _fwd(q, k, v, (seg_r, seg_c), scale, causal, schedule[0])
    return o


def _flash_seg_fwd(q, k, v, seg_r, seg_c, scale, causal, schedule):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _fwd(q, k, v, (seg_r, seg_c), scale, causal, schedule[0])
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, seg_r, seg_c, o, lse)


def _flash_seg_bwd(scale, causal, schedule, res, g):
    q, k, v, seg_r, seg_c, o, lse = res
    dq, dk, dv = _bwd_impl(scale, causal, schedule, q, k, v, o, lse, g,
                           seg=(seg_r, seg_c))
    # integer operands take symbolic-zero (float0) cotangents
    dseg_r = np.zeros(seg_r.shape, jax.dtypes.float0)
    dseg_c = np.zeros(seg_c.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg_r, dseg_c


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


def schedule_plan(t: int, causal: bool, schedule,
                  window: Optional[int] = None) -> Dict[str, dict]:
    """What the ``flash.plan`` event says of each kernel: its blocks and
    its tile counts (under a ``window``: the band's). ``heads``: a grid
    step holds one head; several were swept on the v5e and bought nothing
    (PERF.md section 6, PR 45)."""
    return {kernel: {**blocks._asdict(), "heads": 1,
                     **tile_counts(kernel, t, causal, blocks, window)}
            for kernel, blocks in zip(KERNELS, schedule)}


def resolve_schedule(t: int, d: int, dtype, causal: bool, *,
                     block_q: int = None, block_k: int = None,
                     lane_aligned: bool = False, window: int = None
                     ) -> Tuple[Tuple[KernelBlocks, ...], str]:
    """The three kernels' schedules for one launch, and where the blocks
    came from (``explicit``, or ``get_flash_schedule``'s source). Publishes
    the ``flash.plan`` event (with the ``window``, where there is one):
    once per trace of a call, never per step."""
    wanted, source = {}, "explicit"
    if block_q is None or block_k is None:
        from deepspeed_tpu.ops.pallas.autotune import get_flash_schedule

        wanted, source = get_flash_schedule(t, d, dtype, causal, window)
    schedule = []
    for kernel in KERNELS:
        bq, bk, granule = wanted.get(kernel, (None,) * 3)
        schedule.append(fit_blocks(
            kernel, t, causal, bq if block_q is None else block_q,
            bk if block_k is None else block_k, granule,
            lane_aligned=lane_aligned, window=window))
    publish(KIND_FLASH_PLAN, t=t, d=d, causal=bool(causal), source=source,
            kernels=schedule_plan(t, causal, schedule, window),
            **({} if window is None else {"window": window}))
    return tuple(schedule), source


def flash_attention(q, k, v, *, causal: bool = True, scale: float = None,
                    segment_ids=None, block_q: int = None,
                    block_k: int = None, window: int = None):
    """Blockwise attention over ``[batch, seq, heads, head_dim]`` inputs.

    Memory is O(seq) per program instead of O(seq^2); the [T, T] score matrix
    only ever exists one [block_q, block_k] tile at a time in VMEM.

    ``segment_ids`` (``[batch, seq]`` int, 0 = padding) restricts attention
    to *causal AND same-segment* for packed-sequence batches
    (``deepspeed_tpu/data/``): position i attends j iff ``j <= i`` and
    ``seg[i] == seg[j]``, which makes the packed forward/backward exact vs
    per-document unpacked attention (docs/data.md).

    ``block_q``/``block_k`` default to the shape-tuned resolution in
    ``ops/pallas/autotune.py`` (the pretuned table, which may name each
    kernel's own -> the historical want-512 divisor heuristic); pass them
    explicitly to pin all three kernels.

    ``window``: position i attends j iff ``0 <= i - j < window`` (causal,
    no segments); tiles wholly outside that band are not computed, and K
    and V reach a program as the band its strip reads (the
    ``window_flash_*`` kernels). ``k`` and ``v`` may hold fewer heads than
    ``q`` (``[batch, seq, kv_heads, head_dim]``, causal, no segments): the
    ``heads / kv_heads`` query heads of a group read their KV head where it
    lies, and its gradient is the group's sum.
    """
    b, t, h, d = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    if window is not None and window >= t:
        window = None               # every earlier position: the causal mask
    grouped = k.shape[2] != h
    if window is not None or grouped:
        if not causal or segment_ids is not None or h % k.shape[2] \
                or (window is not None and window < 1):
            raise ValueError(
                "a window and grouped queries are built under the causal "
                "mask without segment_ids, for whole groups of heads and a "
                f"window >= 1; got causal={causal}, segment_ids "
                f"{'given' if segment_ids is not None else 'None'}, "
                f"{h} over {k.shape[2]} heads, window={window}")
    schedule, _ = resolve_schedule(t, d, q.dtype, causal,
                                   block_q=block_q, block_k=block_k,
                                   lane_aligned=segment_ids is not None,
                                   window=window)
    if window is not None or grouped:
        of = _flash_kv(q, k, v, float(scale), window, schedule)
    elif segment_ids is None:
        of = _flash(q, k, v, float(scale), bool(causal), schedule)
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
                        schedule)
    return of.reshape(b, h, t, d).transpose(0, 2, 1, 3)
