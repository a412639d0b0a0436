"""Shape-tuned schedule selection for the flash-attention kernels.

``largest_divisor_block``'s fixed ``want`` heuristic picks the largest
divisor of the sequence length — shape-blind. What a kernel wants depends
on (seq, head_dim, dtype, device): on the v5e a strip of the whole
sequence a program (a static schedule, no loop) beats every smaller block
at 1,024 and at 4,096 positions, and each of the three kernels has its own
best blocks and granule (``flash_attention.KernelBlocks``).

:func:`get_flash_schedule` is what ``flash_attention`` asks, and there is
one way to an answer, from tracked files alone:

1. the shipped table (:data:`PRETUNED`: each kernel's ``(block_q,
   block_k, granule)``), source ``pretuned`` — the v5e's bf16 entries at
   1,024 and 4,096 positions (the 1.3B and OLMoE benchmark configs'
   shapes) are measured on the v5e, PR 45 (``benchmarks/flash_sweep.py
   --kernels``, which is how an entry is made; PERF.md section 6); every
   other entry is a seed never run on its chip. A hit is kept in memory,
   one table lookup per process per key.
2. the ``largest_divisor_block`` heuristic, source ``heuristic``: one
   pair for all three kernels, their granules left to the fitting.

An entry is validated against the current shape (divisibility) before
use, and ``flash_attention.fit_blocks`` makes the shapes of whatever comes
out a valid launch.
"""

import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.common import largest_divisor_block

_DEFAULT_WANT = 512  # flash_attention's historical fixed block default

# one kernel's (block_q, block_k, granule); granule None: the fitting's
Blocks = Tuple[int, int, Optional[int]]

# (device_kind, seq, head_dim, dtype, causal) -> the three kernels' blocks,
# in ``flash_attention.KERNELS``' order: forward, dQ, dK/dV.
# Seeds for the 1.3B/seq-1024 shape (n_embd=2048 / 16 heads -> d=128),
# never run on their chips: block_k at seq/4, block_q at seq/2 up to 2048,
# (512, 512) past it. The v5e's bf16 entries below are measured.
# A launch under a window has the window after ``causal``.
PRETUNED: Dict[tuple, Tuple[Blocks, ...]] = {}
for _kind in ("TPU v4", "TPU v5 lite", "TPU v5e", "TPU v5p", "TPU v6 lite",
              "TPU v6e"):
    for _dt in ("bfloat16", "float32"):
        for _t, _pair in ((1024, (512, 256)), (2048, (512, 256)),
                          (4096, (512, 512)), (8192, (512, 512))):
            PRETUNED[(_kind, _t, 128, _dt, True)] = ((*_pair, None),) * 3

# Measured on the v5e, PR 45 (``benchmarks/flash_sweep.py --kernels``, each
# kernel alone at 96 heads of 1,024 and 32 of 4,096 positions; PERF.md
# section 6 has the tables). A strip of the WHOLE sequence wins at both
# lengths: its schedule is static (no loop whose trip count a program id
# decides) and every slice's tile is as wide as its rows see; dK/dV at
# 4,096 is best at strips of 2,048 keys. Granule 256 at 1,024 (1.25 of the
# causal half computed; 128 computes 1.125 and is 10-40% slower in the
# forward and dK/dV), 512 at 4,096 (within 1-5% of 256's time at half the
# slices to trace and lower: ``setup_s``). 2,048 stays a seed: whole strips
# read faster there too, kernel alone, and no cell runs that length.
for _kind in ("TPU v5 lite", "TPU v5e"):
    for _t, _g, _keys in ((1024, 256, 1024), (4096, 512, 2048)):
        PRETUNED[(_kind, _t, 128, "bfloat16", True)] = (
            (_t, 512, _g), (_t, 512, _g), (512, _keys, _g))

# Measured on the v5e, PR 63 (each kernel alone over 28 query heads on 4 KV
# heads at 16,384 positions, ms a call; PERF.md section 6 has the sweep).
# Without a window: forward 14.97 at (1024, 1024, 512) (16.6 at block_k
# 512), dQ 16.86 at (2048, 1024, 256), dK/dV 23.81 at strips of 2,048 keys
# over tiles of 1,024 rows. Under a window of 4,096 (the ``window_flash_*``
# kernels, keyed with the window last): forward 7.87 at (2048, 1024, 512),
# where the heuristic's (512, 512, 256) reads 13.77; dQ 8.56 (9.32); dK/dV
# 11.91 (14.99): long strips, whose edge and diagonal squares are few, over
# tiles of 1,024.
for _kind in ("TPU v5 lite", "TPU v5e"):
    PRETUNED[(_kind, 16384, 128, "bfloat16", True)] = (
        (1024, 1024, 512), (2048, 1024, 256), (1024, 2048, 256))
    PRETUNED[(_kind, 16384, 128, "bfloat16", True, 4096)] = (
        (2048, 1024, 512), (2048, 1024, 256), (1024, 2048, 256))

_lock = threading.Lock()
# PRETUNED's validated hits, by PRETUNED's key
_mem_cache: Dict[tuple, Tuple[Blocks, ...]] = {}


def _valid(blocks, t: int) -> Optional[Tuple[int, int]]:
    """Sanity-check a table entry against the current shape."""
    try:
        bq, bk = int(blocks[0]), int(blocks[1])
    except (TypeError, ValueError, IndexError):
        return None
    if bq < 1 or bk < 1 or t % bq or t % bk:
        return None
    return bq, bk


def get_flash_schedule(t: int, d: int, dtype, causal: bool,
                       window: Optional[int] = None):
    """What each of the three kernels wants at this shape (under a
    ``window``: the ``window_flash_*`` kernels, which have rows of their
    own), and the source (``pretuned`` or ``heuristic``): ``{kernel:
    (block_q, block_k, granule)}`` (granule ``None`` where the kernel's own
    fitting decides)."""
    from deepspeed_tpu.ops.pallas.flash_attention import KERNELS

    key = (jax.devices()[0].device_kind, int(t), int(d),
           jnp.dtype(dtype).name, bool(causal))
    if window is not None:
        key += (int(window),)
    with _lock:
        wanted = _mem_cache.get(key)
        if wanted is None:
            pre = PRETUNED.get(key, ())
            if pre and all(_valid(blocks, t) for blocks in pre):
                wanted = _mem_cache[key] = pre
    if wanted is not None:
        return dict(zip(KERNELS, wanted)), "pretuned"
    block = largest_divisor_block(t, _DEFAULT_WANT)
    return dict.fromkeys(KERNELS, (block, block, None)), "heuristic"


def clear_memory_cache() -> None:
    """Test hook: drop the per-process memoization."""
    with _lock:
        _mem_cache.clear()


# ---------------------------------------------------------------------------
# grouped matmul (ops/pallas/grouped_matmul.py): (tm, tk, tn) per product.
# No live search: a program's set-up is judged, and the table is small.
# ---------------------------------------------------------------------------
# what the tiles of one call may hold of VMEM (double-buffered operands and
# result, the float32 product or accumulator) when nothing better is known
_GMM_VMEM_BUDGET = 24 << 20
_GMM_KINDS = ("gmm", "gmm_t", "tgmm")

# (kind, rows, K, N, groups, dtype, device_kind) -> (tm, tk, tn), found on
# the chip (PERF.md, section 6, PR 38); rows, K, N as the call has them
GMM_PRETUNED: Dict[Tuple[str, int, int, int, int, str, str],
                   Tuple[int, int, int]] = {}
# OLMoE-1B-7B at 2 x 4096 tokens x 8 experts a token (65,536 rows, 64
# experts of [2048, 1024]): row tiles of 256 and the whole of the other
# two axes, for all three products of both projections' shapes. tm 128
# reads within 1.5% of it (an eighth of its visits are second visits of a
# shared tile, a quarter at 256, and the matrix unit likes the longer
# stream as much), tm 512 is 10% slower; a narrower tn reads the rows
# again for every column tile and is 4-30% slower
for _kind in ("TPU v5 lite", "TPU v5e"):
    for _k, _n in ((2048, 1024), (1024, 2048)):
        for _product in _GMM_KINDS:
            GMM_PRETUNED[(_product, 65536, _k, _n, 64, "bfloat16",
                          _kind)] = (256, _k, _n)


def _multiples(x: int, unit: int, most: int) -> List[int]:
    """Divisors of ``x`` that are multiples of ``unit``, at most ``most``,
    largest first."""
    return [b for b in range(min(most, x) // unit * unit, 0, -unit)
            if x % b == 0]


def grouped_matmul_vmem_bytes(kind: str, tm: int, tk: int, tn: int,
                              itemsize: int) -> int:
    """VMEM the tiles of one call take: operands and result double-buffered,
    and the float32 product (``tgmm``: accumulator and product)."""
    if kind == "tgmm":
        return 2 * itemsize * (tm * (tk + tn) + tk * tn) + 8 * tk * tn
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def grouped_matmul_tiles(kind: str, rows: int, k: int, n: int, groups: int,
                         dtype) -> Tuple[int, int, int]:
    """``(tm, tk, tn)`` of one grouped-matmul call. ``kind``: ``gmm``
    (rows [rows, k] by matrices [k, n]), ``gmm_t`` (the same by matrices
    [n, k] contracted on their last axis), ``tgmm`` ([rows, k] and [rows,
    n] to [k, n] a group). For the first two ``tk`` is all of ``k``: it is
    what keeps a group's matrix on chip. The table's entry where the chip
    and the shapes have one; else ``tm`` 128 (the most that divides the
    rows) and the widest ``tn`` (``tgmm``: the largest ``tk x tn``) whose
    tiles fit :data:`_GMM_VMEM_BUDGET`."""
    if kind not in _GMM_KINDS:
        raise ValueError(f"kind {kind!r} is none of {_GMM_KINDS}")
    dtype = jnp.dtype(dtype)
    hit = GMM_PRETUNED.get((kind, rows, k, n, groups, dtype.name,
                            jax.devices()[0].device_kind))
    if hit is not None:
        return hit
    tm = _multiples(rows, 16, 128)[0]
    fits = [(tk, tn)
            for tk in (_multiples(k, 128, k) if kind == "tgmm" else [k])
            for tn in _multiples(n, 128, n)
            if grouped_matmul_vmem_bytes(kind, tm, tk, tn, dtype.itemsize)
            <= _GMM_VMEM_BUDGET]
    tk, tn = max(fits, key=lambda t: (t[0] * t[1], t[1])) if fits \
        else ((128 if kind == "tgmm" else k), 128)
    return tm, tk, tn
