"""Shape-tuned schedule selection for the flash-attention kernels.

``largest_divisor_block``'s fixed ``want`` heuristic picks the largest
divisor of the sequence length — shape-blind. What a kernel wants depends
on (seq, head_dim, dtype, device): on the v5e a strip of the whole
sequence a program (a static schedule, no loop) beats every smaller block
at 1,024 and at 4,096 positions, and each of the three kernels has its own
best blocks and granule (``flash_attention.KernelBlocks``).

Resolution order for :func:`get_flash_blocks` (first hit wins):

1. in-memory cache (one lookup per process per key)
2. on-disk JSON cache — only where ``$DS_TPU_PALLAS_CACHE`` names a
   file (no default location: what a program compiles depends on
   tracked files, not on what an earlier run left in a home directory),
   keyed by ``device_kind|seq|head_dim|dtype|causal``; written by a
   previous autotune run. A corrupt/unreadable file falls through (warn
   once) and is overwritten by the next tuned write.
3. shipped pretuned table (:data:`PRETUNED`: each kernel's blocks and
   granule) — the v5e's bf16 entries at 1,024 and 4,096 positions (the
   1.3B and OLMoE benchmark configs' shapes) are measured on the v5e,
   PR 45 (``benchmarks/flash_sweep.py --kernels``; PERF.md section 6);
   every other entry is a seed never run on its chip.
4. live benchmark at the actual shape, IF enabled (``autotune=True`` or
   ``DS_TPU_FLASH_AUTOTUNE=1``): times the jitted fwd+bwd over a
   divisor-filtered candidate grid and persists the winner to (2).
5. the ``largest_divisor_block`` heuristic — today's default, unchanged.

:func:`get_flash_schedule` is what ``flash_attention`` asks: each
kernel's ``(block_q, block_k, granule)`` by that order (a pair from the
disk cache, the live benchmark or the heuristic goes to all three, their
granules left to the fitting); :func:`get_flash_blocks` is its forward
pair. Every cached/pretuned entry is re-validated against the current
shape (divisibility) before use, and ``flash_attention.fit_blocks`` makes
the shapes of whatever comes out a valid launch, so a stale or hand-edited
cache can never produce an invalid one.
"""

import json
import os
import threading
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.pallas.common import largest_divisor_block

_CACHE_ENV = "DS_TPU_PALLAS_CACHE"
_AUTOTUNE_ENV = "DS_TPU_FLASH_AUTOTUNE"
_DEFAULT_WANT = 512  # flash_attention's historical fixed block default

# one kernel's (block_q, block_k, granule); granule None: the fitting's
Blocks = Tuple[int, int, Optional[int]]

# (device_kind, seq, head_dim, dtype, causal) -> the three kernels' blocks,
# in ``flash_attention.KERNELS``' order: forward, dQ, dK/dV.
# Seeds for the 1.3B/seq-1024 shape (n_embd=2048 / 16 heads -> d=128),
# never run on their chips: block_k at seq/4, block_q at seq/2 up to 2048,
# (512, 512) past it. The v5e's bf16 entries below are measured.
PRETUNED: Dict[Tuple[str, int, int, str, bool], Tuple[Blocks, ...]] = {}
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

_lock = threading.Lock()
_mem_cache: Dict[str, Tuple[Tuple[Blocks, ...], str]] = {}
_disk_warned = False


def cache_path() -> Optional[str]:
    """The disk cache file, or None when ``$DS_TPU_PALLAS_CACHE`` is unset
    (then nothing is read from or written to disk)."""
    return os.environ.get(_CACHE_ENV) or None


def cache_key(device_kind: str, t: int, d: int, dtype, causal: bool) -> str:
    return f"{device_kind}|{int(t)}|{int(d)}|{jnp.dtype(dtype).name}|" \
           f"{bool(causal)}"


def _load_disk_cache() -> Dict[str, List[int]]:
    global _disk_warned
    path = cache_path()
    if path is None or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict):
            raise ValueError(f"expected a JSON object, got {type(data)}")
        return data
    except (OSError, ValueError) as e:
        if not _disk_warned:
            _disk_warned = True
            warnings.warn(
                f"ignoring corrupt Pallas autotune cache {path!r} ({e}); "
                "falling back to the block-size heuristic — the next "
                "autotune run rewrites it", RuntimeWarning)
        return {}


def _store_disk_cache(key: str, blocks: Tuple[int, int]) -> None:
    path = cache_path()
    if path is None:
        return
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    data = _load_disk_cache()
    data[key] = [int(blocks[0]), int(blocks[1])]
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def _valid(blocks, t: int) -> Optional[Tuple[int, int]]:
    """Sanity-check a cached/pretuned entry against the current shape."""
    try:
        bq, bk = int(blocks[0]), int(blocks[1])
    except (TypeError, ValueError, IndexError):
        return None
    if bq < 1 or bk < 1 or t % bq or t % bk:
        return None
    return bq, bk


def _all_three(pair: Tuple[int, int]) -> Tuple[Blocks, ...]:
    return ((pair[0], pair[1], None),) * 3


def default_candidates(t: int) -> List[Tuple[int, int]]:
    """Divisor-filtered (block_q, block_k) grid around the MXU-friendly
    power-of-two sizes, bounded so the f32 score tile stays well under a
    VMEM core (block_q*block_k <= 512*1024 -> 2 MB)."""
    sizes = [b for b in (128, 256, 512, 1024) if b <= t and t % b == 0]
    if not sizes:  # short/odd seq: fall back to the divisor heuristic sizes
        sizes = sorted({largest_divisor_block(t, w)
                        for w in (128, 256, 512)})
    return [(bq, bk) for bq in sizes for bk in sizes
            if bq * bk <= 512 * 1024]


def benchmark_candidates(t: int, d: int, dtype, causal: bool,
                         candidates: List[Tuple[int, int]],
                         batch_heads: int = 4, iters: int = 3
                         ) -> Tuple[int, int]:
    """Time the jitted flash fwd+bwd at the actual (seq, head_dim) shape
    for each candidate and return the fastest. One compile + ``iters``
    timed runs per candidate; called once per (shape, device) ever, the
    winner is persisted to the disk cache."""
    import time

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    shape = (1, t, batch_heads, d)
    q = jnp.asarray(rng.randn(*shape), jnp.dtype(dtype))
    k = jnp.asarray(rng.randn(*shape), jnp.dtype(dtype))
    v = jnp.asarray(rng.randn(*shape), jnp.dtype(dtype))

    best, best_dt = None, float("inf")
    for bq, bk in candidates:

        def loss(q, k, v, bq=bq, bk=bk):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=bq, block_k=bk))

        try:
            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            jax.block_until_ready(step(q, k, v))  # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(step(q, k, v))
            dt = (time.perf_counter() - t0) / iters
        except Exception as e:  # candidate failed to compile/run: skip it
            warnings.warn(
                f"flash autotune candidate ({bq},{bk}) failed: {e}",
                RuntimeWarning)
            continue
        if dt < best_dt:
            best, best_dt = (bq, bk), dt
    if best is None:
        raise RuntimeError(
            f"flash autotune: no candidate ran for t={t} d={d}")
    return best


def _resolve(t: int, d: int, dtype, causal: bool, *,
             want_q: int = _DEFAULT_WANT, want_k: int = _DEFAULT_WANT,
             autotune: Optional[bool] = None,
             candidates: Optional[List[Tuple[int, int]]] = None
             ) -> Tuple[Tuple[Blocks, ...], str]:
    """The three kernels' blocks and the step of the resolution order that
    gave them: ``disk``, ``pretuned``, ``autotuned`` or ``heuristic``."""
    device_kind = jax.devices()[0].device_kind
    key = cache_key(device_kind, t, d, dtype, causal)

    with _lock:
        hit = _mem_cache.get(key)
        if hit is not None:
            return hit
        entry = _valid(_load_disk_cache().get(key), t)
        if entry is not None:
            _mem_cache[key] = (_all_three(entry), "disk")
            return _mem_cache[key]
        pre = PRETUNED.get((device_kind, int(t), int(d),
                            jnp.dtype(dtype).name, bool(causal)), ())
        if pre and all(_valid(blocks, t) for blocks in pre):
            _mem_cache[key] = (pre, "pretuned")
            return _mem_cache[key]

    if autotune is None:
        autotune = os.environ.get(_AUTOTUNE_ENV, "0") not in ("", "0")
    if not autotune:
        return _all_three((largest_divisor_block(t, want_q),
                           largest_divisor_block(t, want_k))), "heuristic"

    tuned = benchmark_candidates(
        t, d, dtype, causal, candidates or default_candidates(t))
    with _lock:
        _mem_cache[key] = (_all_three(tuned), "autotuned")
        try:
            _store_disk_cache(key, tuned)
        except OSError as e:
            warnings.warn(
                f"flash autotune: could not persist winner to "
                f"{cache_path()!r} ({e}); it stays in-memory for this "
                "process", RuntimeWarning)
    return _mem_cache[key]


def get_flash_blocks(t: int, d: int, dtype, causal: bool, *,
                     want_q: int = _DEFAULT_WANT,
                     want_k: int = _DEFAULT_WANT,
                     autotune: Optional[bool] = None,
                     candidates: Optional[List[Tuple[int, int]]] = None
                     ) -> Tuple[int, int]:
    """Resolve the forward's (block_q, block_k) for a flash-attention
    launch.

    ``autotune=None`` defers to the ``DS_TPU_FLASH_AUTOTUNE`` env flag;
    ``candidates`` overrides the benchmark grid (tests use tiny ones).
    """
    return _resolve(t, d, dtype, causal, want_q=want_q, want_k=want_k,
                    autotune=autotune, candidates=candidates)[0][0][:2]


def get_flash_schedule(t: int, d: int, dtype, causal: bool, *,
                       autotune: Optional[bool] = None):
    """What each of the three kernels wants at this shape, and the source:
    ``{kernel: (block_q, block_k, granule)}`` (granule ``None`` where the
    kernel's own fitting decides)."""
    from deepspeed_tpu.ops.pallas.flash_attention import KERNELS

    wanted, source = _resolve(t, d, dtype, causal, autotune=autotune)
    return dict(zip(KERNELS, wanted)), source


def clear_memory_cache() -> None:
    """Test hook: drop the per-process memoization (disk cache untouched)."""
    global _disk_warned
    with _lock:
        _mem_cache.clear()
        _disk_warned = False


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
