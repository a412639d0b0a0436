"""The MoE layer's row movement as Pallas kernels that issue their own row
copies and issue one only for a row that is live here.

A dropless layer sorts its ``tokens * k`` (token, expert) pairs by expert
and moves a row of ``M`` numbers a pair, to the sorted layout and back. A
layer that holds a share of the experts (``MoE.experts_held``) sorts the
pairs routed elsewhere past the last held group: of the ``R`` sorted rows
only the first ``n_live = sum(group_sizes)`` are computed, a number the
device knows and the host does not. XLA's gather moves all ``R`` whatever
the number (``jnp.take``: every shape is static and so is its work). Here
every shape stays static too and what becomes dynamic is how many copies a
call issues, as the grouped matmul's walk makes its real visits dynamic
(``grouped_matmul.py`` ``row_walk``):

* :func:`fetch_rows` ``(src [S, M], index [R], n_live) -> [R, M]``: row r
  is ``src[index[r]]`` (times ``scale[r]`` in float32, one rounding) for
  ``r < n_live``. The grid's LENGTH is ``ceil(n_live / tile)``, a run-time
  scalar: a tile wholly past ``n_live`` has no grid step, no copy and no
  store (its rows are whatever the buffer held: nothing downstream may
  read them, and the grouped matmuls do not), the rows from ``n_live`` to
  the end of the consumer's tile that holds row ``n_live`` are zeros. The dispatch's forward and the combine's gradient with
  respect to the rows.
* :func:`fetch_sum_rows` ``(src [R, M], index [T, k], weights [T, k],
  n_live) -> [T, M]``: ``out[t] = sum_j weights[t, j] * src[index[t, j]]``
  over the pairs with ``index[t, j] < n_live``, float32 in the order j = 0
  .. k-1, one rounding. A pair routed elsewhere issues no copy. Slot j of
  a tile of tokens lands in a buffer of its own, so the sum over k is k
  full-tile additions and no ``[T, k, M]`` view exists. The combine's
  forward and, without weights, the dispatch's transpose.
* :func:`fetch_dot_rows` ``(src [R, M], index [T, k], g [T, M], n_live) ->
  [T, k]`` float32: ``<src[index[t, j]], g[t]>`` for the live pairs, 0 for
  the others: the combine's gradient with respect to the weights, from the
  same fetch.

One row, one copy, one contiguous run of bytes. The TPU lays a ``[S, M]``
array out in tiles of 8 (bf16: 16) rows, so one row is M / 128 pieces a
tile apart and Mosaic slices no single row out of it. The source is
therefore first written ROW-MAJOR by one pass (:func:`as_words`:
``uint32 [S * C, 128]``, row s the C = words / 128 consecutive lines from
s * C; a bf16 row packs column c and column c + M / 2 into one word, so
the two halves come apart as whole lane tiles), a copy moves a row's C
lines to C lines of a staging buffer, and the tile's vector work reads
column block c of all its rows with ONE strided load (lines c, c + C,
...). The pass is a kernel too (:func:`as_words`) and covers the tiles that
hold a row before ``n_live``, as the copies and everything after them do.

Copies for tile i + 1 are issued before tile i's are waited for (two
buffers, a DMA semaphore each), so a tile's vector work runs under the
next tile's copies; a tile's copies are waited for together (a DMA
semaphore counts what was moved: :func:`_wait_rows`). What a copy costs is
the scalar core's time to issue it, ~10-20 ns whatever the row's bytes.

The bodies are written for their TRACE as much as for their speed: vector
work in ``lax`` primitives, the loops over a row's lines and a token's
slots rolled (``lax.fori_loop``). Written out they ran a third faster
(``fetch_sum_rows`` 2.1 ms a call against 3.5 at the cell's shape) and
their eleven traces a step program cost 3 s of set-up on the chip's host,
over the bound a cell's ``setup_s`` is judged by.

Measured alone and inside the step programs on the v5e: PERF.md, section
6, PR 64. ``moe/sharded_moe.py`` ``fetches_live_rows`` is the rule that
says where the kernels take a layer's calls; elsewhere it keeps
``jnp.take``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import interpret as _interpret

FETCH_NAME = "moe-row-fetch"
FETCH_SUM_NAME = "moe-row-fetch-sum"
FETCH_DOT_NAME = "moe-row-fetch-dot"
WORDS_NAME = "moe-row-words"

# sorted rows a grid step of fetch_rows moves, and (token, slot) pairs a
# grid step of the two sums fetches: a few hundred copies in flight, the
# staging buffers a few megabytes
_ROW_TILE = 256
_PAIR_TILE = 512
_VMEM_LIMIT = 64 << 20
_LANES = 128


def _halves(dtype) -> int:
    """Numbers of ``dtype`` in a 32-bit word: 2 (bf16) or 1 (float32)."""
    return 4 // jnp.dtype(dtype).itemsize


def _divisor(n: int, want: int, multiple: int) -> int:
    """The largest multiple of ``multiple`` that divides ``n`` and is at
    most ``want``; 0 where there is none."""
    t = want - want % multiple
    while t and n % t:
        t -= multiple
    return t


def supported(tokens: int, k: int, width: int, dtype) -> bool:
    """Whether the kernels take a layer of ``tokens`` tokens, ``k`` experts
    a token and rows of ``width`` numbers: bf16 or float32, a row's words
    whole lines of 128, and tiles that divide the rows."""
    dtype = jnp.dtype(dtype)
    return (dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and width % (_LANES * _halves(dtype)) == 0
            and bool(_row_tile(tokens * k)) and bool(_token_tile(tokens, k)))


def _row_tile(rows):
    return _divisor(rows, _ROW_TILE, 16)


def _token_tile(tokens, k):
    return _divisor(tokens, max(16, _PAIR_TILE // k // 16 * 16), 16)


def _as_words_kernel(n_live, src, out, *, lines, halves):
    del n_live  # the grid's length alone
    tile = src.shape[0]

    # (a loop, not ``lines`` copies of its body: a kernel's trace is set-up
    # time on the chip's host, and so for every loop over the lines below)
    def line(c, carry):
        word = src[:, _lanes(c)]
        if halves > 1:
            high = src[:, _lanes(lax.add(c, np.int32(lines)))]
            # (a bf16 number's bits are its float32's upper half)
            word = lax.bitwise_or(
                lax.shift_right_logical(_bits(word), _like(word, 16)),
                lax.bitwise_and(_bits(high), _like(word, 0xFFFF0000)))
        out[pl.ds(c, tile, stride=lines), :] = word
        return carry

    lax.fori_loop(0, lines, line, 0)


def as_words(src, n_live=None, *, tile=None):
    """``src`` [S, M] (bf16 or float32) row-major as the 32-bit words a copy
    moves: ``uint32`` (``float32``: itself) ``[S * C, 128]``, row s the C
    lines from ``s * C``. A bf16 row's word w holds column w in its low
    half and column ``w + M / 2`` in its high half. One pass over the rows
    before ``n_live`` (None: all), a tile a grid step; the lines of the
    tiles past it are not written."""
    return _as_words(src, n_live, tile=tile or _row_tile(src.shape[0]),
                     interpret=_interpret())


# The calls are jitted so that one program's many calls of one signature
# (forward, recomputed, two kinds of layer) are traced once, as the grouped
# matmul's are: tracing a Pallas call is set-up time, which is judged.
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _as_words(src, n_live, *, tile, interpret):
    rows, width = src.shape
    halves = _halves(src.dtype)
    lines = width // halves // _LANES
    live = jnp.int32(rows) if n_live is None else n_live.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_as_words_kernel, lines=lines, halves=halves),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // tile if n_live is None
                  else (live + tile - 1) // tile,),
            in_specs=[pl.BlockSpec((tile, width), lambda i, n: (i, 0))],
            out_specs=pl.BlockSpec((tile * lines, _LANES),
                                   lambda i, n: (i, 0))),
        out_shape=jax.ShapeDtypeStruct(
            (rows * lines, _LANES),
            jnp.uint32 if halves > 1 else src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=WORDS_NAME,
    )(live.reshape(1), src)


# The kernels' vector work is written in ``lax`` primitives: every ``jnp``
# function and array operator is a jitted helper whose every call inside a
# trace is a trace of its own, a few hundred of them a kernel body, and a
# program's set-up is judged (``grouped_matmul.row_walk`` says the same).
def _bits(x):
    """The bits of ``x``'s float32 as uint32."""
    return lax.bitcast_convert_type(
        lax.convert_element_type(x, jnp.float32), jnp.uint32)


def _like(x, value):
    """uint32 ``value`` in ``x``'s shape."""
    return lax.full(x.shape, value, jnp.uint32)


def _wide(column, lanes=_LANES):
    """``column`` [n, 1] along ``lanes`` lanes."""
    return lax.broadcast_in_dim(column, (column.shape[0], lanes), (0, 1))


def _numbers(words, halves):
    """The float32 numbers of a block of words, a list of one block a
    half: column block c of the row, then (bf16) of its upper half."""
    if halves == 1:
        return [words]
    return [lax.bitcast_convert_type(
                lax.shift_left(words, _like(words, 16)), jnp.float32),
            lax.bitcast_convert_type(
                lax.bitwise_and(words, _like(words, 0xFFFF0000)),
                jnp.float32)]


def _column(row):
    """``row`` [1, n] (lane-dense float32) as a sublane vector [n, 1]: the
    diagonal of its broadcast, by a masked sum over the lanes (exact: one
    term)."""
    n = row.shape[1]
    wide = (n, n)
    diagonal = lax.eq(lax.broadcasted_iota(jnp.int32, wide, 0),
                      lax.broadcasted_iota(jnp.int32, wide, 1))
    return jnp.sum(lax.select(diagonal, lax.broadcast_in_dim(
        row, wide, (0, 1)), lax.full(wide, 0.0, row.dtype)),
        axis=1, keepdims=True)


def _lanes(block):
    """The 128 lanes of column block ``block`` (a traced index) of a row."""
    return pl.ds(pl.multiple_of(lax.mul(block, np.int32(_LANES)), _LANES),
                 _LANES)


def _unrolled(n, by, body):
    """``body(i)`` for i in ``range(n)``, ``by`` to a turn of the loop
    (Mosaic's ``fori_loop`` unrolls wholly or not at all)."""
    def turn(i, carry):
        first = lax.mul(i, np.int32(by))
        for u in range(by):
            body(lax.add(first, np.int32(u)))
        return carry

    lax.fori_loop(0, n // by, turn, 0)


def _row_copy(src, row, stage, at, sem, lines):
    """Start the copy of source row ``row``'s ``lines`` lines to the lines
    from ``at * lines`` of ``stage``."""
    lines_ = np.int32(lines)
    pltpu.make_async_copy(src.at[pl.ds(lax.mul(row, lines_), lines)],
                          stage.at[pl.ds(lax.mul(at, lines_), lines)],
                          sem).start()


def tiles_written(n_live, tile, zero_to, n_tiles):
    """The grid steps :func:`fetch_rows` makes, a tile of rows each: through
    the block of ``zero_to`` rows that holds row ``n_live`` (a group of no
    rows at the end has its one visit there, and that is a block of no
    live row where ``n_live`` is a block's edge)."""
    return jnp.minimum(n_tiles, (n_live // zero_to + 1) * (zero_to // tile))


def _wait_rows(src, stage, sem, lines, rows):
    """Wait for ``rows`` row copies that signal ``sem``: a DMA semaphore
    counts what was moved, so one wait the size of ``rows`` rows (static)
    stands for that many waits of a row."""
    pltpu.make_async_copy(src.at[pl.ds(0, rows * lines)],
                          stage.at[pl.ds(0, rows * lines)], sem).wait()


def _fetch_rows_kernel(n_live, index, index_next, *refs, lines, halves,
                       scaled):
    refs = list(refs)
    scale = refs.pop(0) if scaled else None
    src, out, stage, sem = refs
    i = pl.program_id(0)
    slot = lax.rem(i, 2)
    tile = out.shape[0]

    def fetches(step):
        # (the steps past the last live tile store zeros: ``zero_to``)
        return step * tile < n_live[0]

    def start(index, slot):
        _unrolled(tile, 4, lambda r: _row_copy(
            src, index[0, r], stage.at[slot], r, sem.at[slot], lines))

    @pl.when((i == 0) & fetches(0))
    def _():
        start(index, slot)

    @pl.when(fetches(i + 1))
    def _():
        start(index_next, 1 - slot)

    @pl.when(fetches(i))
    def _():
        _wait_rows(src, stage.at[slot], sem.at[slot], lines, tile)

    live = _wide(lax.lt(
        lax.broadcasted_iota(jnp.int32, (tile, 1), 0),
        lax.full((tile, 1), n_live[0] - i * tile, jnp.int32)))
    by = _wide(_column(scale[...])) if scaled else None
    zeros = lax.full((tile, _LANES), 0.0, jnp.float32)

    def line(c, carry):
        # column block c of every row of the tile: lines c, c + C, ...
        block = stage[slot, pl.ds(c, tile, stride=lines), :]
        for h, numbers in enumerate(_numbers(block, halves)):
            if scaled:
                numbers = lax.mul(numbers, by)
            out[:, _lanes(lax.add(c, np.int32(h * lines)))] = \
                lax.convert_element_type(
                    lax.select(live, numbers, zeros), out.dtype)
        return carry

    lax.fori_loop(0, lines, line, 0)


def fetch_rows(src, index, n_live=None, scale=None, *, zero_to=None,
               tile=None):
    """``out[r] = src[index[r]]`` for ``r < n_live`` (``* scale[r]`` in
    float32, rounded once to ``src``'s dtype), zeros for the rows from
    ``n_live`` to the end of the block of ``zero_to`` rows (of the tile, if
    that is larger: one of the two divides the other) that holds row
    ``n_live``, a whole block where ``n_live`` is a block's edge, NOTHING
    WRITTEN past that. ``src`` [S, M] bf16 or
    float32, ``index`` [R] int32 in ``[0, S)`` for every r (live or not:
    the last live tile fetches its tail and masks it), ``n_live`` an int32
    scalar (None: all R), ``scale`` [R] float32 or None. (``zero_to``: a
    consumer that works in tiles of its own, as the grouped matmuls do,
    reads finite numbers in every tile it visits: those that hold a live
    row and, for a group of no rows, the one its offset lies in.)"""
    rows = index.shape[0]
    tile = tile or _row_tile(rows)
    if not zero_to or tile % zero_to == 0:
        zero_to = tile
    if zero_to % tile or rows % zero_to:
        raise ValueError(f"zero_to {zero_to} is not a multiple of the tile "
                         f"{tile} that divides the {rows} rows")
    return _fetch_rows(src, index, n_live, scale, zero_to=zero_to, tile=tile,
                       interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("zero_to", "tile", "interpret"))
def _fetch_rows(src, index, n_live, scale, *, zero_to, tile, interpret):
    rows, width = index.shape[0], src.shape[1]
    n_tiles = rows // tile
    halves = _halves(src.dtype)
    lines = width // halves // _LANES
    last = n_tiles - 1
    live = jnp.int32(rows) if n_live is None else n_live.astype(jnp.int32)
    per_tile = pl.BlockSpec((None, 1, tile), lambda i, n: (i, 0, 0))
    words = _as_words(src, None, tile=_row_tile(src.shape[0]),
                      interpret=interpret)
    index = index.astype(jnp.int32).reshape(n_tiles, 1, tile)
    scales = () if scale is None else (
        scale.astype(jnp.float32).reshape(n_tiles, 1, tile),)
    return pl.pallas_call(
        functools.partial(_fetch_rows_kernel, lines=lines, halves=halves,
                          scaled=scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles if n_live is None
                  else tiles_written(live, tile, zero_to, n_tiles),),
            in_specs=[
                pl.BlockSpec((None, 1, tile), lambda i, n: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((None, 1, tile),
                             lambda i, n: (jnp.minimum(i + 1, last), 0, 0),
                             memory_space=pltpu.SMEM),
                *[per_tile for _ in scales],
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, width), lambda i, n: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, tile * lines, _LANES), words.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, width), src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=FETCH_NAME,
    )(live.reshape(1), index, index, *scales, words)


def _fetch_sum_kernel(n_live, index, index_next, index_v, *refs, lines,
                      halves, k, weighted, dot):
    refs = list(refs)
    weights = refs.pop(0) if weighted else None
    g = refs.pop(0) if dot else None
    src, out, stage, sem, started = refs
    i, steps = pl.program_id(0), pl.num_programs(0)
    slot = lax.rem(i, 2)
    tile = index_v.shape[0]
    live_rows = n_live[0]

    def start(index, slot):
        # ... and count the copies: the wait is for as many
        def body(t, count):
            first = lax.mul(t, np.int32(k))

            def pair(j, count):
                r = index[0, lax.add(first, j)]
                fetched = lax.lt(r, live_rows)

                @pl.when(fetched)
                def _():
                    _row_copy(src, r, stage.at[slot, j], t, sem.at[slot],
                              lines)
                return lax.add(count, lax.convert_element_type(
                    fetched, jnp.int32))

            return lax.fori_loop(0, k, pair, count)

        started[slot] = lax.fori_loop(0, tile, body, jnp.int32(0))

    @pl.when(i == 0)
    def _():
        start(index, slot)

    @pl.when(i + 1 < steps)
    def _():
        start(index_next, 1 - slot)

    # eight rows a wait, then the rest one by one
    count = started[slot]
    lax.fori_loop(0, lax.div(count, 8), lambda _, c: _wait_rows(
        src, stage.at[slot, 0], sem.at[slot], lines, 8) or c, 0)
    lax.fori_loop(0, lax.rem(count, 8), lambda _, c: _wait_rows(
        src, stage.at[slot, 0], sem.at[slot], lines, 1) or c, 0)

    # a pair that fetched nothing reads what the buffer held: select,
    # never multiply
    live = [_wide(lax.lt(index_v[:, j:j + 1],
                         lax.full((tile, 1), live_rows, jnp.int32)))
            for j in range(k)]
    by = [_wide(weights[:, j:j + 1]) for j in range(k)] if weighted else None
    zeros = lax.full((tile, _LANES), 0.0, jnp.float32)

    def line(c, dots):
        sums = [None] * halves
        dots = list(dots)
        for j in range(k):
            block = stage[slot, j, pl.ds(c, tile, stride=lines), :]
            for h, numbers in enumerate(_numbers(block, halves)):
                numbers = lax.select(live[j], numbers, zeros)
                if dot:
                    dots[j] = lax.add(dots[j], lax.mul(
                        numbers, lax.convert_element_type(
                            g[:, _lanes(lax.add(c, np.int32(h * lines)))],
                            jnp.float32)))
                    continue
                if weighted:
                    numbers = lax.mul(numbers, by[j])
                sums[h] = numbers if sums[h] is None \
                    else lax.add(sums[h], numbers)
        if not dot:
            for h in range(halves):
                out[:, _lanes(lax.add(c, np.int32(h * lines)))] = \
                    lax.convert_element_type(sums[h], out.dtype)
        return tuple(dots)

    dots = lax.fori_loop(0, lines, line, (zeros,) * k if dot else ())
    if dot:
        for j in range(k):
            out[:, j:j + 1] = jnp.sum(dots[j], axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "tile", "interpret"))
def _fetch_sum(words, index, weights, g, n_live, *, out_dtype, tile,
               interpret):
    tokens, k = index.shape
    halves = 2 if words.dtype == jnp.uint32 else 1
    lines = words.shape[0] // (tokens * k)
    width = lines * halves * _LANES
    n_tiles = tokens // tile
    last = n_tiles - 1
    dot = g is not None
    live = jnp.int32(tokens * k) if n_live is None \
        else n_live.astype(jnp.int32)
    index = index.astype(jnp.int32)
    flat = index.reshape(n_tiles, 1, tile * k)
    per_token = pl.BlockSpec((tile, k), lambda i, n: (i, 0))
    operands, specs = [], []
    if weights is not None:
        operands.append(weights.astype(jnp.float32))
        specs.append(per_token)
    if dot:
        operands.append(g)
        specs.append(pl.BlockSpec((tile, width), lambda i, n: (i, 0)))
    return pl.pallas_call(
        functools.partial(_fetch_sum_kernel, lines=lines, halves=halves,
                          k=k, weighted=weights is not None, dot=dot),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((None, 1, tile * k), lambda i, n: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((None, 1, tile * k),
                             lambda i, n: (jnp.minimum(i + 1, last), 0, 0),
                             memory_space=pltpu.SMEM),
                per_token, *specs,
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, k if dot else width),
                                   lambda i, n: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k, tile * lines, _LANES), words.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct(
            (tokens, k if dot else width),
            jnp.float32 if dot else out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=FETCH_DOT_NAME if dot else FETCH_SUM_NAME,
    )(live.reshape(1), flat, flat, index, *operands, words)


def fetch_sum_rows(words, index, weights=None, n_live=None, *, dtype,
                   tile=None):
    """``out[t] = sum_j weights[t, j] * src[index[t, j]]`` over the pairs
    with ``index[t, j] < n_live``, accumulated in float32 in the order j =
    0 .. k-1 and rounded once to ``dtype``. ``words``:
    ``as_words(src, n_live)`` of the ``src`` [T * k, M] (its rows from
    ``n_live`` on are never fetched), ``index`` [T, k] int32 in ``[0, T *
    k)``, ``weights`` [T, k] float32 (None: ones), ``n_live`` an int32
    scalar (None: all)."""
    return _fetch_sum(words, index, weights, None, n_live,
                      out_dtype=jnp.dtype(dtype),
                      tile=tile or _token_tile(*index.shape),
                      interpret=_interpret())


def fetch_dot_rows(words, index, g, n_live=None, *, tile=None):
    """``out[t, j] = <src[index[t, j]], g[t]>`` in float32 for the pairs
    with ``index[t, j] < n_live``, 0 for the others. ``words``:
    ``as_words(src, n_live)`` of ``src`` [T * k, M]; ``index`` [T, k], ``g``
    [T, M]."""
    return _fetch_sum(words, index, None, g, n_live, out_dtype=None,
                      tile=tile or _token_tile(*index.shape),
                      interpret=_interpret())
