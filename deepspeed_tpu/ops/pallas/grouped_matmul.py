"""The experts' grouped matmul as a Pallas kernel that keeps one expert's
matrix on chip while that expert's rows stream past.

Rows ``[R, K]`` lie sorted by group (an expert's (token, expert) pairs are
one run of rows), ``group_sizes`` ``[E]`` says at run time how long each
run is, every shape is static. Three products, ``jax.lax.ragged_dot``'s and
its two gradients':

* :func:`gmm` ``(lhs [R, K], rhs [E, K, N]) -> [R, N]``: row r times its
  group's matrix; with ``transpose_rhs`` the matrices are ``[E, N, K]`` and
  are contracted on their LAST axis inside the kernel (the rows' gradient:
  no transposed copy of the experts' tensor is ever written); with
  ``layer`` the matrices are a stack ``[layers, E, K, N]`` read where it
  lies, the layer one more prefetched scalar in their block index (a
  serving call inside the layer scan: no slice of the stack is written
  out for the custom call);
* :func:`tgmm` ``(lhs [R, K], dout [R, N]) -> [E, K, N]``: each group's
  rows contracted (the matrices' gradient), a group of no rows zeros.

Left to the TPU compiler a ragged dot becomes a Mosaic call of its own
(``ragged-dot-none.N``) with tiles nothing in a program can set; on the
v5e it stood at 46% of its roofline at the OLMoE widths (PERF.md, section
6, PR 38). Tiled over K, an expert's block of weights changes at every
grid step and every row tile reads the whole matrix again: bound by bytes
by construction. Here **the whole of K is one tile**: the weights' block
index is ``(group, 0, n)`` and does not change between consecutive row
tiles of one group, so the pipeline fetches each expert's matrix once and
the rows stream under it; the product of a row tile is one ``dot`` with
float32 accumulation over all of K and ONE rounding at the store, which is
``ragged_dot``'s arithmetic.

The walk. Row tiles are ``tm`` rows at fixed boundaries; a group that ends
inside a tile shares it with the next, and such a tile is visited once for
each group that has rows in it (the visit computes the whole tile and
stores its own rows under a mask). The visits are laid out before the call
by a few small XLA operations (:func:`row_walk`: per visit its group and
its row tile, prefetched to SMEM for the index maps): at most ``R / tm +
E`` of them, the grid's static length; the visits past the real ones
repeat the last one's blocks and do nothing. Rows that no group covers
(``sum(group_sizes) < R``) belong to a last pseudo-group whose visits store
zeros: ``ragged_dot`` gives zeros there, and ``moe/sharded_moe.py``
``rows_computed`` counts dropped pairs from exactly those. All three
products make the same walk, so a layer lays it out once for its nine
calls (``walk=``).

``tgmm`` walks the same visits with the group's ``[tk, tn]`` output block
resident in a float32 accumulator: cleared at a group's first visit, rows
of other groups masked to zero, stored (one rounding) at its last. An
empty group has one visit, which stores the cleared accumulator (and at
which ``gmm`` does nothing).

Tiles come from ``ops/pallas/autotune.py`` ``grouped_matmul_tiles`` (a
table found on the chip, a default from the shapes); nothing is searched
at run time. The calls are named ``ragged-dot-gmm`` / ``ragged-dot-tgmm``:
it is what they compute, and the trace's readers find a program's grouped
matmuls by that prefix whatever implements them.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import autotune
from deepspeed_tpu.ops.pallas.common import interpret as _interpret

GMM_NAME = "ragged-dot-gmm"
TGMM_NAME = "ragged-dot-tgmm"

# what a call may ask of VMEM beyond the 16 MB it gets unasked: the v5e has
# 128 MiB, and a whole [2048, 1024] bf16 matrix double-buffered is 8 MB
# before the row tiles and the float32 product
_VMEM_CEILING = 96 << 20


def row_walk(group_sizes, rows, tm):
    """The walk over row tiles of ``tm`` rows that all three products make,
    as int32 vectors for SMEM: ``offsets`` [E + 2] (group g is rows
    ``offsets[g]:offsets[g + 1]``; group E is the pseudo-group of the rows
    no group covers), ``group`` [V] and ``tile`` [V] of each visit,
    ``count`` [1] real visits; ``V = rows / tm + E`` is the most there can
    be, and visits from ``count`` on repeat the last one. A group with rows
    is visited once for every tile it has rows in, a group of none once
    (``tgmm`` has its zeros to store; ``gmm`` does nothing there), the
    pseudo-group only if it has rows. One walk serves every call over the
    same ``group_sizes`` (a layer's nine): pass it as ``walk``.

    In ``lax`` primitives throughout: every ``jnp`` function and array
    operator is a jitted helper that a new process traces at its first
    use with each shape, and a program's set-up is judged."""
    i32 = jnp.int32

    def const(like, value):
        return lax.full_like(like, value)

    sizes = lax.convert_element_type(group_sizes, i32)
    experts = sizes.shape[0]
    left = lax.sub(i32(rows), lax.reduce_sum(sizes, (0,)))
    sizes = lax.concatenate(
        [sizes, lax.reshape(lax.max(left, i32(0)), (1,))], 0)
    groups, tiles_m = experts + 1, rows // tm
    visits = tiles_m + experts
    ends = lax.cumsum(sizes)
    first = lax.div(lax.sub(ends, sizes), const(sizes, tm))
    per_group = lax.select(
        lax.gt(sizes, const(sizes, 0)),
        lax.sub(lax.div(lax.add(ends, const(ends, tm - 1)), const(ends, tm)),
                first),
        lax.convert_element_type(                # 1, but 0 for the rest
            lax.lt(lax.iota(i32, groups), const(sizes, experts)), i32))
    visit_end = lax.cumsum(per_group)
    count = lax.slice(visit_end, (groups - 1,), (groups,))
    v = lax.min(lax.iota(i32, visits), lax.broadcast_in_dim(
        lax.sub(count, const(count, 1)), (visits,), (0,)))
    # visit v is group g's when visit_end[g - 1] <= v < visit_end[g]
    wide = (visits, groups)
    group = lax.reduce_sum(lax.convert_element_type(lax.le(
        lax.broadcast_in_dim(visit_end, wide, (1,)),
        lax.broadcast_in_dim(v, wide, (0,))), i32), (1,))
    mine = lax.convert_element_type(lax.eq(
        lax.broadcast_in_dim(group, wide, (0,)),
        lax.broadcast_in_dim(lax.iota(i32, groups), wide, (1,))), i32)
    # ... and its tile the group's first plus the visits the group has had
    # (a pick by a masked sum: no gather)
    tile = lax.add(v, lax.reduce_sum(lax.mul(mine, lax.broadcast_in_dim(
        lax.sub(first, lax.sub(visit_end, per_group)), wide, (1,))), (1,)))
    return (lax.concatenate([lax.full((1,), 0, i32), ends], 0), group,
            lax.clamp(i32(0), tile, i32(tiles_m - 1)), count)


def row_tile_visits(group_sizes, rows, tm) -> int:
    """How many visits :func:`row_walk` makes for these ``group_sizes``
    (host arithmetic on counts): ``rows / tm`` when no group ends inside a
    tile, one more for each that does and for each group of no rows."""
    sizes = np.asarray(group_sizes, np.int64)
    ends = np.cumsum(sizes)
    rest = max(rows - int(sizes.sum()), 0)
    return int(np.where(sizes > 0, -(-ends // tm) - (ends - sizes) // tm,
                        1).sum()
               + (-(-rows // tm) - (rows - rest) // tm if rest else 0))


def _own_rows(offsets, g, tile, shape):
    """[tm, n] bool: the rows of row tile ``tile`` that are group ``g``'s."""
    row = tile * shape[0] + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _gmm_kernel(offsets, group, tile, count, *refs, groups, transpose_rhs):
    # (over a stack of layers the layer's index is a fifth prefetched
    # scalar, which the matrices' index map alone reads)
    lhs_ref, rhs_ref, out_ref = refs[-3:]
    # the grid is (column tiles, visits); (no program_id under a `when`)
    v = pl.program_id(1)
    g, t = group[v], tile[v]
    live = (v < count[0]) & (offsets[g + 1] > offsets[g])

    @pl.when(live & (g < groups))
    def _():
        product = lax.dot_general(
            lhs_ref[...], rhs_ref[...],
            (((1,), (1 if transpose_rhs else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[...] = lax.select(
            _own_rows(offsets, g, t, out_ref.shape),
            product.astype(out_ref.dtype), out_ref[...])

    @pl.when(live & (g == groups))  # rows that no group covers
    def _():
        out_ref[...] = lax.select(
            _own_rows(offsets, g, t, out_ref.shape),
            jnp.zeros(out_ref.shape, out_ref.dtype), out_ref[...])


def _tgmm_kernel(offsets, group, tile, count, lhs_ref, dout_ref, out_ref,
                 acc_ref, *, groups):
    v = pl.program_id(1)
    g, t = group[v], tile[v]
    last_visit = count[0] - 1

    @pl.when((v <= last_visit) & (g < groups))
    def _():
        # mask whichever operand is narrower
        lhs, dout = lhs_ref[...], dout_ref[...]
        if dout.shape[1] <= lhs.shape[1]:
            dout = lax.select(_own_rows(offsets, g, t, dout.shape),
                              dout, lax.full_like(dout, 0))
        else:
            lhs = lax.select(_own_rows(offsets, g, t, lhs.shape),
                             lhs, lax.full_like(lhs, 0))

        @pl.when((v == 0) | (group[lax.max(v - 1, 0)] != g))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

        acc_ref[...] += lax.dot_general(
            lhs, dout, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when((v == last_visit)
                 | (group[lax.min(v + 1, last_visit)] != g))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _params(vmem_bytes):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(min(_VMEM_CEILING,
                                 max(32 << 20, 2 * vmem_bytes))))


def _tiles_and_walk(kind, tiles, walk, group_sizes, rows, k, n, groups,
                    dtype):
    """The call's ``(tm, tk, tn)`` and its walk: the table's tiles unless
    ``tiles`` are given, and a ``walk`` that is given decides ``tm`` (its
    length is ``rows / tm + groups``)."""
    tm, tk, tn = tiles or autotune.grouped_matmul_tiles(
        kind, rows, k, n, groups, dtype)
    if walk is None:
        return (tm, tk, tn), row_walk(group_sizes, rows, tm)
    return (rows // (walk[1].shape[0] - groups), tk, tn), tuple(walk)


def gmm(lhs, rhs, group_sizes, *, transpose_rhs=False, tiles=None,
        walk=None, layer=None):
    """``out[r] = lhs[r] @ rhs[g(r)]`` (``lhs[r] @ rhs[g(r)].T`` with
    ``transpose_rhs``) for rows sorted by group, zeros for the rows past
    ``sum(group_sizes)``; float32 accumulation over all of K, rounded once
    to ``lhs``'s dtype. ``tiles`` ``(tm, tn)`` overrides the table's;
    ``walk`` is :func:`row_walk`'s of these sizes, made once for several
    calls (its ``tm`` then holds).

    With ``layer`` (an int32 scalar, traced or not) ``rhs`` is a STACK
    ``[layers, E, K, N]`` of such tensors as it lies in memory and the
    call multiplies by ``rhs[layer]``: the layer is one more prefetched
    scalar and leads the matrices' block index, so no ``[E, K, N]`` tensor
    is made. (Inside a layer scan the slice ``rhs[layer]`` is an operand
    that XLA writes out whole before a custom call may read it: three
    times 314 MB a layer at DeepSeek-V2's widths, PERF.md section 6, PR
    44.) Same blocks, same walk, same arithmetic: bitwise the slice's."""
    (rows, k), groups = lhs.shape, rhs.shape[-3]
    n = rhs.shape[-2] if transpose_rhs else rhs.shape[-1]
    (tm, _, tn), walk = _tiles_and_walk(
        "gmm_t" if transpose_rhs else "gmm",
        tiles and (tiles[0], k, tiles[1]), walk, group_sizes, rows, k, n,
        groups, lhs.dtype)
    if layer is not None:
        walk += (lax.reshape(lax.convert_element_type(layer, jnp.int32),
                             (1,)),)
    return _gmm(lhs, rhs, walk, transpose_rhs=transpose_rhs, tiles=(tm, tn),
                interpret=_interpret())


# The calls are jitted so that one program's many calls of one signature
# (up and gate, the custom VJP's primal and forward rule) are traced once:
# tracing a Pallas call is tens of milliseconds of a program's set-up,
# which is judged.
@functools.partial(jax.jit,
                   static_argnames=("transpose_rhs", "tiles", "interpret"))
def _gmm(lhs, rhs, walk, *, transpose_rhs, tiles, interpret):
    # ``walk`` is the four vectors, or five with the layer of a stacked
    # ``rhs`` [layers, E, ., .] last
    rows, k = lhs.shape
    groups = rhs.shape[-3]
    n = rhs.shape[-2] if transpose_rhs else rhs.shape[-1]
    tm, tn = tiles
    item = lhs.dtype.itemsize

    def rhs_map(j, v, offsets, group, tile, count, *layer):
        g = lax.min(group[v], groups - 1)
        return tuple(at[0] for at in layer) + (
            (g, j, 0) if transpose_rhs else (g, 0, j))

    return pl.pallas_call(
        functools.partial(_gmm_kernel, groups=groups,
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk),
            grid=(n // tn, walk[1].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, o, g, t, *_: (t[v], 0)),
                pl.BlockSpec((None,) * (rhs.ndim - 2)
                             + ((tn, k) if transpose_rhs else (k, tn)),
                             rhs_map),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, o, g, t, *_: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=_params(
            autotune.grouped_matmul_vmem_bytes("gmm", tm, k, tn, item)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=item * (rows * k * (n // tn) + groups * k * n
                                   + rows * n)),
        interpret=interpret,
        name=GMM_NAME,
    )(*walk, lhs, rhs)


def tgmm(lhs, dout, group_sizes, *, out_dtype=None, tiles=None, walk=None):
    """``out[g] = lhs[rows of g].T @ dout[rows of g]`` ``[E, K, N]`` for
    rows sorted by group, zeros for a group of no rows; float32
    accumulation over a group's rows, rounded once to ``out_dtype``
    (``lhs``'s). ``tiles`` ``(tm, tk, tn)`` overrides the table's;
    ``walk`` as :func:`gmm`'s."""
    groups = group_sizes.shape[0] if walk is None else walk[0].shape[0] - 2
    tiles, walk = _tiles_and_walk(
        "tgmm", tiles, walk, group_sizes, lhs.shape[0], lhs.shape[1],
        dout.shape[1], groups, lhs.dtype)
    return _tgmm(lhs, dout, walk, out_dtype=jnp.dtype(out_dtype or lhs.dtype),
                 tiles=tiles, interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "tiles", "interpret"))
def _tgmm(lhs, dout, walk, *, out_dtype, tiles, interpret):
    rows, k = lhs.shape
    n = dout.shape[1]
    groups = walk[0].shape[0] - 2
    tm, tk, tn = tiles
    item = lhs.dtype.itemsize

    def out_map(j, v, offsets, group, tile, count):
        return lax.min(group[v], groups - 1), j // (n // tn), j % (n // tn)

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk * (n // tn), walk[1].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, v, o, g, t, c: (t[v], j // (n // tn))),
                pl.BlockSpec((tm, tn),
                             lambda j, v, o, g, t, c: (t[v], j % (n // tn))),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        compiler_params=_params(
            autotune.grouped_matmul_vmem_bytes("tgmm", tm, tk, tn, item)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=item * rows * (k * (n // tn) + n * (k // tk))
            + out_dtype.itemsize * groups * k * n),
        interpret=interpret,
        name=TGMM_NAME,
    )(*walk, lhs, dout)


def grouped_matmul(lhs, rhs, group_sizes, walk=None, stack=None, layer=None):
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes)`` through the kernels:
    forward :func:`gmm`; backward the rows' gradient by :func:`gmm` on the
    matrices as they lie (contracted on their last axis) and the matrices'
    gradient by :func:`tgmm`. The residuals are ``ragged_dot``'s own, the
    two operands, and the walk's four small vectors in place of the sizes.
    ``walk``: :func:`row_walk` of these sizes, where a layer makes it once
    for all its calls.

    With ``stack`` ``[layers, E, K, N]`` and ``layer`` (inside a layer
    scan: what the scan's owner closes over and the turn's index), ``rhs``
    is the scan's slice ``stack[layer]`` and is what the call is
    DIFFERENTIATED with respect to, but its values are never read: the
    forward call and the rows' gradient multiply by the stack where it
    lies (``gmm(..., layer=)``), the residuals are the rows, the walk and
    the index (the stack is the loop's invariant, not a copy), and
    :func:`tgmm`'s result is the cotangent of the slice, which the scan
    stacks into the parameter's gradient as it does any layer's. The
    stack gets no cotangent (hand it over under ``lax.stop_gradient``: one
    the size of the stack would otherwise be summed every turn). The same
    kernels on the same blocks: the values are the slice route's to the
    bit, and the slice, read by nothing, leaves the program."""
    if walk is None:
        walk = row_walk(group_sizes, lhs.shape[0], autotune.grouped_matmul_tiles(
            "gmm", *lhs.shape, rhs.shape[2], rhs.shape[0], lhs.dtype)[0])
    return _grouped_matmul(lhs, rhs, tuple(walk), stack, layer)


@jax.custom_vjp
def _grouped_matmul(lhs, rhs, walk, stack, layer):
    return _grouped_matmul_fwd(lhs, rhs, walk, stack, layer)[0]


def _grouped_matmul_fwd(lhs, rhs, walk, stack, layer):
    # what the products read: the slice, or the stack in its place
    read = rhs if stack is None else stack
    return (gmm(lhs, read, None, walk=walk, layer=layer),
            (lhs, read, walk, layer))


def _grouped_matmul_bwd(residuals, dout):
    lhs, read, walk, layer = residuals
    dout = dout.astype(lhs.dtype)
    return (gmm(dout, read, None, transpose_rhs=True, walk=walk, layer=layer),
            tgmm(lhs, dout, None, out_dtype=read.dtype, walk=walk),
            None, None, None)


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def supported(rows, k, n, dtype) -> bool:
    """Whether the kernels take these shapes: K and N whole lanes (128), the
    rows a whole number of row tiles (16 rows at least: a packed bf16
    sublane tile), bf16 or float32 operands."""
    return (k % 128 == 0 and n % 128 == 0 and rows % 16 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))
