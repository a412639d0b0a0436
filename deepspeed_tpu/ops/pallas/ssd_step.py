"""One token of the Mamba-2 recurrence over the lanes' state where it lies:
a Pallas kernel that reads each lane's ``S`` once and writes it once.

``ops/ssd.py`` has the equations. For one token per lane the state's
update and the output are one pass over ``S`` (a head's ``[P, N]``):

    S' = decay S + (dt x) (outer) B          y_p = sum_n S'[p, n] C[n]

Left to XLA (``ssd_step`` on a slice of the stacked leaf) they are two
fusions: the update in place (read, write), and ``y``, which reads the OLD
state again and recomputes the update to reduce it: three passes over 268
MB a layer at 64 lanes of the 34B widths where two are needed, 1.18 ms a
layer on the v5e against the 0.66 ms of its bytes (the kernel: 0.84). The
kernel ``ssm_step`` takes the stacked ``[n_layer, B, H, P, N]`` leaf whole
(aliased to its result; this call's layer is a prefetched scalar of the
index maps) and for each (lane, tile of heads) loads the tile, forms the
new state on the vector unit in float32, stores it in the leaf's dtype, and
sums its products with ``C`` over ``N`` from the tile it already holds.
``N`` is the minor axis and fits a tile whole, so nothing is carried
between grid steps. ``C``'s contraction is a multiply and a lane reduction,
not a ``dot``: the matrix unit would round the state to bf16 at default
precision, and six passes at ``highest`` are slow for a handful of rows.

The tile: a head's ``[128, 256]`` float32 block is 128 KB and a grid step
costs ~0.5 us whatever it moves, so one head a step would be 2,048 steps a
layer, ~1 ms. The heads of one group share ``B`` and ``C``; sixteen of
them are 2 MB, what ``ret_step`` walks (four such tiles live, double-
buffered in and out, under the 16 MB a kernel may use unasked): 128 steps
a layer. Measured, ms a layer: 0.879 / 0.853 / 0.837 at 4 / 8 / 16 heads,
0.826 at 32 with the limit raised: not worth a tile that spans two groups.
``dt x`` and ``y`` travel as ``[P, heads]`` blocks, ``P`` on the sublanes
as in the state's tiles, so that ``dt x`` is a column broadcast and ``y`` a
column store; each head's decay is a scalar in SMEM.

``decay = exp(dt A)``, ``dt x`` and the skip term ``D x`` are kilobytes
and stay in plain XLA (``ops/ssd.py`` ``ssd_step_stacked``).
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import STATE_TILE_BYTES
from deepspeed_tpu.ops.pallas.common import interpret as _interpret

KERNEL_NAME = "ssm_step"


def block_heads(H: int, G: int, P: int, N: int) -> int:
    """Heads in a tile: the most that divide a group's ``H / G`` (they
    share ``B`` and ``C``) whose ``[heads, P, N]`` float32 tile is at most
    ``STATE_TILE_BYTES``; at least one."""
    per_group = H // G
    return max(n for n in range(1, per_group + 1)
               if per_group % n == 0
               and (n == 1 or n * P * N * 4 <= STATE_TILE_BYTES))


def _kernel(layer_ref, decay_ref, s_ref, xdt_ref, bc_ref, out_ref, y_ref):
    del layer_ref  # the index maps' alone
    heads = s_ref.shape[0]
    first = (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)) * heads
    b_row, c_row = bc_ref[0:1, :], bc_ref[1:2, :]             # [1, N] each
    for i in range(heads):
        new = s_ref[i].astype(jnp.float32) * decay_ref[first + i] \
            + xdt_ref[:, i:i + 1] * b_row                     # [P, N]
        out_ref[i] = new.astype(out_ref.dtype)
        y_ref[:, i:i + 1] = jnp.sum(new * c_row, axis=1, keepdims=True)


def ssm_step_update(S, layer, decay, xdt, Bm, Cm, *, heads=None):
    """``S`` ``[n_layer, B, H, P, N]`` (or one layer's ``[B, H, P, N]``
    with ``layer`` None), ``decay`` ``[B, H]``, ``xdt`` ``[B, H, P]``,
    ``Bm`` / ``Cm`` ``[B, G, N]``. Returns ``(S with layer ``layer``
    replaced by decay S + xdt (outer) B in S's dtype, in place where the
    caller donates it; y [B, H, P] float32 = sum_n S' C)``."""
    one_layer = layer is None
    if one_layer:
        S, layer = S[None], 0
    n_layer, B, H, P, N = S.shape
    G = Bm.shape[1]
    per_group = H // G
    heads = heads or block_heads(H, G, P, N)
    if per_group % heads:
        raise ValueError(f"a tile of {heads} heads does not divide the "
                         f"{per_group} heads of a group (H = {H}, G = {G})")
    tiles = H // heads
    f32 = jnp.float32

    def s_map(b, t, layer_ref, decay_ref):
        return layer_ref[0], b, t, 0, 0

    def col_map(b, t, layer_ref, decay_ref):
        return b, t, 0, 0

    def bc_map(b, t, layer_ref, decay_ref):
        return b, t * heads // per_group, 0, 0

    # [B, H, P] -> [B, tiles, P, heads]: P on the sublanes, as in S's tiles
    xdt = jnp.swapaxes(xdt.astype(f32).reshape(B, tiles, heads, P), -1, -2)
    S, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, tiles),
            in_specs=[
                pl.BlockSpec((None, None, heads, P, N), s_map),
                pl.BlockSpec((None, None, P, heads), col_map),
                pl.BlockSpec((None, None, 2, N), bc_map),
            ],
            out_specs=[
                pl.BlockSpec((None, None, heads, P, N), s_map),
                pl.BlockSpec((None, None, P, heads), col_map),
            ]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, tiles, P, heads), f32)],
        input_output_aliases={2: 0},
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      decay.astype(f32).reshape(B * H), S, xdt,
      jnp.stack([Bm.astype(f32), Cm.astype(f32)], axis=2))
    y = jnp.swapaxes(y, -1, -2).reshape(B, H, P)
    return (S[0] if one_layer else S), y
