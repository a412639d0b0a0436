"""One token of power retention over the lanes' state where it lies: a
Pallas kernel that reads each lane's ``S`` once and writes it once.

``ops/power_retention.py`` has the equations. For one token per lane the
state's update and the query are one pass over ``S``:

    S' = g S + v phi(k)^T          y_i = S' phi(q_i)     (i: the query
                                                          heads of a KV head)

Left to XLA the update is one fusion (read, write, in place) and the query
another whose operand cannot be a slice of the stacked leaf, so a layer's
whole state is copied out first: five passes over 1.1 GB a layer at 32
lanes of the 14B widths, where two are needed. The kernel ``ret_step``
takes the stacked ``[n_layer, B, Hkv, d, D]`` leaf whole (aliased to its
result; this call's layer is a prefetched scalar of the index maps), and
for each (lane, KV head) walks the ``D`` axis in blocks: load a ``[d,
block]`` tile, form the new tile on the vector unit, store it, and add its
products with each query head's ``phi(q)`` into a ``[d, 128]`` accumulator
per head, whose lanes are summed at the lane's last block. ``S`` is stored
with ``D`` minor so that ``phi(k)`` and ``phi(q)`` are rows (sublane
broadcasts) and ``v`` and the gate are one ``[d, 2]`` column block.

The normaliser ``z`` is 1/128 of the state and stays in plain XLA.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import STATE_TILE_BYTES
from deepspeed_tpu.ops.pallas.common import interpret as _interpret

KERNEL_NAME = "ret_step"
_LANE = 128


def block_columns(d: int, D: int) -> int:
    """Columns of ``D`` in a tile: the largest multiple of 128 dividing
    ``D`` whose ``[d, block]`` float32 tile is at most ``STATE_TILE_BYTES``;
    all of ``D`` where 128 does not divide it (small shapes)."""
    if D % _LANE:
        return D
    best = _LANE
    for n in range(1, D // _LANE + 1):
        block = n * _LANE
        if D % block == 0 and d * block * 4 <= STATE_TILE_BYTES:
            best = block
    return best


def _kernel(layer_ref, s_ref, gv_ref, pk_ref, pq_ref, out_ref, y_ref,
            acc_ref, *, groups, fold):
    del layer_ref  # the index maps' alone
    t, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(t == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gv = gv_ref[...]                            # [d, 2]: v, then the gate
    new = s_ref[...].astype(jnp.float32) * gv[:, 1:2] \
        + gv[:, 0:1] * pk_ref[...]                          # [d, block]
    out_ref[...] = new.astype(out_ref.dtype)
    width = new.shape[1] // fold
    for i in range(groups):
        prod = new * pq_ref[i:i + 1, :]
        part = prod[:, :width]
        for c in range(1, fold):
            part = part + prod[:, c * width:(c + 1) * width]
        acc_ref[i] += part

    @pl.when(t == last)
    def _():
        for i in range(groups):
            y_ref[:, i:i + 1] = jnp.sum(acc_ref[i], axis=1, keepdims=True)


def retention_step_update(S, layer, g, v, phik, phiq, *, block=None):
    """``S`` ``[n_layer, B, Hkv, d, D]`` float32 (or one layer's ``[B, Hkv,
    d, D]`` with ``layer`` None), ``g`` ``[B, Hkv]``, ``v`` ``[B, Hkv, d]``,
    ``phik`` ``[B, Hkv, D]``, ``phiq`` ``[B, Hkv, G, D]``. Returns ``(S
    with layer ``layer`` replaced by g S + v phi(k)^T, in place where the
    caller donates it; num [B, Hkv, G, d] = S' phi(q))``."""
    one_layer = layer is None
    if one_layer:
        S, layer = S[None], 0
    n_layer, B, Hkv, d, D = S.shape
    G = phiq.shape[2]
    block = block or block_columns(d, D)
    if D % block:
        raise ValueError(f"block {block} does not divide D = {D}")
    f32 = jnp.float32
    gv = jnp.stack([v.astype(f32),
                    jnp.broadcast_to(g.astype(f32)[..., None], v.shape)],
                   axis=-1)                                 # [B, Hkv, d, 2]
    fold = block // _LANE if block % _LANE == 0 else 1

    def s_map(b, h, t, layer_ref):
        return layer_ref[0], b, h, 0, t

    def col_map(b, h, t, layer_ref):
        return b, h, 0, 0

    def row_map(b, h, t, layer_ref):
        return b, h, 0, t

    S, y = pl.pallas_call(
        functools.partial(_kernel, groups=G, fold=fold),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, D // block),
            in_specs=[
                pl.BlockSpec((None, None, None, d, block), s_map),
                pl.BlockSpec((None, None, d, 2), col_map),
                pl.BlockSpec((None, None, 1, block), row_map),
                pl.BlockSpec((None, None, G, block), row_map),
            ],
            out_specs=[
                pl.BlockSpec((None, None, None, d, block), s_map),
                pl.BlockSpec((None, None, d, G), col_map),
            ],
            scratch_shapes=[pltpu.VMEM((G, d, block // fold), f32)]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, d, G), f32)],
        input_output_aliases={1: 0},
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), S, gv,
      phik.astype(f32)[:, :, None, :], phiq.astype(f32))
    return (S[0] if one_layer else S), jnp.swapaxes(y, -1, -2)
