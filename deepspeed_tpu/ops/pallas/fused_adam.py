"""Fused AdamW as a Pallas kernel.

TPU equivalent of the reference's multi-tensor Adam
(``csrc/adam/multi_tensor_adam.cu:163`` via ``FusedAdam``,
``ops/adam/fused_adam.py:15``): one kernel updates param, m and v in place
(input/output aliasing) in a single pass over each flat shard — one HBM
read/write per buffer instead of optax's (already XLA-fused) elementwise
chain. Exposed as an optax GradientTransformation so it slots into the
engine/ZeRO sharding machinery unchanged.
"""

import functools
from typing import NamedTuple

import chex
import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas.common import interpret as _interpret

# the kernel's name in a profiler trace and in the lowered HLO
KERNEL_NAME = "fused_adam"


def _adamw_kernel(lr_ref, c1_ref, c2_ref, p_ref, g_ref, m_ref, v_ref,
                  po_ref, mo_ref, vo_ref,
                  *, b1, b2, eps, weight_decay):
    lr = lr_ref[0, 0]
    # bias corrections precomputed host-side (Mosaic has no scalar powf)
    c1 = c1_ref[0, 0]
    c2 = c2_ref[0, 0]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    # bias correction (reference multi_tensor_adam.cu mode=ADAM_MODE_0/1)
    update = (m / c1) / (jnp.sqrt(v / c2) + eps)
    p = p_ref[...].astype(jnp.float32)
    p = p - lr * (update + weight_decay * p)
    po_ref[...] = p.astype(po_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


def fused_adamw_update(p, g, m, v, lr, step, *, b1=0.9, b2=0.999, eps=1e-8,
                       weight_decay=0.0, block_rows: int = 256):
    """Single-buffer fused update; flattens to (rows, 128) lanes for the VPU
    and streams VMEM-sized row blocks over a 1-D grid."""
    shape = p.shape
    n = p.size
    lanes = 128
    rows = max(1, -(-n // lanes))
    block_rows = min(block_rows, rows)
    rows = -(-rows // block_rows) * block_rows  # multiple of block_rows
    pad = rows * lanes - n

    def flat(x, dtype):
        x = x.reshape(-1).astype(dtype)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(rows, lanes)

    pf, gf = flat(p, p.dtype), flat(g, jnp.float32)
    mf, vf = flat(m, jnp.float32), flat(v, jnp.float32)
    step_f = jnp.asarray(step, jnp.float32)
    lr_arr = jnp.full((1, 1), lr, jnp.float32)
    c1_arr = jnp.reshape(1.0 - b1 ** step_f, (1, 1))
    c2_arr = jnp.reshape(1.0 - b2 ** step_f, (1, 1))

    from jax.experimental.pallas import tpu as pltpu

    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)
    buf_spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))

    po, mo, vo = pl.pallas_call(
        functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps,
                          weight_decay=weight_decay),
        grid=(rows // block_rows,),
        in_specs=[scalar_spec, scalar_spec, scalar_spec, buf_spec, buf_spec,
                  buf_spec, buf_spec],
        out_specs=[buf_spec, buf_spec, buf_spec],
        out_shape=[
            jax.ShapeDtypeStruct(pf.shape, p.dtype),
            jax.ShapeDtypeStruct(mf.shape, jnp.float32),
            jax.ShapeDtypeStruct(vf.shape, jnp.float32),
        ],
        input_output_aliases={3: 0, 5: 1, 6: 2},
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(lr_arr, c1_arr, c2_arr, pf, gf, mf, vf)

    def unflat(x, dtype):
        return x.reshape(-1)[:n].reshape(shape).astype(dtype)

    return unflat(po, p.dtype), unflat(mo, jnp.float32), unflat(vo, jnp.float32)


class FusedAdamWState(NamedTuple):
    count: chex.Array
    mu: optax.Updates
    nu: optax.Updates


def fused_adamw(learning_rate, b1=0.9, b2=0.999, eps=1e-8,
                weight_decay=0.0) -> optax.GradientTransformation:
    """optax wrapper around the Pallas kernel (state layout mirrors
    optax.adamw so ZeRO opt-state sharding rules apply unchanged)."""

    def init(params):
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return FusedAdamWState(
            count=jnp.zeros([], jnp.int32),
            mu=zeros,
            nu=jax.tree.map(jnp.copy, zeros),
        )

    def update(grads, state, params):
        if params is None:
            raise ValueError("fused_adamw requires params")
        # lr schedule is evaluated at the PRE-increment count (optax
        # convention: first update sees fn(0)); bias correction uses the
        # 1-indexed step like optax/reference Adam
        lr = (learning_rate(state.count) if callable(learning_rate)
              else learning_rate)
        count = state.count + 1
        step = count.astype(jnp.float32)

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
            pn, mn, vn = fused_adamw_update(
                p, g, m, v, lr, step, b1=b1, b2=b2, eps=eps,
                weight_decay=weight_decay,
            )
            new_p.append(pn)
            new_m.append(mn)
            new_v.append(vn)

        updates = jax.tree.unflatten(
            treedef, [pn - p for pn, p in zip(new_p, flat_p)]
        )
        new_state = FusedAdamWState(
            count=count,
            mu=jax.tree.unflatten(treedef, new_m),
            nu=jax.tree.unflatten(treedef, new_v),
        )
        return updates, new_state

    return optax.GradientTransformation(init, update)
