"""Stacked expert FFNs.

Parity with reference ``deepspeed/moe/experts.py:9`` (Experts = ModuleList of
cloned FFNs, each rank holding ``num_local_experts``). TPU re-design: ONE
parameter tensor with a leading ``experts`` axis, sharded over the ``ep`` mesh
axis — "local experts" are the shard XLA assigns this device; the per-expert
loop becomes a batched einsum on the MXU.
"""

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp


class StackedExperts(nn.Module):
    """[E, C, M] -> [E, C, M] two-layer FFN, vectorized over experts; or,
    with ``group_sizes`` ([E], summing to R), [R, M] -> [R, M] over rows
    sorted by expert: each einsum becomes one grouped matmul over the
    ragged groups (``jax.lax.ragged_dot``), so no row is padding and none
    is dropped.

    Param shapes carry the expert axis first (``wi: [E, M, H]``,
    ``wo: [E, H, M]``) so expert-parallel sharding rules can address it
    (see moe/layer.py moe_sharding_rules).

    ``gated=True`` makes each expert a SwiGLU FFN (Mixtral-style:
    ``wo @ (act(wg x) * (wi x))``, biasless), with a ``wg`` gate tensor
    alongside ``wi`` — same expert-parallel layout.
    """

    num_experts: int
    d_model: int
    d_hidden: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    activation: Callable = nn.gelu
    gated: bool = False
    use_bias: bool = True

    @nn.compact
    def __call__(self, x, group_sizes=None):
        E, M, H = self.num_experts, self.d_model, self.d_hidden
        if group_sizes is None:
            def matmul(a, w):
                return jnp.einsum("ecm,emh->ech", a, w)

            def per_expert(b):
                return b[:, None, :]
        else:
            def matmul(a, w):
                return jax.lax.ragged_dot(a, w, group_sizes)

            def per_expert(b):
                return jnp.repeat(b, group_sizes, axis=0,
                                  total_repeat_length=x.shape[0])

        wi = self.param("wi", nn.initializers.lecun_normal(),
                        (E, M, H), self.param_dtype)
        wo = self.param("wo", nn.initializers.lecun_normal(),
                        (E, H, M), self.param_dtype)
        x = x.astype(self.dtype)
        h = matmul(x, wi.astype(self.dtype))
        if self.use_bias:
            bi = self.param("bi", nn.initializers.zeros, (E, H),
                            self.param_dtype)
            h = h + per_expert(bi).astype(self.dtype)
        if self.gated:
            wg = self.param("wg", nn.initializers.lecun_normal(),
                            (E, M, H), self.param_dtype)
            g = matmul(x, wg.astype(self.dtype))
            h = self.activation(g) * h
        else:
            h = self.activation(h)
        y = matmul(h, wo.astype(self.dtype))
        if self.use_bias:
            bo = self.param("bo", nn.initializers.zeros, (E, M),
                            self.param_dtype)
            y = y + per_expert(bo).astype(self.dtype)
        return y
