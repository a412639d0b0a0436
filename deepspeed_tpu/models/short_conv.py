"""The gated short convolution of a layer of kind ``"conv"``
(``GPTConfig.short_conv``, ``GPTConfig.layer_types``).

LFM2's ``Lfm2ShortConv``, equation for equation, on the block's normalised
input ``u`` ``[T, C]``:

    [B | C | z] = u W_in          (three parts of width C, in that order)
    g = B * z
    c_t = sum_k w[k] * g_{t - (K - 1) + k}     (depthwise, causal, K taps,
                                                w[K - 1] on the current
                                                token, zeros before the
                                                start; no bias, no
                                                activation)
    y = (C * c) W_out

The convolution is ``ops/ssd.py`` ``causal_conv1d``, the Mamba-2 mixer's,
and what a lane keeps is that mixer's leaf too: ``conv_tail`` ``[B, K - 1,
C]`` (compute dtype), the last ``K - 1`` values of ``g``. Three entry
shapes, the same equations: a pass that starts a sequence (zero tail), one
that continues it from the tail in the ``cache`` collection, and one token
(a decode step: ``K`` multiplies a channel). Where whoever runs the layers
stacks the tails of all convolution layers into one ``[layers, B, K - 1,
C]`` leaf, this call is layer ``cache_layer`` of it: it reads its slice and
writes it back in place. Like every recurrent leaf the tail cannot be cut
at a shorter prefix.

LEFT-padded prompts: a pad's input is zeroed before the input projection
(no bias anywhere: everything it projects is zero), so through leading
pads the tail stays zero and the tail after a prompt in a bucket is the
tail after the prompt alone.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.mamba2 import CONV_TAIL
from deepspeed_tpu.ops import ssd
from deepspeed_tpu.telemetry.scopes import (
    SCOPE_CONV_GATE_CONV,
    SCOPE_CONV_IN_PROJ,
    SCOPE_CONV_OUT_PROJ,
)


class ShortConv(nn.Module):
    config: "GPTConfig"  # noqa: F821  (models/transformer_lm.py)

    @nn.compact
    def __call__(self, u, *, mask=None, decode=False, cache_layer=None):
        cfg = self.config
        K = cfg.short_conv.width
        B, T, C = u.shape
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (K, C), cfg.param_dtype)

        with jax.named_scope(SCOPE_CONV_IN_PROJ):
            if mask is not None:
                u = jnp.where(mask.astype(jnp.bool_)[..., None], u, 0)
            p = nn.Dense(3 * C, use_bias=False, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="in_proj")(u)

        with jax.named_scope(SCOPE_CONV_GATE_CONV):
            g = p[..., :C] * p[..., 2 * C:]
            if decode:
                cache = self.variable("cache", CONV_TAIL, jnp.zeros,
                                      (B, K - 1, C), cfg.dtype)
                tail = cache.value if cache_layer is None else \
                    jax.lax.dynamic_index_in_dim(cache.value, cache_layer, 0,
                                                 keepdims=False)
            else:
                tail = jnp.zeros((B, K - 1, C), cfg.dtype)
            c, tail = ssd.causal_conv1d(g, conv_w, None, tail)
            if decode:
                cache.value = tail if cache_layer is None else \
                    jax.lax.dynamic_update_index_in_dim(
                        cache.value, tail, cache_layer, 0)
            y = (p[..., C:2 * C].astype(jnp.float32) * c).astype(cfg.dtype)

        with jax.named_scope(SCOPE_CONV_OUT_PROJ):
            return nn.Dense(C, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name="out_proj")(y)
