"""The Mamba-2 mixer of a hybrid block (``GPTConfig.ssm``).

Falcon-H1's ``FalconH1Mixer``, equation for equation (``ops/ssd.py`` holds
the recurrence): input projection with the muP vector over its segments
``[z | x | B | C | dt]``, a depthwise causal convolution over ``x B C``
with SiLU, the selective state-space recurrence per head, the gate and the
norm inside each group of channels, the output projection.

Three entry shapes, the same equations:

* a pass that starts a sequence (training forward, ``prefill``): zero
  state, zero convolution tail, the chunked scan;
* a pass that continues one (``prefill_more``): the same from the state
  and tail in the ``cache`` collection;
* one token (a decode step): the recurrence itself, as one pass of the
  kernel ``ssm_step`` over the state where it lies (``ssd.ssd_step_stacked``;
  the plain ``ssd.ssd_step`` on a slice where ``step_kernel`` says no).

On the decode path the state ``[B, H, P, N]`` (float32 unless
``SSMConfig.state_dtype`` says otherwise) and the tail ``[B, d_conv - 1,
conv_dim]`` (compute dtype) are leaves ``ssm_state`` / ``conv_tail`` of the
``cache`` collection, beside attention's ``cached_key`` / ``cached_value``.
Under ``ScannedBlocks`` they are the stacked ``[n_layer, ...]`` buffers the
layer loop carries and this call is layer ``cache_layer`` of them: it reads
its slice and writes it back in place (the kernel takes the leaf whole,
aliased to its result), and produces no whole leaf otherwise. Unlike keys
and values, neither can be truncated to a shorter prefix: what rewinds or
shares a cache has to refuse such a model.

LEFT-padded prompts: a pad's input is zeroed before the input projection
(no bias: everything it projects is zero) and again after the convolution,
whose bias would otherwise put ``silu(bias)`` into the state; so through
leading pads the state and the tail stay exactly zero, and the state after
a prompt in a bucket equals the state after the prompt alone.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import ssd
from deepspeed_tpu.telemetry.scopes import (
    SCOPE_SSM_CONV,
    SCOPE_SSM_GATE_NORM,
    SCOPE_SSM_IN_PROJ,
    SCOPE_SSM_OUT_PROJ,
    SCOPE_SSM_SCAN,
)

# names of the mixer's leaves in the ``cache`` collection
SSM_STATE = "ssm_state"
CONV_TAIL = "conv_tail"


def mup_vector(m):
    """The muP factors over the input projection's columns."""
    gn = m.n_groups * m.d_state
    z, x, b, c, dt = m.multipliers
    return jnp.concatenate([
        jnp.full((m.d_inner,), z, jnp.float32),
        jnp.full((m.d_inner,), x, jnp.float32),
        jnp.full((gn,), b, jnp.float32), jnp.full((gn,), c, jnp.float32),
        jnp.full((m.n_heads,), dt, jnp.float32)])


def _dt_bias_init(key, shape, dtype, dt_min=0.001, dt_max=0.1):
    """Mamba-2's initialiser: step sizes log-uniform in ``[dt_min,
    dt_max]`` through the inverse of softplus, so that a freshly made
    model's states live from a few tokens to a few thousand, as a trained
    one's do."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (jnp.log(dt_max) - jnp.log(dt_min)) + jnp.log(dt_min))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class Mamba2Mixer(nn.Module):
    config: "GPTConfig"  # noqa: F821  (models/transformer_lm.py)

    @nn.compact
    def __call__(self, u, *, mask=None, decode=False, cache_layer=None):
        from deepspeed_tpu.models.transformer_lm import scaled, step_kernel

        cfg = self.config
        m = cfg.ssm
        B, T, C = u.shape
        H, P, N, G = m.n_heads, m.d_head, m.d_state, m.n_groups
        f32 = jnp.float32

        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (m.d_conv, m.conv_dim), cfg.param_dtype)
        conv_b = self.param("conv_bias", nn.initializers.zeros,
                            (m.conv_dim,), cfg.param_dtype)
        # A and D as the published constructor makes them
        A_log = self.param(
            "A_log", lambda k, s, d: jnp.log(jnp.arange(1, s[0] + 1, dtype=d)),
            (H,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,),
                             cfg.param_dtype)
        D = self.param("D", nn.initializers.ones, (H,), cfg.param_dtype)

        keep = None if mask is None else mask.astype(jnp.bool_)[..., None]

        with jax.named_scope(SCOPE_SSM_IN_PROJ):
            if keep is not None:
                u = jnp.where(keep, u, 0)
            p = nn.Dense(m.in_proj_dim, use_bias=False, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="in_proj")(
                             scaled(u, m.in_multiplier))
            p = (p.astype(f32) * mup_vector(m)).astype(cfg.dtype)
            z = p[..., :m.d_inner]
            xBC = p[..., m.d_inner:m.d_inner + m.conv_dim]
            dt = p[..., m.d_inner + m.conv_dim:]

        # ---- the cache: this layer's state and tail ----------------------
        cache = None
        if decode:
            cache = {
                SSM_STATE: self.variable("cache", SSM_STATE, jnp.zeros,
                                         (B, H, P, N), m.state_dtype),
                CONV_TAIL: self.variable("cache", CONV_TAIL, jnp.zeros,
                                         (B, m.d_conv - 1, m.conv_dim),
                                         cfg.dtype)}

        def leaf(name, shape, dtype):
            if cache is None:
                return jnp.zeros(shape, dtype)
            v = cache[name].value
            return v if cache_layer is None else \
                jax.lax.dynamic_index_in_dim(v, cache_layer, 0,
                                             keepdims=False)

        def put(name, val):
            if cache is None:
                return
            var = cache[name]
            val = val.astype(var.value.dtype)
            var.value = val if cache_layer is None else \
                jax.lax.dynamic_update_index_in_dim(
                    var.value, val, cache_layer, 0)

        with jax.named_scope(SCOPE_SSM_CONV):
            tail = leaf(CONV_TAIL, (B, m.d_conv - 1, m.conv_dim), cfg.dtype)
            xBC, tail = ssd.causal_conv1d(xBC, conv_w, conv_b, tail)
            put(CONV_TAIL, tail)
            xBC = nn.silu(xBC)                               # float32
            if keep is not None:
                xBC = jnp.where(keep, xBC, 0.0)
            x = xBC[..., :m.d_inner].reshape(B, T, H, P)
            Bm = xBC[..., m.d_inner:m.d_inner + G * N].reshape(B, T, G, N)
            Cm = xBC[..., m.d_inner + G * N:].reshape(B, T, G, N)

        with jax.named_scope(SCOPE_SSM_SCAN):
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
            A = -jnp.exp(A_log.astype(f32))
            if decode and T == 1 and step_kernel():
                # one token: each lane's state read once and written once
                # where it lies in the (stacked) leaf; no slice is made
                y, cache[SSM_STATE].value = ssd.ssd_step_stacked(
                    cache[SSM_STATE].value, cache_layer, x[:, 0], dt[:, 0],
                    A, Bm[:, 0], Cm[:, 0], D)
            else:
                state = leaf(SSM_STATE, (B, H, P, N), f32).astype(f32)
                if decode and T == 1:
                    y, state = ssd.ssd_step(state, x[:, 0], dt[:, 0], A,
                                            Bm[:, 0], Cm[:, 0], D)
                else:
                    y, state = ssd.ssd_chunked_scan(state, x, dt, A, Bm, Cm,
                                                    D, m.chunk)
                put(SSM_STATE, state)
            y = y.reshape(B, T, m.d_inner)                   # float32

        with jax.named_scope(SCOPE_SSM_GATE_NORM):
            # the gate, then the RMS norm inside each group of channels
            norm_w = self.param("norm_scale", nn.initializers.ones,
                                (m.d_inner,), cfg.param_dtype)
            y = (y * nn.silu(z.astype(f32))).reshape(B, T, G, m.d_inner // G)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + cfg.layer_norm_epsilon)
            y = (y.reshape(B, T, m.d_inner)
                 * norm_w.astype(f32)).astype(cfg.dtype)

        with jax.named_scope(SCOPE_SSM_OUT_PROJ):
            out = nn.Dense(C, use_bias=False, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name="out_proj")(y)
            return scaled(out, m.out_multiplier)
