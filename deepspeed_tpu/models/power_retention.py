"""The power-retention mixer of a block (``GPTConfig.retention``): the token
mixer IN PLACE OF attention, not beside it.

The block is Qwen3's with the softmax core replaced (``ops/
power_retention.py`` holds the equations): q, k, v projections without
bias, an RMS norm inside each head of q and of k (one ``head_dim``-wide
weight each), rotary over the whole head, a gate ``log g = logsigmoid(x Wg
+ b_g)`` of one value per KV head from the block's normalised input, the
retention itself, the output projection.

Three entry shapes, the same equations:

* a pass that starts a sequence (training forward, ``prefill``): zero
  ``S`` and ``z``, the chunked pass;
* a pass that continues one (``prefill_more``): the same from the state in
  the ``cache`` collection;
* one token (a decode step): the recurrence itself.

**The state is the cache.** On the decode path ``S`` ``[B, Hkv, d, D]``
and ``z`` ``[B, Hkv, D]`` (float32 unless ``RetentionConfig.state_dtype``
says otherwise; ``D = (d / 2 + 1) d`` stored entries of which ``d (d + 1) /
2`` count: ops/power_retention.py) are the leaves ``ret_state`` /
``ret_norm`` of the ``cache`` collection and ``clock`` ``[B]`` counts the
tokens each lane has taken in. There is no key, value or ``valid`` leaf:
nothing is stored per position. Under ``ScannedBlocks`` the leaves are the
stacked ``[n_layer, ...]`` buffers the layer loop carries and this call is
layer ``cache_layer`` of them: it reads its slice and writes it back in
place. Like any recurrent state they cannot be cut at a prefix.

LEFT-padded prompts: a pad's ``k`` and ``v`` are zeroed and its ``log g``
is 0, so it neither decays nor feeds the state, and rotary's positions
count real tokens only (``clock`` + the tokens before this one in the
pass): the state after a prompt in a bucket IS the state after the prompt
alone, rotation for rotation, and through leading pads ``S`` and ``z``
stay exactly zero.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import power_retention as pr
from deepspeed_tpu.telemetry.scopes import (
    SCOPE_RET_OUT_PROJ,
    SCOPE_RET_PROJ,
    SCOPE_RET_QK_NORM_ROPE,
    SCOPE_RET_STATE,
)

# names of the mixer's leaves in the ``cache`` collection
RET_STATE = "ret_state"
RET_NORM = "ret_norm"
RET_CLOCK = "clock"
# standard deviation of the gate projection's initial kernel: small, so
# that a fresh model's half-lives are the bias's
_GATE_KERNEL_STD = 0.002


def _gate_bias_init(key, shape, dtype, shortest=8.0, longest=4096.0):
    """Half-lives log-uniform in ``[shortest, longest]`` tokens per KV
    head: ``b = logit(2^(-1 / halflife))``. A zero-centred gate halves the
    state every token, and nothing could then tell how a state is
    stored."""
    half = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                   * (jnp.log(longest) - jnp.log(shortest))
                   + jnp.log(shortest))
    log_g = -jnp.log(2.0) / half
    # logit(g) = log g - log(1 - g)
    return (log_g - jnp.log(-jnp.expm1(log_g))).astype(dtype)


class PowerRetention(nn.Module):
    config: "GPTConfig"  # noqa: F821  (models/transformer_lm.py)

    @nn.compact
    def __call__(self, x, *, mask=None, decode=False, cache_layer=None):
        from deepspeed_tpu.models.transformer_lm import step_kernel
        from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb

        cfg = self.config
        r = cfg.retention
        B, T, C = x.shape
        H, Hkv, d = cfg.n_head, cfg.kv_heads, cfg.head_dim
        D = pr.sympow2_width(d)
        f32 = jnp.float32
        keep = None if mask is None else mask.astype(jnp.bool_)

        def dense(width, name, bias=False, **kw):
            return nn.Dense(width, use_bias=bias, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name=name, **kw)

        with jax.named_scope(SCOPE_RET_PROJ):
            qkv = dense((H + 2 * Hkv) * d, "c_attn")(x)
            q = qkv[..., :H * d].reshape(B, T, H, d)
            k = qkv[..., H * d:(H + Hkv) * d].reshape(B, T, Hkv, d)
            v = qkv[..., (H + Hkv) * d:].reshape(B, T, Hkv, d)
            log_g = jax.nn.log_sigmoid(dense(
                Hkv, "gate", bias=True,
                kernel_init=nn.initializers.normal(_GATE_KERNEL_STD),
                bias_init=_gate_bias_init)(x).astype(f32))   # [B, T, Hkv]

        # ---- the cache: this layer's state, normaliser and clock ---------
        cache = None
        # a state that this very call creates is zero
        fresh = not (decode and self.has_variable("cache", RET_STATE))
        if decode:
            cache = {
                RET_STATE: self.variable("cache", RET_STATE, jnp.zeros,
                                         (B, Hkv, d, D), r.state_dtype),
                RET_NORM: self.variable("cache", RET_NORM, jnp.zeros,
                                        (B, Hkv, D), r.state_dtype),
                RET_CLOCK: self.variable("cache", RET_CLOCK, jnp.zeros,
                                         (B,), jnp.int32)}

        def leaf(name, shape, dtype):
            if cache is None:
                return jnp.zeros(shape, dtype)
            v_ = cache[name].value
            return v_ if cache_layer is None else \
                jax.lax.dynamic_index_in_dim(v_, cache_layer, 0,
                                             keepdims=False)

        def put(name, val):
            if cache is None:
                return
            var = cache[name]
            val = val.astype(var.value.dtype)
            var.value = val if cache_layer is None else \
                jax.lax.dynamic_update_index_in_dim(
                    var.value, val, cache_layer, 0)

        with jax.named_scope(SCOPE_RET_QK_NORM_ROPE):
            # float32 from here on: what reaches the state is never
            # rounded to the compute dtype again
            def head_norm(t, name):
                return nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=f32,
                                  param_dtype=cfg.param_dtype, name=name)(
                                      t.astype(f32))

            clock = leaf(RET_CLOCK, (B,), jnp.int32)
            if keep is None:
                taken = jnp.full((B,), T, jnp.int32)
                pos = clock[:, None] + jnp.arange(T)[None, :]
            else:
                seen = jnp.cumsum(keep.astype(jnp.int32), axis=1)
                taken = seen[:, -1]
                pos = clock[:, None] + jnp.clip(seen - 1, 0)
            put(RET_CLOCK, clock + taken)

            def rope(t):
                return apply_rotary_pos_emb(
                    t, pos, base=cfg.rope_theta, rotary_dim=cfg.rotary_dim,
                    interleaved=cfg.rotary_interleaved)

            q = rope(head_norm(q, "q_norm"))
            k = rope(head_norm(k, "k_norm"))
            v = v.astype(f32)
            if keep is not None:
                k = jnp.where(keep[..., None, None], k, 0.0)
                v = jnp.where(keep[..., None, None], v, 0.0)
                log_g = jnp.where(keep[..., None], log_g, 0.0)

        with jax.named_scope(SCOPE_RET_STATE):
            if decode and T == 1 and step_kernel():
                # one token: each lane's state read once and written once
                # where it lies in the (stacked) leaf; no slice is made
                y, cache[RET_STATE].value, cache[RET_NORM].value = \
                    pr.retention_step_stacked(
                        cache[RET_STATE].value, cache[RET_NORM].value,
                        cache_layer, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
                        r.eps)
            else:
                S = leaf(RET_STATE, (B, Hkv, d, D), f32)
                z = leaf(RET_NORM, (B, Hkv, D), f32)
                if decode and T == 1:
                    y, S, z = pr.retention_step(
                        S, z, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], r.eps)
                else:
                    y, S, z = pr.retention_chunked(
                        S, z, q, k, v, log_g, r.eps, r.chunk, fresh=fresh)
                put(RET_STATE, S)
                put(RET_NORM, z)
            y = y.reshape(B, T, H * d).astype(cfg.dtype)

        with jax.named_scope(SCOPE_RET_OUT_PROJ):
            return dense(C, "c_proj")(y)
