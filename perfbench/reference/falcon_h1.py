"""Falcon-H1's forward pass, plainly (``modeling_falcon_h1.py`` of
``transformers``; Zuo et al. 2025, "Falcon-H1: A Family of Hybrid-Head
Language Models"), over the parameter tree the program's ``GPT`` holds:
``wte``, ``lm_head``, ``ln_f`` and ``h/block`` with a leading layer axis
(``attn/c_attn`` holds q, k and v side by side; ``mamba/in_proj`` projects
to ``[z | x | B | C | dt]``; ``mamba/conv_kernel`` is ``[width, channels]``
with the current token last).

One unpadded sequence at a time, float32 throughout, every matmul at
precision ``highest``. No cache, no chunks, no batching: the state-space
recurrence is a ``lax.scan`` over single tokens,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,   y_t = S_t C_t + D x_t

A Python loop over the layers casts ONE layer's weights to float32 at a
time (a 34B-width layer is 1.72 GB in float32), so the reference fits
beside the served model; the head runs in vocabulary blocks and only at
the positions asked for.

The block, as published: ``u = rms(h)``; ``h += ssm_out_multiplier *
mamba(u) + attention_out_multiplier * attn(attention_in_multiplier * u)``;
``h += mlp(rms(h))``. The twelve multipliers sit where the published code
has them. Departures from the published code, each of them a matter of
arithmetic and not of the equations:

* the published mixer computes the recurrence in chunks of
  ``mamba_chunk_size`` (a reordering of the same sums; the program's
  chunked scan is checked against this token-by-token form);
* the published code rounds the convolution's output and the mixer's
  gated, normalised output to the model's dtype; here nothing is rounded;
* ``time_step_limit`` is ``(0, inf)`` in the published mixer, so its clamp
  of ``dt`` does nothing and is left out;
* one form of the mixer, the published configuration's
  (``mamba_conv_bias`` and ``mamba_rms_norm`` true, ``mamba_proj_bias`` and
  ``mamba_norm_before_gate`` false); ``sizes`` refuses any other;
* pads: the published code zeroes padded positions before the input
  projection and after the convolution; a reference over one unpadded
  sequence has none. A sequence may be padded on the RIGHT to a fixed
  shape (a causal model's earlier positions do not see it); ``length``
  then says where the recurrent state stops taking tokens in, so that the
  state and the convolution's tail it hands back are those after the real
  tokens.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def sizes(config):
    """What the equations need, from the published keys of a configuration
    file (``config.json``'s names)."""
    c = config
    form = {"mamba_conv_bias": True, "mamba_proj_bias": False,
            "mamba_rms_norm": True, "mamba_norm_before_gate": False}
    other = {k: c[k] for k, v in form.items() if c[k] is not v}
    if other:
        raise ValueError(f"the mixer is written in one form, {form}; the "
                         f"configuration says {other}")
    return {
        "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
        "n_head": int(c["num_attention_heads"]),
        "n_kv_head": int(c["num_key_value_heads"]),
        "head_dim": int(c["head_dim"]),
        "ssm_heads": int(c["mamba_n_heads"]),
        "ssm_head_dim": int(c["mamba_d_head"]),
        "ssm_state": int(c["mamba_d_state"]),
        "ssm_groups": int(c["mamba_n_groups"]),
        "d_ssm": int(c["mamba_d_ssm"]), "d_conv": int(c["mamba_d_conv"]),
        "embedding_multiplier": float(c["embedding_multiplier"]),
        "lm_head_multiplier": float(c["lm_head_multiplier"]),
        "attention_in_multiplier": float(c["attention_in_multiplier"]),
        "attention_out_multiplier": float(c["attention_out_multiplier"]),
        "key_multiplier": float(c["key_multiplier"]),
        "mlp_multipliers": tuple(float(v) for v in c["mlp_multipliers"]),
        "ssm_in_multiplier": float(c["ssm_in_multiplier"]),
        "ssm_out_multiplier": float(c["ssm_out_multiplier"]),
        "ssm_multipliers": tuple(float(v) for v in c["ssm_multipliers"]),
    }


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """Rotate-half rotary embedding over the whole head of [T, H, D]."""
    t, d = x.shape[0], x.shape[2]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def attention(x, p, s):
    t = x.shape[0]
    h, hkv, d = s["n_head"], s["n_kv_head"], s["head_dim"]
    qkv = mm(x, p["c_attn"]["kernel"])
    q = qkv[:, :h * d].reshape(t, h, d)
    k = qkv[:, h * d:(h + hkv) * d].reshape(t, hkv, d) * s["key_multiplier"]
    v = qkv[:, (h + hkv) * d:].reshape(t, hkv, d)
    q, k = rotary(q, s["theta"]), rotary(k, s["theta"])
    k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
    return mm(o.reshape(t, h * d), p["c_proj"]["kernel"])


def mup_vector(s):
    gn = s["ssm_groups"] * s["ssm_state"]
    z, x, b, c, dt = s["ssm_multipliers"]
    return jnp.concatenate([
        jnp.full((s["d_ssm"],), z), jnp.full((s["d_ssm"],), x),
        jnp.full((gn,), b), jnp.full((gn,), c),
        jnp.full((s["ssm_heads"],), dt)]).astype(jnp.float32)


def mamba(u, p, s, length):
    """The mixer's output ``[T, C]``, the recurrent state ``[H, P, N]``
    after token ``length - 1`` (tokens from ``length`` on leave it as it
    is: a sequence padded on the right to a fixed shape still gives the
    state after its real tokens) and the convolution's tail then, its
    last ``d_conv - 1`` inputs ``[K - 1, conv channels]``."""
    t = u.shape[0]
    H, P, N, G = (s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"],
                  s["ssm_groups"])
    d, K = s["d_ssm"], s["d_conv"]
    proj = mm(u * s["ssm_in_multiplier"], p["in_proj"]["kernel"]) \
        * mup_vector(s)
    z, xBC, dt = proj[:, :d], proj[:, d:2 * d + 2 * G * N], \
        proj[:, 2 * d + 2 * G * N:]
    # causal depthwise convolution: K - 1 zeros in front, the current
    # token under the last tap
    ext = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1])), xBC], 0)
    tail = jax.lax.dynamic_slice_in_dim(ext, length, K - 1, axis=0)
    conv = sum(ext[k:k + t] * p["conv_kernel"][k] for k in range(K)) \
        + p["conv_bias"]
    xBC = jax.nn.silu(conv)
    x = xBC[:, :d].reshape(t, H, P)
    Bm = jnp.repeat(xBC[:, d:d + G * N].reshape(t, G, N), H // G, axis=1)
    Cm = jnp.repeat(xBC[:, d + G * N:].reshape(t, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # [t, H]
    A = -jnp.exp(p["A_log"])                                 # [H]

    def token(S, c):
        x_t, B_t, C_t, dt_t, i = c
        S = jnp.where(i < length, jnp.exp(dt_t * A)[:, None, None] * S
                      + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :],
                      S)
        y = jnp.einsum("hpn,hn->hp", S, C_t, precision=HIGHEST) \
            + p["D"][:, None] * x_t
        return S, y

    state, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                            (x, Bm, Cm, dt, jnp.arange(t)))
    y = y.reshape(t, d)

    def grouped_rms(a):
        a = a.reshape(t, G, d // G)
        a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + s["eps"])
        return a.reshape(t, d) * p["norm_scale"]

    y = grouped_rms(y * jax.nn.silu(z))
    return mm(y, p["out_proj"]["kernel"]), state, tail


def mlp(x, p, s):
    gate_m, down_m = s["mlp_multipliers"]
    up = mm(x, p["c_fc"]["kernel"])
    gate = mm(x, p["c_gate"]["kernel"]) * gate_m
    return mm(up * jax.nn.silu(gate), p["c_proj"]["kernel"]) * down_m


def block(h, p, s, length):
    u = rms_norm(h, p["ln_1"]["scale"], s["eps"])
    mixed, state, tail = mamba(u, p["mamba"], s, length)
    h = h + s["ssm_out_multiplier"] * mixed \
        + s["attention_out_multiplier"] * attention(
            u * s["attention_in_multiplier"], p["attn"], s)
    return h + mlp(rms_norm(h, p["ln_2"]["scale"], s["eps"]), p["mlp"],
                   s), state, tail


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_sizes):
    s = dict(frozen_sizes)

    @jax.jit
    def layer(h, stacked, i, length):
        p = jax.tree.map(lambda a: a[i].astype(jnp.float32), stacked)
        return block(h, p, s, length)

    return layer


def hidden_and_states(params, ids, s, length=None):
    """``([T, C] float32 hidden states after the final norm, [layers, H, P,
    N] recurrent states after token length - 1, [layers, K - 1, conv
    channels] convolution tails then)`` of one sequence ``ids [T]``,
    unpadded or padded on the right (``length`` defaults to T)."""
    ids = jnp.asarray(ids, jnp.int32)
    h = params["wte"]["embedding"][ids].astype(jnp.float32) \
        * s["embedding_multiplier"]
    layer = _layer_fn(tuple(sorted(s.items())))
    stacked = params["h"]["block"]
    n_layer = jax.tree.leaves(stacked)[0].shape[0]
    length = jnp.int32(ids.shape[0] if length is None else length)
    states, tails = [], []
    for i in range(n_layer):
        h, state, tail = layer(h, stacked, i, length)
        states.append(state)
        tails.append(tail)
    return rms_norm(h, params["ln_f"]["scale"].astype(jnp.float32),
                    s["eps"]), jnp.stack(states), jnp.stack(tails)


def hidden(params, ids, s):
    """The hidden states of ``hidden_and_states`` alone."""
    return hidden_and_states(params, ids, s)[0]


@functools.partial(jax.jit, static_argnums=(2,))
def _head_block(rows, head, width, start):
    w = jax.lax.dynamic_slice_in_dim(head, start, width, axis=1)
    return mm(rows, w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(2,))
def _head_block_stats(rows, head, width, start, tokens, scale):
    """Of one vocabulary block of the rows' logits: the largest, the sum,
    the sum of squares and the logit of each row's token where the block
    holds it (0 elsewhere)."""
    block = _head_block(rows, head, width, start) * scale
    col = tokens - start
    inside = (col >= 0) & (col < width)
    took = jnp.take_along_axis(
        block, jnp.clip(col, 0, width - 1)[:, None], axis=1)[:, 0]
    return (block.max(-1), block.sum(-1), (block * block).sum(-1),
            jnp.where(inside, took, 0.0))


def position_stats(params, ids, s, positions, tokens, vocab_block=32768,
                   pad_to=1, states=None):
    """For each of ``positions`` (numpy, float64): ``margin``, how far the
    logit of its ``tokens`` entry lies below the position's largest logit,
    in units of the standard deviation of the position's logits, ``(max -
    logit[token]) / std``. The head runs in vocabulary blocks and only the
    blocks' statistics leave the device. The positions are padded up to a
    multiple of ``pad_to`` (with the first of them, dropped again), so
    that requests of many lengths share a few compiled shapes. ``states``
    is ``hidden(params, ids, s)`` where the caller has it already."""
    n = len(positions)
    fill = (-n) % pad_to
    positions = list(positions) + [positions[0]] * fill
    tokens = jnp.asarray(list(tokens) + [0] * fill, jnp.int32)
    if states is None:
        states = hidden(params, ids, s)
    rows = states[jnp.asarray(positions, jnp.int32)]
    head = params["lm_head"]
    vocab = head.shape[1]
    parts = [_head_block_stats(rows, head, min(vocab_block, vocab - start),
                               start, tokens, s["lm_head_multiplier"])
             for start in range(0, vocab, vocab_block)]
    cols = [np.stack([np.asarray(p[i], np.float64) for p in parts])[:, :n]
            for i in range(len(parts[0]))]
    top, total, squares, took = cols
    mean = total.sum(0) / vocab
    std = np.sqrt(squares.sum(0) / vocab - mean * mean)
    return {"margin": (top.max(0) - took.sum(0)) / std}


def logits(params, ids, s, positions=None, vocab_block=32768):
    """[len(positions), vocab] float32 logits (numpy) at ``positions`` (all
    of them when None), the untied head applied in vocabulary blocks."""
    rows = hidden(params, ids, s)
    if positions is not None:
        rows = rows[jnp.asarray(positions, jnp.int32)]
    head = params["lm_head"]
    vocab = head.shape[1]
    out = np.empty((rows.shape[0], vocab), np.float32)
    for start in range(0, vocab, vocab_block):
        width = min(vocab_block, vocab - start)
        out[:, start:start + width] = np.asarray(
            _head_block(rows, head, width, start))
    return out * np.float32(s["lm_head_multiplier"])
