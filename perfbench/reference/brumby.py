"""Brumby's forward pass, plainly, over the parameter tree the program's
``GPT`` holds: ``wte``, ``lm_head``, ``ln_f`` and ``h/block`` with a
leading layer axis (``attn/c_attn`` holds q, k and v side by side;
``attn/q_norm`` / ``attn/k_norm`` one ``head_dim``-wide weight each;
``attn/gate`` a kernel and a bias of one value per KV head; ``mlp/c_fc`` is
the up projection).

The block is Qwen3's (``modeling_qwen3.py`` of ``transformers``: the
catalog's ``config`` is a Qwen3-14B config under ``model_type: brumby``)
with the softmax core replaced by power retention (Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239; the ``retention``
kernels' ``power_retention(Q, K, V, log_G, deg)``): gated linear attention
whose kernel is ``(q . k)^2``. No modeling file for ``brumby`` was at hand:
every point the published ``config`` has no key for is marked ASSUMED
below and listed in the configuration file.

One unpadded sequence at a time, float32 throughout, every matmul at
precision ``highest``. No cache, no chunks, no batching: the recurrence is
a loop over single tokens (``lax.fori_loop``) with ``phi``, the symmetric
square, explicit. Per KV head, ``S`` ``[D, d]`` and ``z`` ``[D]``, ``D = d (d + 1) /
2``:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t) . z_t + eps)

``phi(u)`` lists ``u_a u_b`` for ``a <= b`` in lexicographic order,
off-diagonal entries times ``sqrt 2`` (``phi(q) . phi(k) = (q . k)^2``). The
program stores another order (a permutation: ``pairs`` says which entry is
which pair). A Python loop over the layers casts ONE layer's weights to
float32 at a time; the head runs in vocabulary blocks and only at the
positions asked for.

The core is an argument of ``block`` (``retention_core`` unless told
otherwise), so that a test can put softmax in its place and hold
everything around it to the published Qwen3 code.

Departures from published code, each a matter of arithmetic and not of the
equations:

* the published kernels compute a pass over many tokens in chunks and
  answer short contexts from keys and values, switching to the state past
  a length (a reordering of the same sums);
* nothing is rounded to the model's dtype between the projections and the
  output projection;
* a sequence may be padded on the RIGHT to a fixed shape; ``length`` then
  says where the state stops taking tokens in.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.falcon_h1 import (  # the generic pieces  # noqa: F401
    HIGHEST,
    _head_block,
    mm,
    rms_norm,
    rotary,
)


def sizes(config):
    """What the equations need, from the published keys of a configuration
    file (``config.json``'s names) and its ``assumed`` retention block."""
    c = config
    r = c["retention"]
    if int(r["degree"]) != 2:
        raise ValueError("the reference is written for degree 2")
    if c["attention_bias"] or c["use_sliding_window"] or c["rope_scaling"]:
        raise ValueError("one form: no bias on q, k, v, o; no window; "
                         "plain rotary")
    return {
        "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
        "n_head": int(c["num_attention_heads"]),
        "n_kv_head": int(c["num_key_value_heads"]),
        "head_dim": int(c["head_dim"]),
        "ret_eps": float(r["eps"]),
        # the shared ``position_stats`` (falcon_h1.py) scales the head by
        # it; Qwen3 has none
        "lm_head_multiplier": 1.0,
    }


def pairs(d):
    """``(a, b)`` ``[D]``: the pairs ``a <= b`` in lexicographic order."""
    a, b = np.triu_indices(d)
    return a, b


def phi(u):
    """The symmetric square of ``u [..., d]``: ``[..., d (d + 1) / 2]``."""
    a, b = pairs(u.shape[-1])
    coef = jnp.where(a == b, 1.0, jnp.sqrt(2.0)).astype(jnp.float32)
    return u[..., a] * u[..., b] * coef


def retention_core(q, k, v, log_g, s, length):
    """``q [T, H, d]``, ``k`` / ``v`` ``[T, Hkv, d]`` (after norm and
    rotary), ``log_g [T, Hkv]``. Returns ``(y [T, H, d], S [Hkv, D, d],
    z [Hkv, D])``, the state after token ``length - 1``.

    ASSUMED: degree 2 (the release's statement); the gate applied to the
    state, one value per KV head; the normaliser the gated sum of keys
    with ``eps`` added; query head ``i`` reads KV head ``i // (H // Hkv)``
    (Qwen3's grouping); no scale on ``q . k`` (it cancels between numerator
    and denominator)."""
    t, h, d = q.shape
    hkv = k.shape[1]
    D = d * (d + 1) // 2

    def token(i, carry):
        S, z, y = carry
        g = jnp.exp(log_g[i])                                # [Hkv]
        pk = phi(k[i])                                       # [Hkv, D]
        S = g[:, None, None] * S + pk[:, :, None] * v[i][:, None, :]
        z = g[:, None] * z + pk
        pq = phi(q[i]).reshape(hkv, h // hkv, D)
        num = jnp.einsum("hgs,hsd->hgd", pq, S, precision=HIGHEST)
        den = jnp.einsum("hgs,hs->hg", pq, z, precision=HIGHEST)
        y_i = (num / (den[..., None] + s["ret_eps"])).reshape(h, d)
        return S, z, jax.lax.dynamic_update_index_in_dim(y, y_i, i, 0)

    # one token at a time, the real ones only: a row past ``length`` (the
    # right padding) gets y = 0 and is read by nobody
    S, z, y = jax.lax.fori_loop(
        0, length, token, (jnp.zeros((hkv, D, d), jnp.float32),
                           jnp.zeros((hkv, D), jnp.float32),
                           jnp.zeros((t, h, d), jnp.float32)))
    return y, S, z


def softmax_core(q, k, v, log_g, s, length):
    """Qwen3's own core, for a test that holds everything around the
    retention to the published code: causal softmax attention, no gate, no
    state."""
    t, h, d = q.shape
    k, v = (jnp.repeat(a, h // k.shape[1], axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST), \
        jnp.zeros((0,)), jnp.zeros((0,))


def mixer(x, p, s, length, core=retention_core):
    """``Qwen3Attention.forward`` around ``core``: projections without
    bias, ``q_norm`` / ``k_norm`` inside each head BEFORE rotary
    (ASSUMED kept: Qwen3 has them without a config key), rotate-half
    rotary over the whole head at ``rope_theta`` (ASSUMED kept: the config
    keeps the key), then the core, then ``o_proj``.

    ASSUMED: ``log g = logsigmoid(x Wg + b_g)`` from the block's normalised
    input ``x``, one value per KV head, with a bias."""
    t = x.shape[0]
    h, hkv, d = s["n_head"], s["n_kv_head"], s["head_dim"]
    qkv = mm(x, p["c_attn"]["kernel"])
    q = qkv[:, :h * d].reshape(t, h, d)
    k = qkv[:, h * d:(h + hkv) * d].reshape(t, hkv, d)
    v = qkv[:, (h + hkv) * d:].reshape(t, hkv, d)
    q = rotary(rms_norm(q, p["q_norm"]["scale"], s["eps"]), s["theta"])
    k = rotary(rms_norm(k, p["k_norm"]["scale"], s["eps"]), s["theta"])
    log_g = jax.nn.log_sigmoid(
        mm(x, p["gate"]["kernel"]) + p["gate"]["bias"])      # [T, Hkv]
    y, S, z = core(q, k, v, log_g, s, length)
    return mm(y.reshape(t, h * d), p["c_proj"]["kernel"]), S, z


def mlp(x, p):
    up = mm(x, p["c_fc"]["kernel"])
    gate = mm(x, p["c_gate"]["kernel"])
    return mm(up * jax.nn.silu(gate), p["c_proj"]["kernel"])


def block(h, p, s, length, core=retention_core):
    """``Qwen3DecoderLayer.forward``: two pre-norm residual branches."""
    mixed, S, z = mixer(rms_norm(h, p["ln_1"]["scale"], s["eps"]),
                        p["attn"], s, length, core)
    h = h + mixed
    return h + mlp(rms_norm(h, p["ln_2"]["scale"], s["eps"]), p["mlp"]), S, z


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_sizes, core):
    s = dict(frozen_sizes)

    @jax.jit
    def layer(h, stacked, i, length):
        p = jax.tree.map(lambda a: a[i].astype(jnp.float32), stacked)
        return block(h, p, s, length, core)

    return layer


def hidden_and_states(params, ids, s, length=None, core=retention_core):
    """``([T, C] float32 hidden states after the final norm, S [layers,
    Hkv, D, d], z [layers, Hkv, D])`` of one sequence ``ids [T]``, unpadded
    or padded on the right (``length`` defaults to T): the states after
    token ``length - 1``."""
    ids = jnp.asarray(ids, jnp.int32)
    h = params["wte"]["embedding"][ids].astype(jnp.float32)
    layer = _layer_fn(tuple(sorted(s.items())), core)
    stacked = params["h"]["block"]
    n_layer = jax.tree.leaves(stacked)[0].shape[0]
    length = jnp.int32(ids.shape[0] if length is None else length)
    states, norms = [], []
    for i in range(n_layer):
        h, S, z = layer(h, stacked, i, length)
        states.append(S)
        norms.append(z)
    return rms_norm(h, params["ln_f"]["scale"].astype(jnp.float32),
                    s["eps"]), jnp.stack(states), jnp.stack(norms)


def logits(params, ids, s, positions=None, vocab_block=32768,
           core=retention_core):
    """[len(positions), vocab] float32 logits (numpy) at ``positions`` (all
    of them when None), the untied head applied in vocabulary blocks."""
    rows = hidden_and_states(params, ids, s, core=core)[0]
    if positions is not None:
        rows = rows[jnp.asarray(positions, jnp.int32)]
    head = params["lm_head"]
    vocab = head.shape[1]
    out = np.empty((rows.shape[0], vocab), np.float32)
    for start in range(0, vocab, vocab_block):
        width = min(vocab_block, vocab - start)
        out[:, start:start + width] = np.asarray(
            _head_block(rows, head, width, start))
    return out
