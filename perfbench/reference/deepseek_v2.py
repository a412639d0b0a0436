"""DeepSeek-V2's forward pass, plainly, over the parameter tree the
program's ``GPT`` holds: ``wte``, ``lm_head``, ``ln_f``, the leading dense
block ``h/dense_0`` and the expert blocks ``h/block`` with a leading layer
axis. In a block: ``ln_1``, ``ln_2``; ``attn/q_a`` (``W_qa``), ``attn/
q_a_norm``, ``attn/q_b`` (per head ``[q_nope | q_rope]``), ``attn/kv_a``
(``[c | r]``), ``attn/kv_a_norm``, ``attn/kv_b`` (per head ``[k_nope |
v]``), ``attn/c_proj`` (``W_o``); a dense block's ``mlp/c_fc`` (up),
``c_gate``, ``c_proj`` (down); an expert block's ``mlp/gate`` (``W_g``),
``mlp/experts/wi`` (up), ``wg`` (gate), ``wo`` (down) of the experts the
layer HOLDS, and ``mlp/shared`` (the shared experts as one SwiGLU of their
summed width).

The equations are those of the published ``modeling_deepseek.py``
(``DeepseekV2Attention``, ``MoEGate`` with ``group_limited_greedy``,
``DeepseekV2MoE``, ``DeepseekV2YarnRotaryEmbedding``) and of
arXiv:2405.04434, section 2. One unpadded sequence at a time (or one
padded on the RIGHT: causal attention never reads the padding), float32
throughout, every matmul at precision ``highest``. The NON-absorbed form
only: every position's ``k_nope`` and ``v`` are decompressed per head and
attended per head. No cache, no kernels, no batching. A Python loop over
the layers casts ONE layer's weights to float32 at a time, attention runs
in blocks of query positions and the experts one after another over every
token (an expert's output is multiplied by the token's weight for it, zero
where the token did not choose it), so that the longest request at the
published widths fits beside the served system; the head runs in
vocabulary blocks and only at the positions asked for.

Departures from the published code, each noted where it is made:

* ``held = (first, count)``: the expert layer computes the routed part of
  the experts ``first .. first + count - 1`` alone and leaves out what the
  others would add, exactly as one chip of an expert-parallel deployment
  does before the exchange that sums the shares (the router, the groups
  and the choice are over ALL ``n_routed`` experts either way). With
  ``held = (0, n_routed)`` it is the published layer;
* the published code re-orders each rotary vector from interleaved pairs
  to halves before ``rotate_half``; under seeded random weights that is a
  fixed permutation of ``W_qb``'s and ``W_kva``'s rotary columns, so the
  rotary columns are taken as stored, halves first (the configuration's
  ``assumed.rotary_order``);
* nothing is rounded to the model's dtype anywhere; the softmax, which the
  published code also takes in float32, stays float32 into the product
  with ``v``;
* ``seq_aux`` and the balance losses are training's and are not computed.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.falcon_h1 import (  # the generic pieces  # noqa: F401
    HIGHEST,
    _head_block,
    mm,
    position_stats,
    rms_norm,
)


def yarn_mscale(factor, mscale):
    """``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """``DeepseekV2YarnRotaryEmbedding``'s blended inverse frequencies
    ``[dim / 2]`` (numpy float64): a dimension whose wavelength makes more
    than ``beta_fast`` turns in the original context keeps its frequency,
    one that makes fewer than ``beta_slow`` has it divided by ``factor``,
    a linear ramp over the dimensions between."""
    def correction_dim(turns):
        return dim * math.log(
            scaling["original_max_position_embeddings"]
            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return extra / scaling["factor"] * ramp + extra * (1.0 - ramp)


def sizes(config):
    """What the equations need, from the published keys of a configuration
    file (``config.json``'s names) and its ``moe`` block (which experts
    this share holds, of how many)."""
    c = config
    sc = c["rope_scaling"]
    if (c["attention_bias"] or sc["type"] != "yarn"
            or c["topk_method"] != "group_limited_greedy"
            or c["scoring_func"] != "softmax" or c["norm_topk_prob"]
            or c["moe_layer_freq"] != 1 or c["hidden_act"] != "silu"
            or c["tie_word_embeddings"]):
        raise ValueError(
            "one form: no bias, YaRN, softmax scores, group-limited greedy "
            "choice, weights not renormalised, experts in every layer "
            "after the leading dense ones, SiLU, an untied head")
    qk = int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"])
    m = yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return {
        "eps": float(c["rms_norm_eps"]),
        "n_head": int(c["num_attention_heads"]),
        "nope": int(c["qk_nope_head_dim"]),
        "rope": int(c["qk_rope_head_dim"]),
        "v_dim": int(c["v_head_dim"]),
        "kv_rank": int(c["kv_lora_rank"]),
        "scale": qk ** -0.5 * m * m,
        "inv_freq": tuple(yarn_inv_freq(
            int(c["qk_rope_head_dim"]), float(c["rope_theta"]), sc)),
        # the multiplier on cos and sin (1.0 when the two mscales agree)
        "rope_mscale": yarn_mscale(sc["factor"], sc["mscale"])
        / yarn_mscale(sc["factor"], sc["mscale_all_dim"]),
        "first_k_dense": int(c["first_k_dense_replace"]),
        "n_routed": int(c["moe"]["routed_over"]),
        "held": tuple(int(x) for x in c["moe"]["experts_held"]),
        "n_group": int(c["n_group"]), "topk_group": int(c["topk_group"]),
        "top_k": int(c["num_experts_per_tok"]),
        "routed_scale": float(c["routed_scaling_factor"]),
        # the shared ``position_stats`` (falcon_h1.py) scales the head by it
        "lm_head_multiplier": 1.0,
    }


def rotary(x, s, offset=0):
    """Rotate-half rotary over the last axis of ``x [T, ..., rope]`` with
    the YaRN frequencies; row t is position ``offset + t``."""
    t = x.shape[0]
    pos = (jnp.arange(t) + offset).astype(jnp.float32)
    angles = pos[:, None] * jnp.asarray(s["inv_freq"], jnp.float32)
    angles = angles.reshape((t,) + (1,) * (x.ndim - 2) + (-1,))
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1) * s["rope_mscale"]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1) * s["rope_mscale"]
    d = x.shape[-1]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def latents(x, p, s, offset=0):
    """``(c_kv [T, kv_rank], k_rope [T, rope])`` of a block's normalised
    input: the compressed latent after its norm and the decoupled rotary
    key after rotary, one of each a token. They are all the program's
    cache keeps of a token."""
    ckr = mm(x, p["kv_a"]["kernel"])
    c_kv = rms_norm(ckr[:, :s["kv_rank"]], p["kv_a_norm"]["scale"], s["eps"])
    return c_kv, rotary(ckr[:, s["kv_rank"]:], s, offset)


def attention(x, p, s, offset=0, query_block=256):
    """``DeepseekV2Attention.forward``, non-absorbed. Returns ``(y, c_kv,
    k_rope)``. ``offset`` is the first row's position: the scores see
    differences of positions alone, so it moves no output, only the
    rotation the rotary keys come out with (a served lane's rows begin
    after its bucket's padding)."""
    t = x.shape[0]
    h, dn, dr, dv = s["n_head"], s["nope"], s["rope"], s["v_dim"]
    c_q = rms_norm(mm(x, p["q_a"]["kernel"]), p["q_a_norm"]["scale"],
                   s["eps"])
    q = mm(c_q, p["q_b"]["kernel"]).reshape(t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], rotary(q[..., dn:], s, offset)
    c_kv, k_rope = latents(x, p, s, offset)
    kv = mm(c_kv, p["kv_b"]["kernel"]).reshape(t, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    rows = []
    for start in range(0, t, query_block):
        stop = min(start + query_block, t)
        scores = (jnp.einsum("qhd,khd->hqk", q_nope[start:stop], k_nope,
                             precision=HIGHEST)
                  + jnp.einsum("qhd,kd->hqk", q_rope[start:stop], k_rope,
                               precision=HIGHEST)) * s["scale"]
        causal = (jnp.arange(t)[None, :]
                  <= jnp.arange(start, stop)[:, None])
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        rows.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST))
    y = jnp.concatenate(rows).reshape(t, h * dv)
    return mm(y, p["c_proj"]["kernel"]), c_kv, k_rope


def swiglu(x, up, gate, down):
    return mm(mm(x, up) * jax.nn.silu(mm(x, gate)), down)


def dense_mlp(x, p):
    return swiglu(x, p["c_fc"]["kernel"], p["c_gate"]["kernel"],
                  p["c_proj"]["kernel"])


def route(x, gate_kernel, s):
    """``MoEGate.forward`` with ``group_limited_greedy``: ``[T, n_routed]``
    float32, the weight ``routed_scale * s_e`` of each expert a token
    chose and 0 elsewhere. The groups are consecutive runs of ``n_routed /
    n_group`` experts; a group's score is its largest probability; the
    experts outside the ``topk_group`` best groups are out; of the rest
    the ``top_k`` largest are chosen, ties to the lower index (``top_k``'s
    rule in both libraries)."""
    scores = jax.nn.softmax(mm(x, gate_kernel.astype(jnp.float32)), -1)
    t, e = scores.shape
    groups = scores.reshape(t, s["n_group"], -1).max(-1)
    _, best = jax.lax.top_k(groups, s["topk_group"])
    keep = jnp.zeros((t, s["n_group"]), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    limited = jnp.where(jnp.repeat(keep, e // s["n_group"], axis=1),
                        scores, 0.0)
    weights, chosen = jax.lax.top_k(limited, s["top_k"])
    return jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], chosen].set(weights * s["routed_scale"])


def moe(x, p, s):
    """``DeepseekV2MoE.forward`` at inference (no token dropped), the
    routed part summed over the experts HELD alone, then the shared
    experts on every token."""
    first, count = s["held"]
    weights = route(x, p["gate"]["kernel"], s)[:, first:first + count]
    ex = p["experts"]

    def one(y, e):
        out = swiglu(x, ex["wi"][e], ex["wg"][e], ex["wo"][e])
        return y + weights[:, e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    sh = p["shared"]
    return y + swiglu(x, sh["c_fc"]["kernel"], sh["c_gate"]["kernel"],
                      sh["c_proj"]["kernel"])


def block(h, p, s, offset=0):
    """``DeepseekV2DecoderLayer.forward``: two pre-norm residual branches;
    the MLP is dense or an expert layer by what the block holds."""
    mixed, c_kv, k_rope = attention(
        rms_norm(h, p["ln_1"]["scale"], s["eps"]), p["attn"], s, offset)
    h = h + mixed
    u = rms_norm(h, p["ln_2"]["scale"], s["eps"])
    ff = moe(u, p["mlp"], s) if "experts" in p["mlp"] else \
        dense_mlp(u, p["mlp"])
    return h + ff, c_kv, k_rope


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_sizes):
    s = dict(frozen_sizes)

    @jax.jit
    def layer(h, stacked, i, offset):
        p = jax.tree.map(lambda a: a[i].astype(jnp.float32), stacked)
        return block(h, p, s, offset)

    return layer


def layers_of(params):
    """``[(stacked tree, index)]`` of the model's blocks in order: the
    leading dense blocks (each a tree of its own, given a layer axis of
    one), then the scanned expert blocks."""
    h = params["h"]
    out = [(jax.tree.map(lambda a: a[None], h[name]), 0)
           for name in sorted(k for k in h if k.startswith("dense_"))]
    if "block" in h:
        n = jax.tree.leaves(h["block"])[0].shape[0]
        out += [(h["block"], i) for i in range(n)]
    return out


def hidden_and_states(params, ids, s, length=None, offset=0):
    """``([T, C] float32 hidden states after the final norm, c_kv [layers,
    T, kv_rank], k_rope [layers, T, rope])`` of one sequence ``ids [T]``,
    unpadded or padded on the right (rows from ``length`` on are then
    nobody's), its first token at position ``offset``."""
    del length      # causal: a real row never reads the padding
    offset = jnp.int32(offset)
    ids = jnp.asarray(ids, jnp.int32)
    h = params["wte"]["embedding"][ids].astype(jnp.float32)
    layer = _layer_fn(tuple(sorted(s.items())))
    lat, rope = [], []
    for stacked, i in layers_of(params):
        h, c_kv, k_rope = layer(h, stacked, i, offset)
        lat.append(c_kv)
        rope.append(k_rope)
    return rms_norm(h, params["ln_f"]["scale"].astype(jnp.float32),
                    s["eps"]), jnp.stack(lat), jnp.stack(rope)


def hidden(params, ids, s):
    return hidden_and_states(params, ids, s)[0]


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
    return out
