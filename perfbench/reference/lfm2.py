"""LFM2-MoE's forward pass, plainly (the ``lfm2_moe`` modelling code of
``transformers``: ``Lfm2MoeShortConv``, ``Lfm2MoeAttention``,
``Lfm2MoeSparseMoeBlock``, ``Lfm2MoeDecoderLayer``), over the parameter
tree the program's ``GPT`` holds for a model that declares its layers'
kinds: ``wte`` (the head is its transpose: tied), ``ln_f`` (the family's
``embedding_norm``) and, under ``h``, one stack a kind of block with a
leading axis over that kind's layers in order: ``conv_dense`` (the leading
``num_dense_layers`` layers, here all convolutions), ``conv`` and
``attention``. In a block: ``ln_1`` (``operator_norm``), ``ln_2``
(``ffn_norm``); a convolution's ``conv/in_proj`` (``[B | C | z]``),
``conv/conv_kernel`` ``[taps, C]`` with the current token last,
``conv/out_proj``; attention's ``attn/c_attn`` (q, k and v side by side),
``attn/q_norm``, ``attn/k_norm`` (one weight a head dimension),
``attn/c_proj``; a dense layer's ``mlp/c_gate`` (``w1``), ``c_fc``
(``w3``), ``c_proj`` (``w2``); an expert layer's ``mlp/gate`` (``W_g``),
``mlp/expert_bias`` and ``mlp/experts/wg`` (``w1``), ``wi`` (``w3``),
``wo`` (``w2``).

The equations, ``x`` the residual stream, no projection with a bias:

* layer: ``h = x + mixer(rms(x))``; ``x' = h + ffn(rms(h))``;
* gated short convolution on ``u [T, C]``: ``[B | C | z] = u W_in``; ``g =
  B * z``; ``c_t = sum_k w[k] g_{t - (K - 1) + k}`` (depthwise, causal,
  zeros before the start, no bias, no activation); ``y = (C * c) W_out``;
* attention: per-head RMS norm of q and of k, rotary over the whole head
  (halves, not interleaved pairs), causal softmax of ``q k^T / sqrt(d)``,
  each KV head serving ``heads / kv_heads`` query heads;
* expert layer: ``s = sigmoid(h W_g)``; the chosen ``k`` are the largest
  of ``s + b``, ties to the lower index; weights ``s_i / (sum of the
  chosen s + 1e-6)`` times ``routed_scaling_factor``; the bias ``b`` enters
  the choice and nothing else.

One unpadded sequence at a time (or one padded on the RIGHT: a causal
model's earlier rows never read the padding; ``length`` then says where
the convolutions' tails are taken), float32 throughout, every matmul at
precision ``highest``. No cache, no kernels, no batching. A Python loop
over the layers casts ONE layer's weights to float32 at a time, attention
runs in blocks of query positions and the experts one after another over
every token (an expert's output times the token's weight for it, zero
where the token did not choose it), so that the longest request at the
published widths fits beside the served system; the head runs in
vocabulary blocks and only at the positions asked for.

Departures from the published code, each a matter of form:

* the published cache keeps ``conv_L_cache`` columns of ``g`` of which the
  oldest is never read again; what is handed back here is the last
  ``conv_L_cache - 1``, all a continuation reads;
* nothing is rounded to the model's dtype anywhere;
* ``offset`` is the first row's rotary position (a served lane's rows
  begin after its bucket's padding): the scores see differences of
  positions alone, so it moves no output, only the rotation the keys come
  out with.
"""
import functools

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

CONV, ATTENTION = "conv", "full_attention"


def sizes(config):
    """What the equations need, from the published keys of a configuration
    file (``config.json``'s names)."""
    c = config
    kinds = tuple(c["layer_types"])
    if (c["conv_bias"] or not c["norm_topk_prob"] or not c["use_expert_bias"]
            or not c["tie_word_embeddings"]
            or set(kinds) - {CONV, ATTENTION}
            or len(kinds) != c["num_hidden_layers"]):
        raise ValueError(
            "one form: convolutions without bias, sigmoid scores corrected "
            "by a bias and renormalised, a tied head, and a kind "
            f"({CONV} | {ATTENTION}) for every layer")
    rope = c.get("rope_parameters") or {}
    return {
        "eps": float(c["norm_eps"]),
        "theta": float(c.get("rope_theta", rope.get("rope_theta"))),
        "n_head": int(c["num_attention_heads"]),
        "n_kv_head": int(c["num_key_value_heads"]),
        "head_dim": int(c["hidden_size"]) // int(c["num_attention_heads"]),
        "taps": int(c["conv_L_cache"]),
        "kinds": kinds, "n_dense": int(c["num_dense_layers"]),
        "top_k": int(c["num_experts_per_tok"]),
        "routed_scale": float(c["routed_scaling_factor"]),
        # the shared ``position_stats`` (falcon_h1.py) scales the head by it
        "lm_head_multiplier": 1.0,
    }


def rotary(x, theta, offset=0):
    """Rotate-half rotary over the last axis of ``x [T, heads, d]``; row t
    is position ``offset + t``."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = (jnp.arange(t) + offset).astype(jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def short_conv(u, p, s, length):
    """``Lfm2MoeShortConv.forward`` over a whole sequence. Returns ``(y,
    the last taps - 1 rows of g before row length)``."""
    t, c = u.shape
    taps = s["taps"]
    bcz = mm(u, p["in_proj"]["kernel"])
    b, gate, z = bcz[:, :c], bcz[:, c:2 * c], bcz[:, 2 * c:]
    g = b * z
    ext = jnp.concatenate([jnp.zeros((taps - 1, c), g.dtype), g])
    conv = sum(ext[k:k + t] * p["conv_kernel"][k] for k in range(taps))
    # row r of ``ext`` is g's row r - (taps - 1)
    tail = jax.lax.dynamic_slice_in_dim(ext, length, taps - 1, axis=0)
    return mm(gate * conv, p["out_proj"]["kernel"]), tail


def attention(u, p, s, offset=0, query_block=512):
    """``Lfm2MoeAttention.forward``. Returns ``(y, k [T, kv_heads, d] after
    its norm and rotary, v [T, kv_heads, d])``."""
    t = u.shape[0]
    h, hkv, d = s["n_head"], s["n_kv_head"], s["head_dim"]
    qkv = mm(u, p["c_attn"]["kernel"])
    q = qkv[:, :h * d].reshape(t, h, d)
    k = qkv[:, h * d:(h + hkv) * d].reshape(t, hkv, d)
    v = qkv[:, (h + hkv) * d:].reshape(t, hkv, d)
    q = rotary(rms_norm(q, p["q_norm"]["scale"], s["eps"]), s["theta"],
               offset)
    k = rotary(rms_norm(k, p["k_norm"]["scale"], s["eps"]), s["theta"],
               offset)
    qg = q.reshape(t, hkv, h // hkv, d)
    rows = []
    for start in range(0, t, query_block):
        stop = min(start + query_block, t)
        scores = jnp.einsum("qhgd,khd->hgqk", qg[start:stop], k,
                            precision=HIGHEST) * d ** -0.5
        causal = jnp.arange(t)[None, :] <= jnp.arange(start, stop)[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        rows.append(jnp.einsum("hgqk,khd->qhgd", probs, v,
                               precision=HIGHEST))
    y = jnp.concatenate(rows).reshape(t, h * d)
    return mm(y, p["c_proj"]["kernel"]), k, v


def swiglu(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def route(x, p, s, biased=True):
    """``Lfm2MoeSparseMoeBlock.route_tokens_to_experts``: ``[T, experts]``
    float32, the weight of each expert a token chose and 0 elsewhere.
    ``biased`` False leaves the bias out of the choice (what the counter
    ``bias_changed_share`` compares with)."""
    scores = jax.nn.sigmoid(mm(x, p["gate"]["kernel"].astype(jnp.float32)))
    t, e = scores.shape
    choose_by = scores + p["expert_bias"].astype(jnp.float32) if biased \
        else scores
    _, chosen = jax.lax.top_k(choose_by, s["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-6) \
        * s["routed_scale"]
    return jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], chosen].set(picked)


def moe(x, p, s):
    """``Lfm2MoeSparseMoeBlock.forward``: every expert over every token,
    weighted; no token dropped, no shared expert."""
    weights = route(x, p, s)
    ex = p["experts"]

    def one(y, e):
        out = swiglu(x, ex["wg"][e], ex["wi"][e], ex["wo"][e])
        return y + weights[:, e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(weights.shape[1]))
    return y


def block(h, p, s, kind, offset, length):
    """``Lfm2MoeDecoderLayer.forward``. Returns ``(h, kept)``: a
    convolution keeps its tail, attention its keys and values."""
    u = rms_norm(h, p["ln_1"]["scale"], s["eps"])
    if kind == CONV:
        mixed, kept = short_conv(u, p["conv"], s, length)
    else:
        mixed, *kept = attention(u, p["attn"], s, offset)
    h = h + mixed
    u = rms_norm(h, p["ln_2"]["scale"], s["eps"])
    if "experts" in p["mlp"]:
        return h + moe(u, p["mlp"], s), kept
    m = p["mlp"]
    return h + swiglu(u, m["c_gate"]["kernel"], m["c_fc"]["kernel"],
                      m["c_proj"]["kernel"]), kept


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_sizes, kind):
    s = dict(frozen_sizes)

    @jax.jit
    def layer(h, stacked, i, offset, length):
        p = jax.tree.map(lambda a: a[i].astype(jnp.float32), stacked)
        return block(h, p, s, kind, offset, length)

    return layer


def layers_of(params, s):
    """``[(kind, stacked tree, index)]`` of the model's blocks in order:
    each layer's place in the stack of its kind of block."""
    out, seen = [], {}
    for layer, kind in enumerate(s["kinds"]):
        name = {CONV: "conv", ATTENTION: "attention"}[kind] \
            + ("_dense" if layer < s["n_dense"] else "")
        out.append((kind, params["h"][name], seen.get(name, 0)))
        seen[name] = seen.get(name, 0) + 1
    return out


def hidden_and_states(params, ids, s, length=None, offset=0):
    """``([T, C] float32 hidden states after the final norm, k and v
    [attention layers, T, kv_heads, d], tails [convolution layers, taps -
    1, C])`` of one sequence ``ids [T]``, unpadded or padded on the right
    (``length`` real rows: the tails are those after them), its first
    token at rotary position ``offset``."""
    ids = jnp.asarray(ids, jnp.int32)
    length = jnp.int32(ids.shape[0] if length is None else length)
    offset = jnp.int32(offset)
    h = params["wte"]["embedding"][ids].astype(jnp.float32)
    frozen = tuple(sorted(s.items()))
    keys, values, tails = [], [], []
    for kind, stacked, i in layers_of(params, s):
        h, kept = _layer_fn(frozen, kind)(h, stacked, i, offset, length)
        if kind == CONV:
            tails.append(kept)
        else:
            keys.append(kept[0])
            values.append(kept[1])
    return (rms_norm(h, params["ln_f"]["scale"].astype(jnp.float32),
                     s["eps"]),
            jnp.stack(keys), jnp.stack(values), jnp.stack(tails))


def hidden(params, ids, s):
    return hidden_and_states(params, ids, s)[0]


def head_of(params):
    """``{"lm_head": [C, vocab]}``: the tied head as the shared
    ``position_stats`` reads one."""
    return {"lm_head": params["wte"]["embedding"].T}


def logits(params, ids, s, positions=None, vocab_block=32768):
    """[len(positions), vocab] float32 logits (numpy) at ``positions`` (all
    of them when None), the tied head applied in vocabulary blocks."""
    rows = hidden(params, ids, s)
    if positions is not None:
        rows = rows[jnp.asarray(positions, jnp.int32)]
    head = head_of(params)["lm_head"]
    vocab = head.shape[1]
    out = np.empty((rows.shape[0], vocab), np.float32)
    for start in range(0, vocab, vocab_block):
        width = min(vocab_block, vocab - start)
        out[:, start:start + width] = np.asarray(
            _head_block(rows, head, width, start))
    return out
