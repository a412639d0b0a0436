"""Keye-VL-2.0-30B-A3B's language model, plainly, over the parameter tree
the program's ``GPT`` holds: ``wte``, ``lm_head``, ``ln_f`` and ``h/block``
with a leading layer axis. In a block: ``ln_1``, ``ln_2``; ``attn/c_attn``
(``W_q``, ``W_k``, ``W_v`` side by side), ``attn/q_norm``, ``attn/k_norm``,
``attn/c_proj`` (``W_o``); ``attn/indexer/wq`` (``W_qI``), ``wk``
(``W_kI``), ``k_norm`` (a LayerNorm), ``weights_proj`` (``W_w``);
``mlp/gate`` (``W_g``), ``mlp/experts/wi`` (up), ``wg`` (gate), ``wo``
(down) of the experts the layer HOLDS.

The layer. For a block's input ``x``, ``h = RMSNorm(x)``, a query at
position ``t`` and positions ``s <= t``:

1. ``q = W_q h`` (``n_head`` heads of ``head_dim``), ``k = W_k h``, ``v =
   W_v h`` (``n_kv_head`` heads), no bias; ``q`` and ``k`` RMS-normalised
   per head with a learned weight; rotary on the whole head, halves
   convention, base ``rope_theta``: of the ``head_dim / 2`` frequencies
   the first ``mrope_section[0]`` take their angle from the temporal
   position, the next from the height, the last from the width; for text
   the three are equal.
2. The indexer: ``qI = W_qI h`` (``indexer_num_heads`` heads of
   ``indexer_head_dim``), ``kI = LayerNorm(W_kI h)`` (one head), ``w = W_w
   h * indexer_num_heads^-1/2 * indexer_head_dim^-1/2``; rotary on the
   whole of ``qI`` and ``kI`` with the block's own positions (its ladder
   over ``indexer_head_dim``, the sections in the model's proportions);
   ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``.
3. ``S_t`` = the ``topk`` positions ``s <= t`` of largest ``I[t, s]`` (all
   of them while there are no more; ties to the lower position, as
   ``lax.top_k``).
4. ``o_t = softmax_{s in S_t}(q_t . k_s * head_dim^-1/2) v_s`` per query
   head, head ``r`` reading KV head ``r // (n_head / n_kv_head)``; ``x +=
   W_o o``.
5. ``x += sum_{e in top8(softmax(W_g RMSNorm(x)))} p_e / sum(p) *
   SwiGLU_e(.)``: the router over ALL ``routed_over`` experts in float32,
   the sum over the experts HELD alone.

One unpadded sequence at a time (or one padded on the RIGHT: a causal
model's real rows never read the padding), float32 throughout, every
matmul at precision ``highest``. No cache, no kernels, no batching: the
dense ``[T, T]`` indexer scores and a mask from ``top_k`` a row, computed
in blocks of query positions so that the longest request at the published
widths fits beside the served system. A Python loop over the layers casts
ONE layer's weights to float32 at a time; the head runs in vocabulary
blocks and only at the positions asked for.

Departures from what is published (the configuration's ``assumed`` gives
each one's source):

* the config carries no switch for the per-head q/k norm; it is
  Qwen3-MoE's, whose keys (``head_dim``, ``num_experts``, ``norm_topk_prob``,
  ``decoder_sparse_step``) this config carries;
* ``sa_config`` gives the indexer's sizes alone. Its equations are
  DeepSeek-V3.2-Exp's published indexer with the block's normalised input
  in place of the query latent (this model has none); that indexer's
  Hadamard rotation of ``qI`` and ``kI`` is an orthogonal map applied to
  both and changes no score, and its 8-bit keys are storage: neither is
  computed;
* ``q_chunk_size`` / ``kv_chunk_size`` are read as the tiling of a pass of
  many queries and change no value. A block-wise reading exists (whole
  key chunks chosen a query chunk) and was not taken: ``described_as``
  names a token-level indexer and ``topk`` counts tokens;
* ``held = (first, count)``: the expert layer computes the routed part of
  the experts ``first .. first + count - 1`` alone and leaves out what the
  others would add, as one chip of an expert-parallel deployment does
  before the exchange that sums the shares. With ``held = (0, routed_over)``
  it is the published layer;
* nothing is rounded to the model's dtype anywhere.
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


def indexer_sections(sections, head_dim, indexer_head_dim):
    """The model's rotary sections in the indexer's own ladder: the same
    proportions of ``indexer_head_dim / 2`` frequencies."""
    out = tuple(s * indexer_head_dim // head_dim for s in sections)
    if sum(out) != indexer_head_dim // 2:
        raise ValueError(
            f"mrope_section {tuple(sections)} of a head of {head_dim} has "
            f"no whole counterpart in an indexer head of {indexer_head_dim}")
    return out


def sizes(config):
    """What the equations need, from the published keys of a configuration
    file (``config.json``'s names) and its ``moe`` block (which experts
    this share holds, of how many)."""
    c = config
    sa, rs = c["sa_config"], c["rope_scaling"]
    if (c["attention_bias"] or c["hidden_act"] != "silu"
            or c["tie_word_embeddings"] or not c["norm_topk_prob"]
            or c["decoder_sparse_step"] != 1 or c["mlp_only_layers"]
            or c["use_sliding_window"] or sa["indexer_num_kv_heads"] != 1
            or rs["rope_type"] != "default"):
        raise ValueError(
            "one form: no bias, SiLU, an untied head, renormalised top-k "
            "weights, experts in every layer, no sliding window, one "
            "index key head, plain (sectioned) rotary")
    sections = tuple(int(x) for x in rs["mrope_section"])
    return {
        "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
        "n_head": int(c["num_attention_heads"]),
        "n_kv_head": int(c["num_key_value_heads"]),
        "head_dim": int(c["head_dim"]),
        "sections": sections,
        "ix_heads": int(sa["indexer_num_heads"]),
        "ix_dim": int(sa["indexer_head_dim"]),
        "ix_sections": indexer_sections(
            sections, int(c["head_dim"]), int(sa["indexer_head_dim"])),
        "topk": int(sa["topk"]),
        "n_routed": int(c["moe"]["routed_over"]),
        "held": tuple(int(x) for x in c["moe"]["experts_held"]),
        "top_k": int(c["num_experts_per_tok"]),
        # the shared ``position_stats`` (falcon_h1.py) scales the head by it
        "lm_head_multiplier": 1.0,
    }


def rotary(x, pos, theta, sections):
    """Rotate-half rotary over the last axis of ``x [T, heads, d]`` at the
    position streams ``pos [3, T]``: frequency ``i`` takes its angle from
    the stream of the section it lies in."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    stream = np.repeat(np.arange(len(sections)), sections)      # [d / 2]
    angles = pos.astype(jnp.float32)[stream, :].T * inv_freq    # [T, d/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def index_keys(x, p, s, pos):
    """``kI [T, ix_dim]``: the one index key a position, after its norm and
    rotary; with ``k`` and ``v`` all that the program's cache keeps."""
    k = layer_norm(mm(x, p["wk"]["kernel"]), p["k_norm"]["scale"],
                   p["k_norm"]["bias"], s["eps"])
    return rotary(k[:, None, :], pos, s["theta"], s["ix_sections"])[:, 0]


def attention(x, p, s, pos, at, query_block=256, with_chosen=False):
    """Equations 1-4. Returns ``(y, k [T, n_kv_head, d], v, kI [T,
    ix_dim], chosen, (qI [n, ix_heads, ix_dim], w [n, ix_heads]))``:
    ``chosen [T, T]`` bool (row ``t`` the set ``S_t``) with
    ``with_chosen``, else None; the indexer's queries and weights of the
    positions ``at [n]``."""
    t = x.shape[0]
    h, hkv, d = s["n_head"], s["n_kv_head"], s["head_dim"]
    qkv = mm(x, p["c_attn"]["kernel"])
    q = rms_norm(qkv[:, :h * d].reshape(t, h, d), p["q_norm"]["scale"],
                 s["eps"])
    k = rms_norm(qkv[:, h * d:(h + hkv) * d].reshape(t, hkv, d),
                 p["k_norm"]["scale"], s["eps"])
    v = qkv[:, (h + hkv) * d:].reshape(t, hkv, d)
    q = rotary(q, pos, s["theta"], s["sections"])
    k = rotary(k, pos, s["theta"], s["sections"])
    ix = p["indexer"]
    q_i = rotary(mm(x, ix["wq"]["kernel"]).reshape(t, s["ix_heads"],
                                                   s["ix_dim"]),
                 pos, s["theta"], s["ix_sections"])
    k_i = index_keys(x, ix, s, pos)
    w = mm(x, ix["weights_proj"]["kernel"]) \
        * (s["ix_heads"] ** -0.5 * s["ix_dim"] ** -0.5)
    qg = q.reshape(t, hkv, h // hkv, d)
    # blocks of query positions, one after another (``lax.map``: one
    # block's program, whatever the length); a query past the end sees
    # every position and is dropped again
    n_blocks = -(-t // query_block)
    padded = n_blocks * query_block - t

    def blocks(a):
        a = jnp.pad(a, [(0, padded)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape((n_blocks, query_block) + a.shape[1:])

    def one_block(xs):
        start, q_i_b, w_b, qg_b = xs
        causal = (jnp.arange(t)[None, :]
                  <= (start + jnp.arange(query_block))[:, None])  # [qb, T]
        dots = jnp.einsum("qjd,kd->qjk", q_i_b, k_i, precision=HIGHEST)
        index = jnp.sum(jax.nn.relu(dots) * w_b[:, :, None], 1)
        index = jnp.where(causal, index, -jnp.inf)
        if t > s["topk"]:
            vals, best = jax.lax.top_k(index, s["topk"])
            chosen = jnp.zeros(causal.shape, bool).at[
                jnp.arange(query_block)[:, None], best].max(vals > -jnp.inf)
        else:
            chosen = causal
        scores = jnp.einsum("qhgd,khd->hgqk", qg_b, k,
                            precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(
            jnp.where(chosen[None, None], scores, -jnp.inf), -1)
        out = jnp.einsum("hgqk,khd->qhgd", probs, v, precision=HIGHEST)
        return out, (chosen if with_chosen else jnp.zeros((), bool))

    out, sets = jax.lax.map(one_block, (
        jnp.arange(n_blocks) * query_block, blocks(q_i), blocks(w),
        blocks(qg)))
    y = out.reshape(n_blocks * query_block, h * d)[:t]
    return (mm(y, p["c_proj"]["kernel"]), k, v, k_i,
            sets.reshape(-1, t)[:t] if with_chosen else None,
            (q_i[at], w[at]))


def choose(q_i, w, keys, topk):
    """Equations 2-3 for ONE query over the index keys it sees, on the
    host in float64: the sorted ``min(topk, n)`` rows of ``keys [n,
    ix_dim]`` of largest ``I``, ties to the lower row (a stable sort), for
    ``q_i [ix_heads, ix_dim]`` and ``w [ix_heads]``."""
    dots = np.asarray(keys, np.float64) @ np.asarray(q_i, np.float64).T
    index = (np.maximum(dots, 0.0) * np.asarray(w, np.float64)).sum(1)
    return np.sort(np.argsort(-index, kind="stable")[:topk])


def swiglu(x, up, gate, down):
    return mm(mm(x, up) * jax.nn.silu(mm(x, gate)), down)


def route(x, gate_kernel, s):
    """``[T, n_routed]`` float32: the renormalised weight of each expert a
    token chose (the ``top_k`` largest of a softmax over ALL experts, ties
    to the lower index) and 0 elsewhere."""
    scores = jax.nn.softmax(mm(x, gate_kernel.astype(jnp.float32)), -1)
    t, e = scores.shape
    weights, chosen = jax.lax.top_k(scores, s["top_k"])
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    return jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], chosen].set(weights)


def moe(x, p, s):
    """Equation 5, summed over the experts HELD alone."""
    first, count = s["held"]
    weights = route(x, p["gate"]["kernel"], s)[:, first:first + count]
    ex = p["experts"]

    def one(y, e):
        out = swiglu(x, ex["wi"][e], ex["wg"][e], ex["wo"][e])
        return y + weights[:, e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    return y


def block(h, p, s, pos, at, with_chosen=False):
    mixed, k, v, k_i, chosen, queries = attention(
        rms_norm(h, p["ln_1"]["scale"], s["eps"]), p["attn"], s, pos, at,
        with_chosen=with_chosen)
    h = h + mixed
    h = h + moe(rms_norm(h, p["ln_2"]["scale"], s["eps"]), p["mlp"], s)
    return h, k, v, k_i, chosen, queries


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_sizes, with_chosen):
    s = dict(frozen_sizes)

    @jax.jit
    def layer(h, stacked, i, pos, at):
        p = jax.tree.map(lambda a: a[i].astype(jnp.float32), stacked)
        return block(h, p, s, pos, at, with_chosen)

    return layer


def text_positions(t, offset=0):
    """The three position streams of ``t`` text tokens, the first at
    ``offset``: all equal."""
    return jnp.broadcast_to(jnp.arange(t) + jnp.int32(offset), (3, t))


def hidden_and_states(params, ids, s, length=None, offset=0, positions=None,
                      with_chosen=False, queries_at=()):
    """``([T, C] float32 hidden states after the final norm, (k [layers,
    T, n_kv_head, d], v, kI [layers, T, ix_dim]), chosen, (qI [layers, n,
    ix_heads, ix_dim], w [layers, n, ix_heads]))`` of one sequence ``ids
    [T]``, unpadded or padded on the right (rows from ``length`` on are
    then nobody's), its first token at position ``offset`` of every
    stream, or at ``positions [3, T]``. ``chosen [layers, T, T]`` bool with
    ``with_chosen`` (tests), else None; the indexer's queries and weights
    at the ``n`` positions ``queries_at``."""
    del length      # causal: a real row never reads the padding
    ids = jnp.asarray(ids, jnp.int32)
    pos = text_positions(ids.shape[0], offset) if positions is None \
        else jnp.asarray(positions, jnp.int32)
    h = params["wte"]["embedding"][ids].astype(jnp.float32)
    layer = _layer_fn(tuple(sorted(s.items())), bool(with_chosen))
    stacked = params["h"]["block"]
    at = jnp.asarray(queries_at, jnp.int32).reshape(-1)
    ks, vs, kis, sets, qis, ws = [], [], [], [], [], []
    for i in range(jax.tree.leaves(stacked)[0].shape[0]):
        h, k, v, k_i, chosen, (q_i, w) = layer(h, stacked, i, pos, at)
        ks.append(k)
        vs.append(v)
        kis.append(k_i)
        sets.append(chosen)
        qis.append(q_i)
        ws.append(w)
    return (rms_norm(h, params["ln_f"]["scale"].astype(jnp.float32),
                     s["eps"]),
            (jnp.stack(ks), jnp.stack(vs), jnp.stack(kis)),
            jnp.stack(sets) if with_chosen else None,
            (jnp.stack(qis), jnp.stack(ws)))


def hidden(params, ids, s):
    return hidden_and_states(params, ids, s)[0]


def logits(params, ids, s, positions=None, vocab_block=32768, rotary_at=None):
    """[len(positions), vocab] float32 logits (numpy) at ``positions`` (all
    of them when None), the untied head applied in vocabulary blocks;
    ``rotary_at [3, T]`` the position streams where they are not the text's."""
    rows = hidden_and_states(params, ids, s, positions=rotary_at)[0]
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
