"""AFMoE's forward pass (Arcee's Trinity; ``modeling_afmoe.py`` under the
keys of its ``config.json``), plainly, over the parameter tree the
program's ``GPT`` holds for a model that declares its layers' kinds:
``wte``, ``ln_f``, ``lm_head`` ``[C, vocab]`` (untied) and, under ``h``, one
stack a kind of block with a leading axis over that kind's layers in order:
``window_dense`` (the leading ``num_dense_layers`` layers, here all
sliding), ``window`` and ``attention`` (the program's names for
``sliding_attention`` and ``full_attention``). In a block: ``ln_1``
(``input_layernorm``), ``ln_1_post`` (``post_attention_layernorm``),
``ln_2`` (``pre_mlp_layernorm``), ``ln_2_post`` (``post_mlp_layernorm``);
``attn/c_attn`` (q, k and v side by side), ``attn/c_gate``
(``gate_proj``), ``attn/q_norm``, ``attn/k_norm`` (one weight a head
dimension), ``attn/c_proj``; a dense layer's ``mlp/c_gate`` (``W1``),
``c_fc`` (``W3``), ``c_proj`` (``W2``); an expert layer's ``mlp/gate``
(``Wr``), ``mlp/expert_bias`` (``b``), ``mlp/experts/wg | wi | wo`` (the
HELD experts' ``W1 | W3 | W2``) and ``mlp/shared`` (one SwiGLU).

The equations, ``h`` the residual stream, no projection with a bias,
``norm(x; w) = x / sqrt(mean(x^2) + eps) * w``:

* ``h = E[ids] * sqrt(hidden)`` (``mup_enabled``);
* ``a = norm(h; ln_1)``; ``q = a Wq`` ``[T, heads, d]``, ``k = a Wk``, ``v
  = a Wv`` ``[T, kv_heads, d]``, ``g = a Wg``; q and k normed per head
  over ``d``; on ``sliding_attention`` layers rotary on q and k (halves, no
  scaling), on ``full_attention`` layers NONE; scores ``q_i . k_j /
  sqrt(d)`` for ``0 <= i - j`` and, on sliding layers, ``i - j <
  sliding_window``; softmax; each KV head serving ``heads / kv_heads``
  query heads; ``o = (P v) * sigmoid(g)``; ``h = h + norm(o Wo;
  ln_1_post)``;
* ``m = norm(h; ln_2)``; a dense layer: ``f = (silu(m W1) * (m W3)) W2``;
  an expert layer: ``s = sigmoid(m Wr)`` over ALL ``routed_over`` experts;
  chosen = the ``num_experts_per_tok`` largest of ``s + b``, ties to the
  lower index; ``w_e = route_scale * s_e / (sum of the chosen s + 1e-20)``;
  ``f = shared(m) + sum over the chosen e that are HELD of w_e
  expert_e(m)``; ``h = h + norm(f; ln_2_post)``;
* ``logits = norm(h; ln_f) W_head``.

``held = (first, count)``: the expert layer computes the routed part of the
experts ``first .. first + count - 1`` alone and leaves out what the other
devices of the deployment would add, in program and reference alike; with
``held = (0, routed_over)`` it is the published layer. The shared expert is
computed here, once (eight shares' routed parts plus it add up to the uncut
layer: tests/unit/test_afmoe.py).

One unpadded sequence at a time (or one padded on the RIGHT: a causal
model's earlier rows never read the padding), float32 throughout, every
matmul at precision ``highest``. No cache, no kernels, no batching. A
Python loop over the layers casts ONE layer's attention and dense weights
to float32 at a time and one expert's at a time inside the loop over the
experts (an expert's output times the token's weight for it, zero where the
token did not choose it); what a layer does to each row alone runs over
blocks of rows (``by_rows``) and attention in blocks of query positions
over every key under the mask written as above, so that the longest request
at the published widths fits beside the served system; the head runs in
vocabulary blocks and only at the positions asked for.

Departures from the published code, each a matter of form: nothing is
rounded to the model's dtype anywhere; ``offset`` is the first row's rotary
position (a served lane's rows begin after its bucket's padding): the
scores see differences of positions alone, so it moves no output, only the
rotation the sliding layers' keys come out with.
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
from perfbench.reference.lfm2 import rotary

SLIDING, FULL = "sliding_attention", "full_attention"
# the published names of the layers' kinds -> the program's stacks
STACKS = {SLIDING: "window", FULL: "attention"}


def sizes(config):
    """What the equations need, from the published keys of a configuration
    file (``config.json``'s names) and its ``moe`` block."""
    c = config
    kinds = tuple(c["layer_types"])
    if (c["tie_word_embeddings"] or c["hidden_act"] != "silu"
            or c["score_func"] != "sigmoid" or c["rope_scaling"] is not None
            or c["num_shared_experts"] != 1 or c["n_group"] != 1
            or not c["mup_enabled"] or set(kinds) - {SLIDING, FULL}
            or len(kinds) != c["num_hidden_layers"]):
        raise ValueError(
            "one form: an untied head, SwiGLU, sigmoid scores over one "
            "group, one shared expert, plain rotary, the embedding scaled "
            f"by sqrt(hidden), and a kind ({SLIDING} | {FULL}) for every "
            "layer")
    return {
        "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
        "n_head": int(c["num_attention_heads"]),
        "n_kv_head": int(c["num_key_value_heads"]),
        "head_dim": int(c["head_dim"]), "window": int(c["sliding_window"]),
        "kinds": kinds, "n_dense": int(c["num_dense_layers"]),
        "top_k": int(c["num_experts_per_tok"]),
        "route_scale": float(c["route_scale"]),
        "held": tuple(int(x) for x in c["moe"]["experts_held"]),
        "embed_scale": float(c["hidden_size"]) ** 0.5,
        # the shared ``position_stats`` (falcon_h1.py) scales the head by it
        "lm_head_multiplier": 1.0,
    }


def by_rows(fn, x, rows):
    """``fn(block of rows [rows, ...], first row's index)`` over ``x [T,
    ...]`` (or a tuple of such) a block at a time (``lax.map``), the results side by side
    again: what a layer does to each row alone is done to ``rows`` rows at
    a time, so that its temporaries are a block's and not the sequence's
    (the longest request at the published widths beside the served
    system)."""
    t = jax.tree.leaves(x)[0].shape[0]
    rows = math.gcd(t, rows)
    out = jax.lax.map(lambda at: fn(*at), (
        jax.tree.map(lambda a: a.reshape((t // rows, rows) + a.shape[1:]),
                     x), jnp.arange(0, t, rows)))
    return jax.tree.map(lambda a: a.reshape((t,) + a.shape[2:]), out)


def attention(h, p, s, kind, ln, offset=0, rows=2048, query_block=64):
    """``AfmoeAttention.forward`` on ``norm(h; ln)``. Returns ``(y, k [T,
    kv_heads, d] after its norm and, on a sliding layer, rotary, v [T,
    kv_heads, d])``. Keys and values of every row first, then the queries
    in blocks of ``query_block`` rows against all of them."""
    t = h.shape[0]
    n_head, hkv, d = s["n_head"], s["n_kv_head"], s["head_dim"]
    w = p["c_attn"]["kernel"]
    turn = (lambda x, at: rotary(x, s["theta"], offset + at)) \
        if kind == SLIDING else (lambda x, at: x)

    def keys(hb, at):
        kv = mm(rms_norm(hb, ln, s["eps"]), w[:, n_head * d:])
        k = rms_norm(kv[:, :hkv * d].reshape(-1, hkv, d),
                     p["k_norm"]["scale"], s["eps"])
        return turn(k, at), kv[:, hkv * d:].reshape(-1, hkv, d)

    k, v = by_rows(keys, h, rows)

    def rows_of(hb, at):
        u = rms_norm(hb, ln, s["eps"])
        q = turn(rms_norm(mm(u, w[:, :n_head * d]).reshape(-1, n_head, d),
                          p["q_norm"]["scale"], s["eps"]), at)
        ahead = (at + jnp.arange(hb.shape[0]))[:, None] \
            - jnp.arange(t)[None, :]                        # i - j
        seen = (ahead >= 0) & (ahead < s["window"]) if kind == SLIDING \
            else ahead >= 0
        scores = jnp.einsum("qhgd,khd->hgqk",
                            q.reshape(-1, hkv, n_head // hkv, d), k,
                            precision=HIGHEST) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        y = jnp.einsum("hgqk,khd->qhgd", probs, v, precision=HIGHEST)
        y = y.reshape(-1, n_head * d) \
            * jax.nn.sigmoid(mm(u, p["c_gate"]["kernel"]))
        return mm(y, p["c_proj"]["kernel"])

    return by_rows(rows_of, h, query_block), k, v


def swiglu(x, gate, up, down):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def route(x, p, s, biased=True):
    """``AfmoeTokenChoiceRouter.forward``: ``[T, routed_over]`` float32,
    the weight of each expert a token chose and 0 elsewhere."""
    scores = jax.nn.sigmoid(mm(x, p["gate"]["kernel"].astype(jnp.float32)))
    t, e = scores.shape
    choose_by = scores + p["expert_bias"].astype(jnp.float32) if biased \
        else scores
    _, chosen = jax.lax.top_k(choose_by, s["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * s["route_scale"]
    return jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], chosen].set(picked)


def moe(x, p, s, experts):
    """``AfmoeMoE.forward``: the shared expert on every token, and the
    routed part summed over the experts HELD alone. ``experts(name, e)`` is
    held expert ``e``'s matrix ``name`` in float32."""
    first, count = s["held"]
    weights = route(x, p, s)[:, first:first + count]

    def one(y, e):
        out = swiglu(x, experts("wg", e), experts("wi", e), experts("wo", e))
        return y + weights[:, e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    sh = p["shared"]
    return y + swiglu(x, sh["c_gate"]["kernel"], sh["c_fc"]["kernel"],
                      sh["c_proj"]["kernel"])


def block(h, p, s, kind, offset, experts=None, rows=2048):
    """``AfmoeDecoderLayer.forward``: four norms, two residual branches.
    Returns ``(h, k, v)``."""
    mixed, k, v = attention(h, p["attn"], s, kind, p["ln_1"]["scale"],
                            offset, rows)

    def rest(rows_of, at):
        hb, mb = rows_of
        hb = hb + rms_norm(mb, p["ln_1_post"]["scale"], s["eps"])
        m = rms_norm(hb, p["ln_2"]["scale"], s["eps"])
        if experts is not None:
            f = moe(m, p["mlp"], s, experts)
        else:
            f = swiglu(m, p["mlp"]["c_gate"]["kernel"],
                       p["mlp"]["c_fc"]["kernel"],
                       p["mlp"]["c_proj"]["kernel"])
        return hb + rms_norm(f, p["ln_2_post"]["scale"], s["eps"])

    return by_rows(rest, (h, mixed), rows), k, v


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_sizes, kind):
    s = dict(frozen_sizes)

    @jax.jit
    def layer(h, stacked, i, offset):
        held = stacked["mlp"].get("experts")
        rest = dict(stacked, mlp={k: v for k, v in stacked["mlp"].items()
                                  if k != "experts"})
        p = jax.tree.map(lambda a: a[i].astype(jnp.float32), rest)
        experts = None if held is None else (
            lambda name, e: held[name][i, e].astype(jnp.float32))
        return block(h, p, s, kind, offset, experts)

    return layer


def layers_of(params, s):
    """``[(kind, stacked tree, index)]`` of the model's blocks in order:
    each layer's place in the stack of its kind of block."""
    out, seen = [], {}
    for layer, kind in enumerate(s["kinds"]):
        name = STACKS[kind] + ("_dense" if layer < s["n_dense"] else "")
        out.append((kind, params["h"][name], seen.get(name, 0)))
        seen[name] = seen.get(name, 0) + 1
    return out


def hidden_and_states(params, ids, s, offset=0):
    """``([T, C] float32 hidden states after the final norm, [(k, v)] a
    layer, each [T, kv_heads, d])`` of one sequence ``ids [T]``, unpadded
    or padded on the right (a causal model's real rows never read the
    padding), its first token at rotary position ``offset``."""
    ids = jnp.asarray(ids, jnp.int32)
    offset = jnp.int32(offset)
    h = params["wte"]["embedding"][ids].astype(jnp.float32) \
        * s["embed_scale"]
    frozen = tuple(sorted(s.items()))
    kept = []
    for kind, stacked, i in layers_of(params, s):
        h, k, v = _layer_fn(frozen, kind)(h, stacked, i, offset)
        kept.append((k, v))
    return rms_norm(h, params["ln_f"]["scale"].astype(jnp.float32),
                    s["eps"]), kept


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
