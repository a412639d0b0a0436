"""dots3-note-prev's language model (``model_type: dots3_note``; the keys of
its ``config.json``), plainly, over the parameter tree the program's ``GPT``
holds for a model that declares its layers' kinds: ``wte``, ``ln_f``,
``lm_head`` ``[C, vocab]`` (untied) and, under ``h``, one stack a kind of
block with a leading axis over that kind's layers in order:
``attention_dense`` (the leading ``first_k_dense_replace`` layers, here
full), ``attention`` and ``window`` (the program's names for
``full_attention`` and ``sliding_attention``). In a block: ``ln_1``,
``ln_2``; ``attn/q_a`` (``W_qa``), ``attn/q_a_norm``, ``attn/q_b`` (per head
``[q_nope | q_rope]``), ``attn/kv_a`` (``[c | r]``), ``attn/kv_a_norm``,
``attn/kv_b`` (per head ``[k_nope | v]``), ``attn/c_gate`` (``W_g`` ``[C,
heads]``), ``attn/c_proj`` (``W_o``) and, in a full layer,
``attn/indexer/wq`` (``W_Iq``, from the query latent), ``wk`` (``W_Ik``),
``k_norm`` (a LayerNorm's scale and bias), ``weights_proj`` (``W_Iw``); a
dense layer's ``mlp/c_gate``, ``c_fc``, ``c_proj``; an expert layer's
``mlp/gate`` (``W_r``), ``mlp/expert_bias``, ``mlp/experts/wg | wi | wo``
(the HELD experts') and ``mlp/shared`` (one SwiGLU).

The equations, ``x = RMSNorm(h)`` with eps ``rms_norm_eps``, no projection
with a bias:

*full_attention* (the un-prefixed sizes):

    c_q = RMSNorm(x W_qa) * s_q          q = c_q W_qb -> H x [q_nope | q_rope]
    [c | r] = x W_kva    c_kv = RMSNorm(c) * s_kv    k_rope = RoPE(r)
    [k_nope_h | v_h] = c_kv W_kvb        scale = (nope + rope)^-1/2
    qI = c_q W_Iq -> n x d     kI = LayerNorm(x W_Ik)   (rotary on the first
         ``qk_rope_head_dim`` dimensions of each)
    w  = x W_Iw * n^-1/2 * d^-1/2
    I(t, s) = sum_j w_j(t) ReLU(qI_j(t) . kI(s)),  s <= t
    S_t = the ``index_topk`` positions of largest I(t, .) among s <= t (ties
          to the lower position); all of them while there are no more
    score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + RoPE(q_rope_h(t)) .
                     k_rope(s)) * scale,   s in S_t
    o_h = sum_s softmax(score_h)(t, s) v_h(s)
    g = sigmoid(x W_g)                   y = concat_h(g_h o_h) W_o

*sliding_attention*: the same without the indexer at the ``swa_`` sizes and
``swa_rope_theta``, a query at ``i`` seeing ``0 <= i - j <
sliding_window_size``.

*MLP*: the leading dense layers SwiGLU; the others ``shared(x) + sum over
the chosen e that are HELD of w_e expert_e(x)`` with ``s = sigmoid(x W_r)``
over all ``n_routed_experts``, the ``num_experts_per_tok`` largest of ``s +
bias`` chosen, ``w_e = routed_scaling_factor * s_e / (sum of the chosen s +
1e-20)`` (DeepSeek-V3's ``noaux_tc`` gate).

Assumed where the config is silent (the configuration file's ``assumed``
says why each): ``s_q = (hidden / q_lora_rank)^1/2`` and ``s_kv = (hidden /
kv_lora_rank)^1/2`` with each kind's own ranks
(``apply_mla_qkv_lora_rescale``); the gate is one scalar a head from the
normalised input (``attention_gate_type: headwise``); the indexer's form
(LayerNorm on its key, rotary on the first ``qk_rope_head_dim`` dimensions,
the weight scale, queries from ``c_q``) is DeepSeek-V3.2-Exp's; the router's
correction bias is a parameter drawn from the seed; no expert groups.

Departures: ``held = (first, count)``: the expert layer computes the routed
part of the experts ``first .. first + count - 1`` alone (the router scores
all of them) and the shared expert once, in program and reference alike;
the vision tower, the audio encoder and multi-token prediction are not part
of the language model's config and are not here; nothing is rounded to the
model's dtype; ``offset`` is the first row's rotary position (a served
lane's rows begin after its bucket's padding).

One unpadded sequence at a time (or one padded on the RIGHT), float32,
every matmul at precision ``highest``, the NON-absorbed form: keys and
values are decompressed per head. No cache, no kernel. What a layer does to
each row alone runs over blocks of rows (``by_rows``); a full layer's sets
``S_t`` are made first, in blocks of queries; attention then walks the
heads in groups and, inside a group, the queries in blocks against every
key under the mask, so that 24,576 positions at the published widths fit
beside the served system.

``step_rows`` is the same arithmetic for one token a lane over rows that
are GIVEN (a served lane's own stored rows and its step's own choice), for
a comparison that the choice's noise does not enter.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# (the expert layer is AFMoE's to the letter: sigmoid scores over all
# experts, the bias in the choice alone, the chosen scores over their sum +
# 1e-20 times the factor, the held experts' part and the shared expert once)
from perfbench.reference.afmoe import by_rows, moe, route, swiglu  # noqa: F401
from perfbench.reference.falcon_h1 import (  # the generic pieces  # noqa: F401
    HIGHEST,
    _head_block,
    mm,
    position_stats,
    rms_norm,
)
from perfbench.reference.keye_vl import choose, layer_norm  # noqa: F401
from perfbench.reference.lfm2 import rotary

SLIDING, FULL = "sliding_attention", "full_attention"
# the published names of the layers' kinds -> the program's stacks
STACKS = {SLIDING: "window", FULL: "attention"}


def sizes(config):
    """What the equations need, from the published keys of a configuration
    file (``config.json``'s names) and its ``moe`` block."""
    c = config
    kinds = tuple(c["layer_types"])
    if (c["attention_bias"] or c["tie_word_embeddings"]
            or c["hidden_act"] != "silu" or c["scoring_func"] != "sigmoid"
            or c["topk_method"] != "noaux_tc" or not c["norm_topk_prob"]
            or c["rope_scaling"] is not None or c["n_shared_experts"] != 1
            or c["moe_layer_freq"] != 1
            or not c["apply_mla_qkv_lora_rescale"]
            or c["attention_gate_type"] != "headwise"
            or c["swa_attention_gate_type"] != "headwise"
            or set(kinds) - {SLIDING, FULL}
            or len(kinds) != c["num_hidden_layers"]):
        raise ValueError(
            "one form: no bias, an untied head, SwiGLU, sigmoid scores "
            "with a correction bias in the choice and renormalised "
            "weights, one shared expert, experts in every layer after the "
            "leading dense ones, plain rotary, rescaled latents, a gate a "
            f"head, and a kind ({SLIDING} | {FULL}) for every layer")
    hidden = int(c["hidden_size"])

    def kind(prefix, theta):
        q_rank, kv_rank = (int(c[prefix + k])
                           for k in ("q_lora_rank", "kv_lora_rank"))
        nope, rope = (int(c[prefix + k])
                      for k in ("qk_nope_head_dim", "qk_rope_head_dim"))
        return (("n_head", int(c[prefix + "num_attention_heads"])),
                ("nope", nope), ("rope", rope),
                ("v_dim", int(c[prefix + "v_head_dim"])),
                ("kv_rank", kv_rank), ("theta", float(theta)),
                ("scale", float(nope + rope) ** -0.5),
                ("s_q", (hidden / q_rank) ** 0.5),
                ("s_kv", (hidden / kv_rank) ** 0.5))

    return {
        "eps": float(c["rms_norm_eps"]), "kinds": kinds,
        FULL: kind("", c["rope_theta"]),
        SLIDING: kind("swa_", c["swa_rope_theta"]),
        "window": int(c["sliding_window_size"]),
        "ix_heads": int(c["index_n_heads"]),
        "ix_dim": int(c["index_head_dim"]),
        "ix_rope": int(c["qk_rope_head_dim"]),
        "topk": int(c["index_topk"]),
        "n_dense": int(c["first_k_dense_replace"]),
        "top_k": int(c["num_experts_per_tok"]),
        "route_scale": float(c["routed_scaling_factor"]),
        "held": tuple(int(x) for x in c["moe"]["experts_held"]),
        # the shared ``position_stats`` (falcon_h1.py) scales the head by it
        "lm_head_multiplier": 1.0,
    }


def index_rotary(x, s, theta, at):
    """Rotary on the first ``ix_rope`` dimensions of ``x [T, heads,
    ix_dim]``, the rest as they are."""
    n = s["ix_rope"]
    return jnp.concatenate([rotary(x[..., :n], theta, at), x[..., n:]], -1)


def latents(h, p, s, k, ln, offset, rows):
    """What a layer of kind sizes ``k`` needs of each row alone: ``(c_q [T,
    q_rank], c_kv [T, kv_rank], k_rope [T, rope], gate [T, H], index)``,
    ``index`` the indexer's ``(kI [T, d], w [T, n])`` of a layer that has
    one, else ``()``. The queries per head are made a head group at a
    time, where they are used."""
    ix = p.get("indexer")

    def one(hb, at):
        u = rms_norm(hb, ln, s["eps"])
        c_q = rms_norm(mm(u, p["q_a"]["kernel"]), p["q_a_norm"]["scale"],
                       s["eps"]) * k["s_q"]
        ckr = mm(u, p["kv_a"]["kernel"])
        r = k["kv_rank"]
        c_kv = rms_norm(ckr[:, :r], p["kv_a_norm"]["scale"], s["eps"]) \
            * k["s_kv"]
        k_rope = rotary(ckr[:, None, r:], k["theta"], offset + at)[:, 0]
        out = (c_q, c_kv, k_rope,
               jax.nn.sigmoid(mm(u, p["c_gate"]["kernel"])))
        if ix is None:
            return out
        k_i = index_rotary(layer_norm(
            mm(u, ix["wk"]["kernel"]), ix["k_norm"]["scale"],
            ix["k_norm"]["bias"], s["eps"])[:, None, :], s, k["theta"],
            offset + at)[:, 0]
        w = mm(u, ix["weights_proj"]["kernel"]) \
            * (s["ix_heads"] ** -0.5 * s["ix_dim"] ** -0.5)
        return out + (k_i, w)

    return by_rows(one, h, rows)


def index_queries(c_q, wq, s, theta, at):
    """``qI [rows, n, d]`` of the query latents ``c_q [rows, q_rank]``, the
    first at rotary position ``at`` (an ``[rows]`` vector of positions
    where the rows are not consecutive)."""
    q_i = mm(c_q, wq).reshape(-1, s["ix_heads"], s["ix_dim"])
    if jnp.ndim(at) == 0:
        return index_rotary(q_i, s, theta, at)
    # one row a position: rotate each alone
    return jax.vmap(lambda q, a: index_rotary(q[None], s, theta, a)[0])(
        q_i, at)


def chosen_sets(c_q, wq, k_i, w, s, theta, at, index_block):
    """``[rows, T]`` bool: the sets ``S_t`` of the queries whose latents are
    ``c_q [rows, q_rank]``, the first at row ``at`` of the ``T`` index keys
    ``k_i`` (rotary position ``at`` + the sequence's offset, which
    ``theta``'s caller has added), in blocks of ``index_block`` queries (a
    block's scores are ``[queries, index heads, T]`` float32)."""
    t, topk = k_i.shape[0], s["topk"]
    theta, offset = theta

    def one(xs, first):
        cq_b, w_b = xs
        q_b = index_queries(cq_b, wq, s, theta, offset + at + first)
        causal = jnp.arange(t)[None, :] \
            <= (at + first + jnp.arange(q_b.shape[0]))[:, None]
        if t <= topk:
            return causal
        dots = jnp.einsum("qjd,kd->qjk", q_b, k_i, precision=HIGHEST)
        index = jnp.where(causal, jnp.sum(
            jax.nn.relu(dots) * w_b[:, :, None], 1), -jnp.inf)
        vals, best = jax.lax.top_k(index, topk)
        return jnp.zeros(causal.shape, bool).at[
            jnp.arange(q_b.shape[0])[:, None], best].max(vals > -jnp.inf)

    return by_rows(one, (c_q, w), index_block)


def attention(h, p, s, kind, ln, offset=0, rows=2048, query_block=256,
              head_group=16, index_block=32, with_chosen=False,
              queries_at=()):
    """One layer's attention on ``norm(h; ln)``. Returns ``(y, kept,
    chosen, queries)``: ``kept`` what a lane keeps of the layer a position
    (``latent [T, kv_rank]``, ``rope_key [T, rope]`` and, of a full layer,
    ``index_key [T, ix_dim]``), ``chosen [T, T]`` bool of a full layer with
    ``with_chosen`` (else None), ``queries`` its indexer's ``(qI [n, heads,
    d], w [n, heads])`` at the positions ``queries_at``. The queries in
    blocks: a block's sets ``S_t`` first, then a scan over the heads in
    groups (a group's queries, its keys and values of every row, scores
    under the mask, its part of the output projection, summed), so that
    nothing of ``[T, T]`` or ``[T, heads, .]`` exists."""
    k = dict(s[kind])
    t = h.shape[0]
    H, dn, dr, dv, r = (k["n_head"], k["nope"], k["rope"], k["v_dim"],
                        k["kv_rank"])
    c_q, c_kv, k_rope, gate, *index = latents(h, p, s, k, ln, offset, rows)
    kept = {"latent": c_kv, "rope_key": k_rope}
    queries, per_query = None, (c_q, gate)
    if kind == FULL:
        k_i, w = index
        kept["index_key"] = k_i
        wq = p["indexer"]["wq"]["kernel"]
        at = jnp.asarray(queries_at, jnp.int32).reshape(-1)
        queries = (index_queries(c_q[at], wq, s, k["theta"], offset + at),
                   w[at])
        per_query += (w,)
    g = min(head_group, H)
    n_groups = H // g
    weights = (
        jnp.moveaxis(p["q_b"]["kernel"].reshape(-1, n_groups, g, dn + dr),
                     1, 0),
        jnp.moveaxis(p["kv_b"]["kernel"].reshape(r, n_groups, g, dn + dv),
                     1, 0),
        p["c_proj"]["kernel"].reshape(n_groups, g * dv, -1))

    def block(xs, at):
        cq_b, gate_b = xs[:2]
        n = cq_b.shape[0]
        ahead = (at + jnp.arange(n))[:, None] - jnp.arange(t)[None, :]
        seen = (ahead >= 0) & (ahead < s["window"]) if kind == SLIDING \
            else chosen_sets(cq_b, wq, k_i, xs[2], s, (k["theta"], offset),
                             at, index_block)

        def group(out, ws):
            wq_g, wkv_g, wo_g, gate_g = ws
            kv = jnp.einsum("tr,rgd->tgd", c_kv, wkv_g, precision=HIGHEST)
            q = jnp.einsum("tr,rgd->tgd", cq_b, wq_g, precision=HIGHEST)
            qn, qr = q[..., :dn], rotary(q[..., dn:], k["theta"],
                                         offset + at)
            scores = (jnp.einsum("qgd,kgd->gqk", qn, kv[..., :dn],
                                 precision=HIGHEST)
                      + jnp.einsum("qgd,kd->gqk", qr, k_rope,
                                   precision=HIGHEST)) * k["scale"]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            y = jnp.einsum("gqk,kgd->qgd", probs, kv[..., dn:],
                           precision=HIGHEST) * gate_g[:, :, None]
            return out + mm(y.reshape(n, g * dv), wo_g), None

        out, _ = jax.lax.scan(
            group, jnp.zeros((n, h.shape[1]), h.dtype),
            weights + (jnp.moveaxis(gate_b.reshape(n, n_groups, g), 1, 0),))
        return (out, seen) if with_chosen and kind == FULL else (out,)

    y, *chosen = by_rows(block, per_query, query_block)
    return y, kept, chosen[0] if chosen else None, queries


def block(h, p, s, kind, offset, experts=None, rows=2048, **attending):
    """One decoder layer: two norms, two residual branches. Returns ``(h,
    kept, chosen, queries)``."""
    mixed, kept, chosen, queries = attention(
        h, p["attn"], s, kind, p["ln_1"]["scale"], offset, rows, **attending)

    def rest(rows_of, at):
        hb, mb = rows_of
        hb = hb + mb
        m = rms_norm(hb, p["ln_2"]["scale"], s["eps"])
        if experts is not None:
            return hb + moe(m, p["mlp"], s, experts)
        return hb + swiglu(m, p["mlp"]["c_gate"]["kernel"],
                           p["mlp"]["c_fc"]["kernel"],
                           p["mlp"]["c_proj"]["kernel"])

    return by_rows(rest, (h, mixed), rows), kept, chosen, queries


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_sizes, kind, with_chosen):
    s = dict(frozen_sizes)

    @jax.jit
    def layer(h, stacked, i, offset, queries_at):
        held = stacked["mlp"].get("experts")
        rest = dict(stacked, mlp={k: v for k, v in stacked["mlp"].items()
                                  if k != "experts"})
        p = jax.tree.map(lambda a: a[i].astype(jnp.float32), rest)
        experts = None if held is None else (
            lambda name, e: held[name][i, e].astype(jnp.float32))
        return block(h, p, s, kind, offset, experts,
                     with_chosen=with_chosen, queries_at=queries_at)

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


def hidden_and_states(params, ids, s, offset=0, with_chosen=False,
                      queries_at=()):
    """``([T, C] float32 hidden states after the final norm, kept, chosen,
    queries)`` of one sequence ``ids [T]``, unpadded or padded on the right,
    its first token at rotary position ``offset``: an entry a layer of what
    ``attention`` returns (``chosen`` and ``queries`` None for a sliding
    layer)."""
    ids = jnp.asarray(ids, jnp.int32)
    offset = jnp.int32(offset)
    at = jnp.asarray(queries_at, jnp.int32).reshape(-1)
    h = params["wte"]["embedding"][ids].astype(jnp.float32)
    frozen = tuple(sorted(s.items()))
    kept, sets, queries = [], [], []
    for kind, stacked, i in layers_of(params, s):
        h, k, chosen, q = _layer_fn(frozen, kind, bool(with_chosen))(
            h, stacked, i, offset, at)
        kept.append(k)
        sets.append(chosen)
        queries.append(q)
    return (rms_norm(h, params["ln_f"]["scale"].astype(jnp.float32),
                     s["eps"]), kept, sets, queries)


def _step_block(h, p, s, kind, at, held, experts):
    """One decoder layer over ONE row a lane, the rows each query attends
    over GIVEN: ``h [B, C]``, ``at [B]`` the rows' rotary positions,
    ``held = (latent [B, K, kv_rank], rope_key [B, K, rope], seen [B, K])``
    what each lane keeps of the rows its step attended over (the step's own
    row among them) and which of them count. Returns ``(h, wrote)``:
    ``wrote`` the ``latent``, ``rope_key`` (and ``index_key``) the layer
    makes of each lane's row. The equations are ``attention``'s, a lane at
    a time (``lax.map``) and inside a lane the heads in groups."""
    k = dict(s[kind])
    H, dn, dr, dv, r = (k["n_head"], k["nope"], k["rope"], k["v_dim"],
                        k["kv_rank"])
    a, ln = p["attn"], p["ln_1"]["scale"]
    g = min(16, H)
    w_q = jnp.moveaxis(a["q_b"]["kernel"].reshape(-1, H // g, g, dn + dr),
                       1, 0)
    w_kv = jnp.moveaxis(a["kv_b"]["kernel"].reshape(r, H // g, g, dn + dv),
                        1, 0)

    def lane(xs):
        h1, at1, lat, rk, seen = xs
        c_q, c_kv, k_rope, gate, *index = latents(
            h1[None], a, s, k, ln, at1, 1)

        def group(_, ws):
            wq_g, wkv_g, gate_g = ws
            kv = jnp.einsum("kr,rgd->kgd", lat, wkv_g, precision=HIGHEST)
            q = jnp.einsum("tr,rgd->tgd", c_q, wq_g, precision=HIGHEST)
            scores = (jnp.einsum("qgd,kgd->gqk", q[..., :dn], kv[..., :dn],
                                 precision=HIGHEST)
                      + jnp.einsum("qgd,kd->gqk",
                                   rotary(q[..., dn:], k["theta"], at1), rk,
                                   precision=HIGHEST)) * k["scale"]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return None, jnp.einsum(
                "gqk,kgd->qgd", probs, kv[..., dn:],
                precision=HIGHEST)[0] * gate_g[:, None]

        _, y = jax.lax.scan(group, None, (w_q, w_kv,
                                          gate[0].reshape(H // g, g)))
        wrote = {"latent": c_kv[0], "rope_key": k_rope[0]}
        if index:
            wrote["index_key"] = index[0][0]
        return mm(y.reshape(1, H * dv), a["c_proj"]["kernel"])[0], wrote

    mixed, wrote = jax.lax.map(lane, (h, at) + tuple(held))
    h = h + mixed
    m = rms_norm(h, p["ln_2"]["scale"], s["eps"])
    if experts is not None:
        return h + moe(m, p["mlp"], s, experts), wrote
    return h + swiglu(m, p["mlp"]["c_gate"]["kernel"],
                      p["mlp"]["c_fc"]["kernel"],
                      p["mlp"]["c_proj"]["kernel"]), wrote


@functools.lru_cache(maxsize=None)
def _step_fn(frozen_sizes, kind):
    s = dict(frozen_sizes)

    @jax.jit
    def layer(h, stacked, i, at, held):
        kept = stacked["mlp"].get("experts")
        rest = dict(stacked, mlp={k: v for k, v in stacked["mlp"].items()
                                  if k != "experts"})
        p = jax.tree.map(lambda a: a[i].astype(jnp.float32), rest)
        experts = None if kept is None else (
            lambda name, e: kept[name][i, e].astype(jnp.float32))
        return _step_block(h, p, s, kind, at, held, experts)

    return layer


def step_rows(params, ids, at, s, held):
    """ONE decode step of ``B`` lanes replayed from what the lanes keep,
    the selection given (teacher-forced): ``ids [B]`` each lane's token,
    ``at [B]`` its rotary position, ``held`` an entry a layer of
    ``(latent [B, K, kv_rank], rope_key [B, K, rope], seen [B, K] bool)``,
    the rows the lane's step attended over as the LANE stores them (a full
    layer's chosen rows, a window layer's ring) and which of them it saw.
    Returns an entry a layer of the row the layer makes for the token
    (``latent [B, kv_rank]``, ``rope_key``, a full layer's ``index_key``).
    Nothing of the reference's own earlier rows or of its own choice enters:
    what differs from the lane's row is this one step's arithmetic."""
    at = jnp.asarray(at, jnp.int32)
    h = params["wte"]["embedding"][jnp.asarray(ids, jnp.int32)].astype(
        jnp.float32)
    frozen, rows = tuple(sorted(s.items())), []
    for (kind, stacked, i), keys in zip(layers_of(params, s), held):
        h, wrote = _step_fn(frozen, kind)(
            h, stacked, i, at, tuple(jnp.asarray(x) for x in keys))
        rows.append(wrote)
    return rows


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
