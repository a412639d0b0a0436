"""Attention over a chosen few of the cached positions (a lightning
indexer, after DeepSeek-V3.2-Exp's sparse attention; ``GPTConfig.indexer``).

A small indexer scores every cached position for a query,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)

with ``qI`` a few small heads, ``kI`` ONE small key a position (the third
leaf a lane keeps beside keys and values) and ``w`` a weight a head; the
``topk`` positions ``s <= t`` of largest ``I`` are chosen (all of them while
there are no more than ``topk``; ties to the lower position, ``lax.top_k``'s
rule) and the query attends, per head, over those rows alone.

Plain XLA, two forms of the same sums, chosen by the caller from what its
call shows:

* :func:`attend_chosen_rows`: ONE query token a lane over a cache. The
  scores run over a layer's index keys, ``top_k`` gives the rows, and the
  rows' keys and values are gathered out of the stacked leaves where they
  lie (no layer's slice of keys or values is made);
* :func:`attend_tiled`: many query tokens (a prefill, a continuation, a
  pass without a cache), a tile of ``q_chunk`` queries at a time: the
  tile's scores over all keys, the mask of its chosen positions, then an
  online softmax over key tiles of ``kv_chunk``. No ``[heads, T, T]``
  array exists; the largest is a tile's ``[B, index heads, q_chunk, S]``
  float32 dots.
"""
import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry.scopes import (
    SCOPE_DSA_ATTN,
    SCOPE_DSA_INDEX_SCORES,
    SCOPE_DSA_SELECT,
)

_NEG = float("-inf")


def index_scores(q_idx, k_idx, w):
    """``I [B, T, S]`` float32 of ``q_idx [B, T, Hi, Di]``, ``k_idx [B, S,
    Di]`` and ``w [B, T, Hi]`` (float32). The dots accumulate in float32;
    the weighted sum over the index heads is a float32 multiply and add,
    not a matmul (which a TPU would take in bfloat16 passes)."""
    dots = jnp.einsum("bqjd,bsd->bqjs", q_idx, k_idx,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[..., None].astype(jnp.float32),
                   axis=2)


def choose(scores, visible, topk: int):
    """The rows a query attends over: ``(rows [B, T, K] int32, ok [B, T,
    K] bool)`` with ``K = min(topk, S)``; ``ok`` is False where fewer than
    ``K`` positions are visible (such a row is nobody's)."""
    masked = jnp.where(visible, scores, _NEG)
    vals, rows = jax.lax.top_k(masked, min(topk, scores.shape[-1]))
    return rows, vals > _NEG


def chosen_mask(scores, visible, topk: int):
    """``[B, T, S]`` bool: True at the positions :func:`choose` gives,
    without a scatter: everything above the ``topk``-th largest visible
    score, and of the positions that tie with it the lowest, as many as
    are left (``lax.top_k`` breaks ties to the lower index)."""
    S = scores.shape[-1]
    if S <= topk:
        return visible
    masked = jnp.where(visible, scores, _NEG)
    kth = jax.lax.top_k(masked, topk)[0][..., -1:]
    above = masked > kth
    ties = (masked == kth) & visible
    left = topk - jnp.sum(above, axis=-1, keepdims=True)
    # the ties in position order: the first ``left`` of them
    first = jnp.cumsum(ties.astype(jnp.int32), axis=-1) <= left
    return above | (ties & first)


def attend_chosen_rows(q, keys, values, layer, rows, ok, scale, dtype):
    """One query token a lane over its chosen rows: ``q [B, H, D]``;
    ``keys`` / ``values`` the stacked ``[L, B, S, Hkv, D]`` leaves with
    ``layer`` this call's index (or one layer's ``[B, S, Hkv, D]`` with
    ``layer`` None); ``rows`` / ``ok`` ``[B, K]``. Returns ``[B, H, D]``.
    Query head ``r`` reads KV head ``r // (H / Hkv)``; scores and softmax
    in float32."""
    B, H, D = q.shape
    lane = jnp.arange(B)[:, None]
    at = (lane, rows) if layer is None else (layer, lane, rows)
    k_sel, v_sel = keys[at], values[at]                 # [B, K, Hkv, D]
    Hkv = k_sel.shape[2]
    qg = q.reshape(B, Hkv, H // Hkv, D)
    att = jnp.einsum("bhgd,bkhd->bhgk", qg, k_sel,
                     preferred_element_type=jnp.float32) * scale
    att = jnp.where(ok[:, None, None, :], att, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(att, axis=-1).astype(dtype)
    y = jnp.einsum("bhgk,bkhd->bhgd", p, v_sel)
    return y.reshape(B, H, D)


def decode_step(q, q_idx, w, keys, values, index_keys, layer, visible,
                topk: int, scale, dtype):
    """The whole selection of one decode token a lane: scores over the
    layer's index keys, the choice, attention over the chosen rows.
    ``q [B, H, D]``, ``q_idx [B, Hi, Di]``, ``w [B, Hi]``, the three
    stacked leaves, ``visible [B, S]``. Returns ``(y [B, H, D], rows, ok)``."""
    with jax.named_scope(SCOPE_DSA_INDEX_SCORES):
        k_idx = index_keys if layer is None else \
            jax.lax.dynamic_index_in_dim(index_keys, layer, 0,
                                         keepdims=False)
        scores = index_scores(q_idx[:, None], k_idx, w[:, None])
    with jax.named_scope(SCOPE_DSA_SELECT):
        rows, ok = choose(scores, visible[:, None], topk)
        rows, ok = rows[:, 0], ok[:, 0]
    with jax.named_scope(SCOPE_DSA_ATTN):
        y = attend_chosen_rows(q, keys, values, layer, rows, ok, scale,
                               dtype)
    return y, rows, ok


def attend_tiled(q, k, v, q_idx, k_idx, w, q_pos, k_valid, topk: int,
                 q_chunk: int, kv_chunk: int, scale, dtype,
                 keep_mask: bool = False):
    """Many query tokens over ``S`` keys: ``q [B, T, H, D]``, ``k`` / ``v``
    ``[B, S, Hkv, D]``, ``q_idx [B, T, Hi, Di]``, ``k_idx [B, S, Di]``,
    ``w [B, T, Hi]``; ``q_pos [B, T]`` the key row each query sits at (it
    sees the rows ``<= q_pos``), ``k_valid [B, S]`` which rows hold a
    token. Returns ``(y [B, T, H, D], chosen)``: ``chosen [B, T, S]`` bool
    with ``keep_mask`` (tests), else None. A scan over query tiles of
    ``q_chunk``; inside one, the tile's scores over all keys, its chosen
    positions, and an online softmax over key tiles of ``kv_chunk``, whose
    running maximum, sum and weighted values are float32."""
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    c, kc = min(q_chunk, T), min(kv_chunk, S)
    n_q, n_k = -(-T // c), -(-S // kc)

    def pad(t, axis, to, value=0):
        extra = to - t.shape[axis]
        if not extra:
            return t
        widths = [(0, 0)] * t.ndim
        widths[axis] = (0, extra)
        return jnp.pad(t, widths, constant_values=value)

    # a padded query sits before every row and sees none; a padded key
    # holds no token
    q, q_idx, w = (pad(t, 1, n_q * c) for t in (q, q_idx, w))
    q_pos = pad(q_pos.astype(jnp.int32), 1, n_q * c, -1)
    k, v, k_idx = (pad(t, 1, n_k * kc) for t in (k, v, k_idx))
    k_valid = pad(k_valid.astype(jnp.bool_), 1, n_k * kc, False)
    Sp = n_k * kc

    def tiles(t):       # [B, n_q * c, ...] -> [n_q, B, c, ...]
        return jnp.moveaxis(t.reshape((B, n_q, c) + t.shape[2:]), 1, 0)

    def q_tile(_, xs):
        qt, qit, wt, pt = xs
        with jax.named_scope(SCOPE_DSA_INDEX_SCORES):
            scores = index_scores(qit, k_idx, wt)               # [B, c, Sp]
        with jax.named_scope(SCOPE_DSA_SELECT):
            visible = ((jnp.arange(Sp)[None, None, :] <= pt[:, :, None])
                       & k_valid[:, None, :])
            chosen = chosen_mask(scores, visible, topk)
        with jax.named_scope(SCOPE_DSA_ATTN):
            qg = qt.reshape(B, c, Hkv, G, D)

            def kv_tile(state, j):
                m, l, acc = state
                ks = jax.lax.dynamic_slice_in_dim(k, j * kc, kc, 1)
                vs = jax.lax.dynamic_slice_in_dim(v, j * kc, kc, 1)
                ch = jax.lax.dynamic_slice_in_dim(chosen, j * kc, kc, 2)
                s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ks,
                               preferred_element_type=jnp.float32) * scale
                s = jnp.where(ch[:, None, None], s, _NEG)
                m_new = jnp.maximum(m, s.max(-1))
                # a row that has seen nothing yet keeps a finite pivot
                pivot = jnp.where(m_new == _NEG, 0.0, m_new)
                p = jnp.exp(s - pivot[..., None])
                corr = jnp.exp(m - pivot)
                l = l * corr + p.sum(-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bhgqk,bkhd->bhgqd", p.astype(dtype), vs,
                    preferred_element_type=jnp.float32)
                return (m_new, l, acc), None

            init = (jnp.full((B, Hkv, G, c), _NEG, jnp.float32),
                    jnp.zeros((B, Hkv, G, c), jnp.float32),
                    jnp.zeros((B, Hkv, G, c, D), jnp.float32))
            (_, l, acc), _ = jax.lax.scan(kv_tile, init, jnp.arange(n_k))
            y = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
            y = jnp.moveaxis(y, 3, 1).reshape(B, c, H, D).astype(dtype)
        return None, (y, chosen if keep_mask else None)

    _, (y, chosen) = jax.lax.scan(
        q_tile, None, tuple(tiles(t) for t in (q, q_idx, w, q_pos)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, n_q * c, H, D)[:, :T]
    if keep_mask:
        chosen = jnp.moveaxis(chosen, 0, 1).reshape(B, n_q * c, Sp)[:, :T, :S]
    return y, chosen
