"""Attention over a chosen few of the cached positions (a lightning
indexer, after DeepSeek-V3.2-Exp's sparse attention; ``GPTConfig.indexer``).

A small indexer scores every cached position for a query,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)

with ``qI`` a few small heads, ``kI`` ONE small key a position (the third
leaf a lane keeps beside keys and values) and ``w`` a weight a head; the
``topk`` positions ``s <= t`` of largest ``I`` are chosen (all of them while
there are no more than ``topk``; ties to the lower position, ``lax.top_k``'s
rule) and the query attends, per head, over those rows alone.

The same sums in the forms below, chosen by the caller from what its call
shows:

* :func:`decode_step`: ONE query token a lane over a cache. The scores run
  over a layer's index keys; the set is ``lax.top_k``'s to the row and
  nothing is sorted (:func:`chosen_set`: the ``topk``-th largest score by a
  search over the scores' bits, one compare-and-count a bit, then the ties
  by the same search over the positions' bits; :func:`rows_of`: the set's
  positions in ascending order, by two small matrix products over chunks
  of the mask; the v5e took ``top_k`` as a full sort of every position
  with its index, four times the time: PERF.md, PR 51); and the rows' keys
  and values reach the softmax out of the stacked leaves where they lie (no
  layer's slice of keys or values is made) by one of two fetches, both
  exact, so that cost alone decides, each step, from the lanes' clocks
  (:func:`reads_blocks`):

  - *rows* (:func:`attend_chosen_rows`): two XLA gathers of the chosen rows
    and two einsums. The cost is the chosen rows', whatever a lane holds;
  - *blocks* (:func:`attend_chosen_blocks`): the decode kernel of the dense
    path (ops/pallas/decode_attention.py, as it stands) with ``visible &
    chosen`` in the place of ``valid``: each lane's blocks from its first
    chosen row to its clock, streamed near the memory's bandwidth and
    masked down to the chosen set. The cost is the LIVE positions', an
    eighth of a gathered row's each, so it wins while a step's live
    contexts are within that ratio of what it chooses;
* :func:`attend_tiled`: many query tokens (a prefill, a continuation, a
  pass without a cache), a tile of ``q_chunk`` queries at a time: the
  tile's scores over all keys, the mask of its chosen positions, then an
  online softmax over key tiles of ``kv_chunk``. No ``[heads, T, T]``
  array exists; the largest is a tile's ``[B, index heads, q_chunk, S]``
  float32 dots.
"""
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.pallas.decode_attention import (
    block_positions,
    decode_attention,
    live_blocks,
)
from deepspeed_tpu.telemetry.scopes import (
    SCOPE_DSA_ATTN,
    SCOPE_DSA_INDEX_SCORES,
    SCOPE_DSA_SELECT,
    SCOPE_LATENT_INDEX,
    SCOPE_LATENT_SELECT,
    SCOPE_SPARSE_LATENT_ATTN,
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


def _order_keys(masked):
    """``uint32`` whose order is the float32s' total order, as ``lax.top_k``
    ranks them: -0.0 below 0.0 (a relu's weighted sum gives both), ``-inf``
    below every finite score."""
    bits = jax.lax.bitcast_convert_type(masked, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(x, member, need, bits: int):
    """``[B, 1]`` uint32: the largest ``t < 2**bits`` that at least ``need
    [B, 1]`` of the ``member`` s (None: all) of ``x [B, S]`` uint32 reach
    (``x >= t``), which is the ``need``-th largest of them where there are
    that many and ``need > 0``. A search over ``t``'s bits from the top,
    one compare-and-count over ``x`` a bit: nothing is ordered."""
    def narrow(i, t):
        probe = t | (jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        reach = x >= probe
        if member is not None:
            reach &= member
        count = jnp.sum(reach, axis=-1, keepdims=True, dtype=jnp.int32)
        return jnp.where(count >= need, probe, t)

    return jax.lax.fori_loop(
        0, bits, narrow, jnp.zeros(x.shape[:-1] + (1,), jnp.uint32))


def chosen_set(scores, visible, topk: int):
    """``[B, S]`` bool: the ``topk`` best visible positions of a lane (all
    it sees where those are fewer), the set ``lax.top_k`` of the masked
    scores would give to the row, with no sort: the ``topk``-th largest
    score by :func:`_kth_largest` over the scores' 32 bits, everything above
    it, and of the positions that tie with it the lowest, as many as are
    left (``top_k``'s rule), by the same search over the positions' bits."""
    S = scores.shape[-1]
    masked = jnp.where(visible, scores, _NEG)
    seen = masked > _NEG
    if S <= topk:
        return seen
    key = _order_keys(masked)
    kth = _kth_largest(key, None, topk, 32)
    above, ties = key > kth, key == kth
    left = topk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    # the lower the position the larger ``early``; it never has every bit
    # set, so that a lane with nothing left takes no tie
    early = jnp.broadcast_to(
        (S - 1 - jnp.arange(S, dtype=jnp.int32)).astype(jnp.uint32),
        ties.shape)
    last = _kth_largest(early, ties, left, S.bit_length())
    return (above | (ties & (early >= last))) & seen


# positions a chunk of :func:`rows_of`: the v5e's lane width, and small
# integers up to it are exact in bfloat16
_CHUNK = 128


def rows_of(chosen, count: int):
    """The positions of ``chosen [B, S]`` bool in ascending order: ``(rows
    [B, count] int32, ok [B, count] bool)``, ``ok`` False (and the row 0)
    past a lane's last; ``chosen`` holds no more than ``count`` a lane. No
    sort and no scatter: the mask in chunks of ``_CHUNK`` positions, the
    chunk of output ``j`` from the chunks' running count, and its place in
    the chunk from the chunk's own running count, fetched by a matrix
    product with the steps ``running count <= j`` (the sum of the steps
    times the chunks' differences telescopes to the one chunk; every term
    is a small integer, exact in bfloat16 and in the float32 sums)."""
    B, S = chosen.shape
    n_chunks = -(-S // _CHUNK)
    m = jnp.pad(chosen, ((0, 0), (0, n_chunks * _CHUNK - S))).reshape(
        B, n_chunks, _CHUNK).astype(jnp.bfloat16)
    at = jnp.arange(_CHUNK)
    within = jnp.einsum("bnc,cd->bnd", m, (at[:, None] <= at[None, :]).astype(
        jnp.bfloat16), preferred_element_type=jnp.float32)   # running count
    held = within[..., -1].astype(jnp.int32)                  # [B, n_chunks]
    upto = jnp.cumsum(held, axis=-1)            # held by a chunk and before
    j = jnp.arange(count, dtype=jnp.int32)[None, :, None]
    step = upto[:, None, :] <= j                # [B, count, n]: chunks past
    chunk = jnp.sum(step, axis=-1, dtype=jnp.int32)           # [B, count]
    rank = j[..., 0] - jnp.sum(jnp.where(step, held[:, None, :], 0), axis=-1)
    # the running count of output j's chunk: the first chunk's plus the
    # differences of every chunk before its own
    diff = jnp.concatenate([within[:, 1:] - within[:, :-1],
                            -within[:, -1:]], axis=1).astype(jnp.bfloat16)
    mine = within[:, :1] + jnp.einsum(
        "bjn,bnc->bjc", step.astype(jnp.bfloat16), diff,
        preferred_element_type=jnp.float32)                   # [B, count, C]
    place = jnp.sum(mine <= rank[..., None].astype(jnp.float32), axis=-1,
                    dtype=jnp.int32)
    ok = j[..., 0] < upto[:, -1:]
    return jnp.where(ok, chunk * _CHUNK + place, 0), ok


def choose(scores, visible, topk: int):
    """The rows a query attends over: ``(rows [B, K] int32, ok [B, K]
    bool)`` with ``K = min(topk, S)``: :func:`chosen_set`'s positions in
    ascending order; ``ok`` is False where fewer than ``K`` positions are
    visible (such a row is nobody's)."""
    return rows_of(chosen_set(scores, visible, topk),
                   min(topk, scores.shape[-1]))


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


# What the two forms cost on the v5e, in nanoseconds: a position in the
# blocks the kernel reads (the mask's making included), and a chosen
# position by the gathers (its row of keys, its row of values, the einsums
# over them). Each form alone at the selected-attention cell's shape (32
# lanes, 4 KV heads of 128 in bf16, 2,048 chosen of 24,576, blocks of 512),
# chip_smoke.py's sweep (``chiprun_out/p49a/selected.json``; PERF.md, PR
# 49): blocks 0.598 / 1.135 / 2.197 ms with every lane holding 6,144 /
# 12,288 / 24,576 live positions = 3.04 / 2.89 / 2.79 ns a position, and
# 1.219 ms at 16 lanes of 2,048 beside 16 of 24,576 = 2.86; rows 1.610-1.615
# ms whatever is live = 24.6 ns a chosen position. The two cost the same at
# 8.5 live positions a chosen one (~17.4k a lane here)
_NS_A_BLOCK_POSITION = 2.9
_NS_A_CHOSEN_ROW = 24.6


def reads_blocks(first, clock, block: int, chosen: int):
    """Whether a decode step reads the lanes' live blocks under the chosen
    mask (True) or gathers the chosen rows (False): both give the same
    sums, so the cheaper. The kernel reads each lane's blocks from its
    first visible row ``first`` to its query's row ``clock`` (``[B]`` each;
    ``live_blocks``, a lane with nothing still one); the gathers fetch
    ``chosen`` rows a lane whatever it holds (their shape is static). Both
    costs are linear in the lanes' totals, so one predicate a step."""
    lo, hi = live_blocks(first, clock, block)
    return jnp.sum(hi - lo + 1) * (block * _NS_A_BLOCK_POSITION) \
        < first.shape[0] * chosen * _NS_A_CHOSEN_ROW


def attend_chosen_rows(q, keys, values, layer, rows, ok, scale, dtype):
    """One query token a lane over its chosen rows, gathered: ``q [B, H,
    D]``; ``keys`` / ``values`` the stacked ``[L, B, S, Hkv, D]`` leaves
    with ``layer`` this call's index (or one layer's ``[B, S, Hkv, D]``
    with ``layer`` None); ``rows`` / ``ok`` ``[B, K]``. Returns ``[B, H,
    D]``. Query head ``r`` reads KV head ``r // (H / Hkv)``; scores and
    softmax in float32."""
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


def attend_chosen_blocks(q, keys, values, layer, chosen, clock, block,
                         dtype):
    """One query token a lane over its chosen rows, read as blocks: the
    dense path's decode kernel with ``chosen [B, S]`` (:func:`chosen_set`:
    visible and chosen) in the place of ``valid``. It fetches, of each
    lane, the blocks of ``block`` positions between the first chosen row
    and ``clock [B]`` (the query's row; no chosen row lies past it) out of
    the stacked leaves where they lie, and counts the chosen positions
    alone: the same sums as :func:`attend_chosen_rows` over the same set,
    float32 scores under an online softmax, probabilities meeting the
    values in the cache's dtype, the kernel's scale ``1 / sqrt(D)``. A lane
    with nothing chosen gets finite numbers that mean nothing."""
    return decode_attention(q, keys, values, chosen, clock, layer,
                            block=block).astype(dtype)


def decode_step(q, q_idx, w, keys, values, index_keys, layer, visible,
                clock, topk: int, dtype):
    """The whole selection of one decode token a lane: scores over the
    layer's index keys, the choice (as a mask for the blocks and as rows
    for the gathers and the caller), attention over the chosen rows by
    whichever fetch :func:`reads_blocks` finds cheaper for these lanes.
    ``q [B, H, D]``, ``q_idx [B, Hi, Di]``, ``w [B, Hi]``, the three
    stacked leaves, ``visible [B, S]``, ``clock [B]`` the row of each
    lane's query. Returns ``(y [B, H, D], rows, ok)``."""
    B, H, D = q.shape
    S, Hkv = visible.shape[1], keys.shape[-2]
    with jax.named_scope(SCOPE_DSA_INDEX_SCORES):
        k_idx = index_keys if layer is None else \
            jax.lax.dynamic_index_in_dim(index_keys, layer, 0,
                                         keepdims=False)
        scores = index_scores(q_idx[:, None], k_idx, w[:, None])[:, 0]
    with jax.named_scope(SCOPE_DSA_SELECT):
        # one query a lane: scores, the search's passes and every operand
        # of the conditional below are [B, S], lanes along the sublanes (a
        # [B, 1, S] operand is laid out a row a tile, eight times the
        # tiles: PERF.md, PR 48). ``choose`` makes its rows of the same set:
        # the compiler merges the two searches into one (tests/unit/
        # test_grouped_matmul.py counts the compiled program's loops)
        chosen = chosen_set(scores, visible, topk)
        rows, ok = choose(scores, visible, topk)
    with jax.named_scope(SCOPE_DSA_ATTN):
        block = block_positions(S, Hkv, D, keys.dtype.itemsize)
        clock = jnp.minimum(clock, S - 1).astype(jnp.int32)

        # each form hands over [B, H * D], the layout the output projection
        # takes, so that the relayout is the form's own reshape under this
        # scope (one the compiler puts at a conditional's root has no
        # ``op_name``)
        def by_blocks():
            return attend_chosen_blocks(q, keys, values, layer, chosen,
                                        clock, block, dtype).reshape(B, -1)

        def by_rows():
            return attend_chosen_rows(q, keys, values, layer, rows, ok,
                                      1.0 / np.sqrt(D),
                                      dtype).reshape(B, -1)

        y = jax.lax.cond(
            reads_blocks(jnp.argmax(visible, axis=1).astype(jnp.int32),
                         clock, block, min(topk, S)),
            by_blocks, by_rows)
    return y.reshape(B, H, D), rows, ok


def latent_decode_step(q_lat, q_rope, q_idx, w, latent, rope_key,
                       index_keys, layer, visible, topk: int, scale, dtype):
    """:func:`decode_step` over a latent cache: scores over the layer's
    index keys, the choice, and the ABSORBED form of latent attention
    (models/latent_attention.py) over the chosen latents: the latent decode
    kernel over the lanes' live blocks under the chosen mask
    (``mask_plan``). One fetch, no rule: on the v5e at 48 lanes, 128 heads
    over 512 + 64 and 2,048 chosen, the kernel cost 0.55-3.12 ms with
    3,072-24,576 live positions a lane where two gathers of the chosen
    rows and the einsums cost 4.8 ms whatever was live (PERF.md, PR 59), so
    no cache the benchmark runs would gather.
    ``q_lat [B, H, r]``, ``q_rope [B, H, dr]``, ``q_idx [B, Hi, Di]``, ``w
    [B, Hi]``, the three stacked leaves, ``visible [B, S]``. Returns
    ``(o_lat [B, H, r], rows, ok)``."""
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda

    S, r = visible.shape[1], latent.shape[-1]
    with jax.named_scope(SCOPE_LATENT_INDEX):
        k_idx = index_keys if layer is None else \
            jax.lax.dynamic_index_in_dim(index_keys, layer, 0,
                                         keepdims=False)
        scores = index_scores(q_idx[:, None], k_idx, w[:, None])[:, 0]
    with jax.named_scope(SCOPE_LATENT_SELECT):
        chosen = chosen_set(scores, visible, topk)
        rows, ok = choose(scores, visible, topk)
    with jax.named_scope(SCOPE_SPARSE_LATENT_ATTN):
        block = lda.block_positions(S, r, latent.dtype.itemsize)
        o_lat = lda.latent_decode_attention(
            q_lat, q_rope, latent, rope_key, lda.mask_plan(chosen, block),
            layer, scale=scale).astype(dtype)
    return o_lat, rows, ok


def attend_tiled(q, k, v, q_idx, k_idx, w, q_pos, k_valid, topk: int,
                 q_chunk: int, kv_chunk: int, scale, dtype,
                 keep_mask: bool = False, live_tiles=None,
                 scopes=(SCOPE_DSA_INDEX_SCORES, SCOPE_DSA_SELECT,
                         SCOPE_DSA_ATTN)):
    """Many query tokens over ``S`` keys: ``q [B, T, H, D]``, ``k`` / ``v``
    ``[B, S, Hkv, D]``, ``q_idx [B, T, Hi, Di]``, ``k_idx [B, S, Di]``,
    ``w [B, T, Hi]``; ``q_pos [B, T]`` the key row each query sits at (it
    sees the rows ``<= q_pos``), ``k_valid [B, S]`` which rows hold a
    token. Returns ``(y [B, T, H, D], chosen)``: ``chosen [B, T, S]`` bool
    with ``keep_mask`` (tests), else None. A scan over query tiles of
    ``q_chunk``; inside one, the tile's scores over all keys, its chosen
    positions, and an online softmax over key tiles of ``kv_chunk``, whose
    running maximum, sum and weighted values are float32. The values may be
    of another width than the keys (the absorbed form of latent attention:
    one "KV head" whose key is ``[latent | rotary key]`` and whose value is
    the latent). ``live_tiles`` (a scalar, traced or not): walk the first
    ``live_tiles`` key tiles alone, where the caller knows that no query sees a
    row past them (a prefill pass over a long cache); ``scopes``: the
    three scopes the index scores, the choice and the attention are timed
    under."""
    B, T, H, D = q.shape
    S, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    scope_scores, scope_select, scope_attn = scopes
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
        with jax.named_scope(scope_scores):
            scores = index_scores(qit, k_idx, wt)               # [B, c, Sp]
        with jax.named_scope(scope_select):
            visible = ((jnp.arange(Sp)[None, None, :] <= pt[:, :, None])
                       & k_valid[:, None, :])
            chosen = chosen_mask(scores, visible, topk)
        with jax.named_scope(scope_attn):
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
                    jnp.zeros((B, Hkv, G, c, Dv), jnp.float32))
            if live_tiles is None:
                (_, l, acc), _ = jax.lax.scan(kv_tile, init, jnp.arange(n_k))
            else:
                _, l, acc = jax.lax.fori_loop(
                    0, jnp.minimum(live_tiles, n_k),
                    lambda j, state: kv_tile(state, j)[0], init)
            y = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
            y = jnp.moveaxis(y, 3, 1).reshape(B, c, H, Dv).astype(dtype)
        return None, (y, chosen if keep_mask else None)

    _, (y, chosen) = jax.lax.scan(
        q_tile, None, tuple(tiles(t) for t in (q, q_idx, w, q_pos)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, n_q * c, H, Dv)[:, :T]
    if keep_mask:
        chosen = jnp.moveaxis(chosen, 0, 1).reshape(B, n_q * c, Sp)[:, :T, :S]
    return y, chosen
