"""Parameters, operations and bytes of a decoder whose attention layers are
LATENT attention of two kinds side by side (a full kind read through a
lightning indexer's choice of rows, a window kind over a ring of latents,
each with its own heads, ranks and widths and a gate a head), a dense MLP
in its leading layers and in the others a held share of sigmoid-routed
experts beside a shared one (dots3's stack), from shapes alone; kept with
the benchmark like ``mla_flops.py`` and ``dsa_flops.py``. Every function
takes sizes, never a configuration's name, and counts what the equations
need, not what an implementation does."""
from perfbench import mla_flops


def indexer_params(hidden, q_rank, ix_heads, ix_dim):
    """``W_Iq`` (from the query latent), ``W_Ik`` with its LayerNorm's scale
    and bias, ``W_Iw``."""
    return (q_rank * ix_heads * ix_dim + hidden * ix_dim + 2 * ix_dim
            + hidden * ix_heads)


def attention_params(hidden, n_heads, q_rank, kv_rank, nope, rope, v_dim,
                     ix_heads=0, ix_dim=0):
    """One layer's latent attention (``mla_flops.attention_params``), the
    gate a head, and the indexer where the kind has one."""
    return (mla_flops.attention_params(hidden, n_heads, q_rank, kv_rank,
                                       nope, rope, v_dim)
            + hidden * n_heads
            + (indexer_params(hidden, q_rank, ix_heads, ix_dim)
               if ix_heads else 0))


def layer_params(hidden, kind, dense_width=0, expert_width=0, held=0,
                 n_shared=0, n_routed=0):
    """One block: its kind's attention, two norms, and a dense MLP
    (``dense_width``) or the held and shared experts with the router and
    its bias."""
    n = attention_params(hidden, **kind) + 2 * hidden
    if dense_width:
        return n + mla_flops.gated_mlp_params(hidden, dense_width)
    return (n + mla_flops.expert_layer_params(hidden, expert_width, held,
                                              n_shared)
            + (hidden + 1) * n_routed)


def decode_weight_bytes(kinds, n_dense, vocab, hidden, dense_width,
                        expert_width, experts_read, n_shared, n_routed,
                        itemsize=2):
    """Bytes of the parameters one decode step reads: every layer's
    attention of its kind (``kinds``: an ``attention_params`` keyword dict a
    layer) and two norms, the leading dense MLPs, in every other layer the
    shared expert and ``experts_read`` routed experts' three matrices, the
    float32 routers and their biases, the final norm and the head once; of
    the embedding one row a lane (left out: kilobytes)."""
    moe_layers = len(kinds) - n_dense
    n = (sum(attention_params(hidden, **kind) + 2 * hidden
             for kind in kinds)
         + n_dense * mla_flops.gated_mlp_params(hidden, dense_width)
         + moe_layers * (experts_read + n_shared)
         * mla_flops.gated_mlp_params(hidden, expert_width)
         + vocab * hidden + hidden)
    return float(n * itemsize + moe_layers * (hidden + 1) * n_routed * 4)


def latent_row_bytes(kv_rank, rope, itemsize=2):
    """Bytes one cached position of one lane holds in ONE layer: the
    latent and the rotary key."""
    return float((kv_rank + rope) * itemsize)


def lane_cache_bytes(window_layers, full_layers, ring, positions, window,
                     full, ix_dim, itemsize=2):
    """``(ring bytes, dense latent bytes, index key bytes)`` of one lane:
    a ring of ``ring`` rows of the window kind's latent and rotary key in
    each window layer; ``positions`` rows of the full kind's and of the
    index key in each full layer."""
    return (window_layers * ring * latent_row_bytes(
                window["kv_rank"], window["rope"], itemsize),
            full_layers * positions * latent_row_bytes(
                full["kv_rank"], full["rope"], itemsize),
            float(full_layers * positions * ix_dim * itemsize))


def absorbed_attention_step(lanes, positions, n_heads, kv_rank, rope,
                            itemsize=2):
    """``{"flops", "bytes"}`` of ONE layer's absorbed attention on a decode
    step of ``lanes`` query tokens over ``positions`` rows in all (the
    lanes' sum: a window layer's ``min(context, window)``; a full layer's
    chosen rows, or its live rows where the step reads blocks under the
    chosen mask): per row and head a ``kv_rank + rope``-wide score and a
    ``kv_rank``-wide weighted sum; every such latent and rotary key read
    once (the block fetched for the scores is the value), the absorbed
    queries in and the latent outputs out. The products with ``W_kvb`` are
    ``mla_absorb``'s and not counted here."""
    return {
        "flops": mla_flops.absorbed_attention_flops(positions, n_heads,
                                                    kv_rank, rope),
        "bytes": float(itemsize * (positions * (kv_rank + rope)
                                   + lanes * n_heads * (2 * kv_rank + rope)))}


def index_step(lanes, positions, q_rank, hidden, ix_heads, ix_dim,
               itemsize=2):
    """``{"flops", "bytes"}`` of ONE layer's indexer on a decode step: its
    three projections of one token a lane and a dot and a weighted ReLU a
    head and live position; every live index key read once."""
    return {
        "flops": 2.0 * lanes * (q_rank * ix_heads * ix_dim
                                + hidden * (ix_dim + ix_heads))
        + positions * ix_heads * (2.0 * ix_dim + 2.0),
        "bytes": float(itemsize * (
            positions * ix_dim + indexer_params(hidden, q_rank, ix_heads,
                                                ix_dim)))}


def decode_step(weight_bytes, lanes, window_positions, live_positions,
                chosen_positions, window_layers, full_layers, window, full,
                ix_heads, ix_dim, itemsize=2):
    """``{"flops", "bytes"}`` the whole decode step needs: the weights
    once (two operations a parameter byte pair and lane left out: the step
    is bound by its bytes there); in each window layer the lanes' ``min(
    context, window)`` latents and rotary keys; in each full layer every
    LIVE index key and the CHOSEN latents and rotary keys; the operations
    of both absorbed forms over those rows and of the indexer's scores."""
    w = absorbed_attention_step(lanes, window_positions, itemsize=itemsize,
                                **window)
    f = absorbed_attention_step(lanes, chosen_positions, itemsize=itemsize,
                                **full)
    scores = live_positions * ix_heads * (2.0 * ix_dim + 2.0)
    return {
        "flops": window_layers * w["flops"]
        + full_layers * (f["flops"] + scores),
        "bytes": float(weight_bytes + window_layers * w["bytes"]
                       + full_layers * (f["bytes"] + itemsize * ix_dim
                                        * live_positions))}
