"""Parameters, operations and bytes of a latent-attention decoder with a
held share of an expert layer (DeepSeek-V2's block) from shapes alone;
kept with the benchmark like ``flops.py`` and ``retention_flops.py``.
Every function takes sizes, never a configuration's name, and counts the
work the equations need, not what an implementation does: a later kernel's
roofline is read by the same functions."""


def attention_params(hidden, n_heads, q_rank, kv_rank, nope, rope, v_dim):
    """``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o`` without bias and
    the two latents' norm weights."""
    return (hidden * q_rank + q_rank * n_heads * (nope + rope)
            + hidden * (kv_rank + rope) + kv_rank * n_heads * (nope + v_dim)
            + n_heads * v_dim * hidden + q_rank + kv_rank)


def gated_mlp_params(hidden, width):
    return 3 * hidden * width


def expert_layer_params(hidden, expert_width, held, n_shared):
    """The matrices of the ``held`` routed experts and of the shared ones
    (the router apart: it is float32)."""
    return (held + n_shared) * gated_mlp_params(hidden, expert_width)


def decode_weight_bytes(n_layer, first_k_dense, vocab, hidden, dense_width,
                        expert_width, held, n_shared, n_routed, itemsize=2,
                        **attn):
    """Bytes of the parameters one decode step reads: every layer's
    attention and norms, the leading dense MLPs, the HELD experts' and the
    shared experts' matrices (with a few rows an expert nearly every held
    expert is touched, so all are counted), the float32 routers, the final
    norm and the head once; of the embedding one row a lane (left out:
    kilobytes)."""
    per_layer = attention_params(hidden, **attn) + 2 * hidden
    moe_layers = n_layer - first_k_dense
    n = (n_layer * per_layer
         + first_k_dense * gated_mlp_params(hidden, dense_width)
         + moe_layers * expert_layer_params(hidden, expert_width, held,
                                            n_shared)
         + vocab * hidden + hidden)
    return float(n * itemsize + moe_layers * hidden * n_routed * 4)


def latent_bytes_per_position(n_layer, kv_rank, rope, itemsize=2):
    """Bytes one cached position of one lane holds over all layers: the
    latent and the rotary key."""
    return float(n_layer * (kv_rank + rope) * itemsize)


def absorbed_attention_flops(positions, n_heads, kv_rank, rope):
    """Operations of one layer's absorbed attention over ``positions``
    cached positions (summed over the lanes), one query token each: per
    position and head a ``kv_rank + rope``-wide score and a
    ``kv_rank``-wide weighted sum."""
    return 2.0 * positions * n_heads * (2 * kv_rank + rope)


def absorb_flops(lanes, n_heads, kv_rank, nope, v_dim):
    """Operations of one layer's products with the decompression matrices,
    one token a lane: the query into the latent space and the output out
    of it."""
    return 2.0 * lanes * n_heads * kv_rank * (nope + v_dim)


def absorbed_step(lanes, positions, n_heads, kv_rank, nope, rope, v_dim,
                  itemsize=2):
    """``{"flops", "bytes"}`` of one layer's latent attention on a decode
    step of ``lanes`` tokens over ``positions`` live cached positions in
    all: the absorb products and the attention over the latents; every
    live latent and rotary key and ``W_kvb`` read once, q and the output
    (``[lanes, heads, nope + rope]``, ``[lanes, heads, v_dim]``) small
    beside them."""
    return {
        "flops": absorbed_attention_flops(positions, n_heads, kv_rank, rope)
        + absorb_flops(lanes, n_heads, kv_rank, nope, v_dim),
        "bytes": float(itemsize * (
            positions * (kv_rank + rope)
            + kv_rank * n_heads * (nope + v_dim)
            + lanes * n_heads * (nope + rope + v_dim)))}


def held_experts_step(rows, hidden, expert_width, held, itemsize=2):
    """``{"flops", "bytes"}`` of one layer's grouped matmuls over the held
    experts on ``rows`` (token, expert) pairs routed to them: three
    products a pair; every held expert's three matrices read once, the
    rows read and written on both sides of each product."""
    return {
        "flops": 6.0 * rows * hidden * expert_width,
        "bytes": float(itemsize * (
            3 * held * hidden * expert_width
            + rows * (2 * hidden + 2 * expert_width)
            + rows * (hidden + expert_width)))}


def prefill_attention_flops(tokens, n_heads, kv_rank, nope, rope, v_dim):
    """Operations of one layer's per-head attention over a prompt of
    ``tokens``: decompression of every token's keys and values, and causal
    scores and weighted sums at half the square."""
    return (2.0 * tokens * kv_rank * n_heads * (nope + v_dim)
            + tokens * tokens * n_heads * (nope + rope + v_dim))
