"""Parameters and bytes of a decoder whose attention layers are of two
kinds, a window of positions with rotary and every position without, with a
gated attention output, four norms a layer, a dense MLP in its leading
layers and in the others a held share of sigmoid-routed experts beside a
shared one (AFMoE / Trinity's stack), from shapes alone; kept with the
benchmark like ``flops.py`` and ``mla_flops.py``. Every function takes
sizes, never a configuration's name, and counts what the equations need,
not what an implementation does."""
from perfbench import mla_flops


def attention_layer_params(hidden, n_heads, n_kv_heads, head_dim):
    """q, k, v, the output's gate and o without bias and the two per-head
    norm weights."""
    return (hidden * (2 * n_heads + 2 * n_kv_heads) * head_dim
            + n_heads * head_dim * hidden + 2 * head_dim)


def decode_weight_bytes(n_layers, n_dense, vocab, hidden, dense_width,
                        expert_width, experts_read, n_shared, n_routed,
                        n_heads, n_kv_heads, head_dim, itemsize=2):
    """Bytes of the parameters one decode step reads: every layer's
    attention and four norms, the leading dense MLPs, in every other layer
    the shared expert and ``experts_read`` routed experts' three matrices
    (the held experts that got a row: at a few rows a step not all do, and
    a grouped matmul need not read the others), the float32 routers and
    their biases, the final norm and the head once; of the embedding one
    row a lane (left out: kilobytes)."""
    moe_layers = n_layers - n_dense
    n = (n_layers * (attention_layer_params(hidden, n_heads, n_kv_heads,
                                            head_dim) + 4 * hidden)
         + n_dense * mla_flops.gated_mlp_params(hidden, dense_width)
         + moe_layers * (experts_read + n_shared)
         * mla_flops.gated_mlp_params(hidden, expert_width)
         + vocab * hidden + hidden)
    return float(n * itemsize + moe_layers * (hidden + 1) * n_routed * 4)


def kv_bytes_per_position(n_kv_heads, head_dim, itemsize=2):
    """Bytes one cached position of one lane holds in ONE layer: a key and
    a value a KV head."""
    return float(2 * n_kv_heads * head_dim * itemsize)


def lane_cache_bytes(window_layers, full_layers, ring, positions,
                     n_kv_heads, head_dim, itemsize=2):
    """``(window bytes, full bytes)`` of one lane's keys and values: a ring
    of ``ring`` rows in each window layer, ``positions`` rows in each
    layer that sees everything."""
    row = kv_bytes_per_position(n_kv_heads, head_dim, itemsize)
    return window_layers * ring * row, full_layers * positions * row


def attention_step(lanes, positions, n_heads, n_kv_heads, head_dim,
                   itemsize=2):
    """``{"flops", "bytes"}`` of ONE layer's attention on a decode step of
    ``lanes`` query tokens over ``positions`` visible cached positions in
    all (the lanes' sum: a window layer's ``min(context, window)``, a full
    layer's context): a score and a weighted sum a position and query
    head; every visible key and value read once, q, the gate and the
    output (``[lanes, heads, head_dim]`` each) small beside them."""
    return {
        "flops": 4.0 * positions * n_heads * head_dim,
        "bytes": float(itemsize * (2 * positions * n_kv_heads * head_dim
                                   + 3 * lanes * n_heads * head_dim))}


def decode_step_bytes(weight_bytes, window_positions, full_positions,
                      window_layers, full_layers, n_kv_heads, head_dim,
                      itemsize=2):
    """Bytes the whole decode step needs: the weights once, and each
    kind's visible positions (all lanes) in each of its layers."""
    row = kv_bytes_per_position(n_kv_heads, head_dim, itemsize)
    return float(weight_bytes + row * (window_layers * window_positions
                                       + full_layers * full_positions))
