"""Parameters, operations and bytes of a hybrid block (a Mamba-2 mixer
beside attention, then a gated MLP) from shapes alone; kept with the
benchmark like ``flops.py`` and ``moe_flops.py``. Every function takes
sizes, never a configuration's name."""


def mixer_params(hidden, d_ssm, n_groups, d_state, n_heads, d_conv):
    """Parameters of one mixer: the input projection to ``[z | x | B | C |
    dt]``, the depthwise convolution over ``x B C`` with its bias, ``A``,
    ``D`` and ``dt``'s bias per head, the grouped norm's weight, the output
    projection. No bias on a projection."""
    conv_dim = d_ssm + 2 * n_groups * d_state
    in_proj = hidden * (d_ssm + conv_dim + n_heads)
    return (in_proj + (d_conv + 1) * conv_dim + 3 * n_heads + d_ssm
            + d_ssm * hidden)


def attention_params(hidden, n_heads, n_kv_heads, head_dim):
    """q, k, v and the output projection of grouped-query attention whose
    heads need not tile the hidden size."""
    return hidden * (n_heads + 2 * n_kv_heads) * head_dim \
        + n_heads * head_dim * hidden


def gated_mlp_params(hidden, width):
    return 3 * hidden * width


def hybrid_layer_params(hidden, width, n_heads, n_kv_heads, head_dim, d_ssm,
                        n_groups, d_state, ssm_heads, d_conv):
    """One block: mixer, attention, MLP and the two norms' weights."""
    return (mixer_params(hidden, d_ssm, n_groups, d_state, ssm_heads, d_conv)
            + attention_params(hidden, n_heads, n_kv_heads, head_dim)
            + gated_mlp_params(hidden, width) + 2 * hidden)


def hybrid_params(n_layer, vocab, hidden, **layer):
    """The whole model with an untied head and the final norm."""
    return n_layer * hybrid_layer_params(hidden, **layer) \
        + 2 * vocab * hidden + hidden


def decode_weight_bytes(n_layer, vocab, hidden, itemsize=2, **layer):
    """Bytes of the parameters one decode step reads: every layer and the
    head once, and of the embedding one row a lane (left out: kilobytes)."""
    return float((n_layer * hybrid_layer_params(hidden, **layer)
                  + vocab * hidden + hidden) * itemsize)


def state_bytes(n_heads, d_head, d_state, itemsize=4):
    """One lane's recurrent state in one layer."""
    return float(n_heads * d_head * d_state * itemsize)


def conv_tail_bytes(d_ssm, n_groups, d_state, d_conv, itemsize=2):
    """One lane's convolution tail in one layer."""
    return float((d_conv - 1) * (d_ssm + 2 * n_groups * d_state) * itemsize)


def kv_bytes_per_position(n_layer, n_kv_heads, head_dim, itemsize=2):
    """Keys and values one cached position of one lane holds."""
    return float(2 * n_layer * n_kv_heads * head_dim * itemsize)


def scan_step_bytes(lanes, n_heads, d_head, d_state, n_groups,
                    state_itemsize=4):
    """Least HBM bytes of one layer's recurrence for one token of ``lanes``
    lanes: every lane's state read and written once, and x, y (float32
    ``[heads, d_head]``), B, C (float32 ``[groups, d_state]``) and dt
    (float32 ``[heads]``) of each lane, which are small beside it."""
    small = 4 * (2 * n_heads * d_head + 2 * n_groups * d_state + n_heads)
    return float(lanes * (2 * state_bytes(n_heads, d_head, d_state,
                                          state_itemsize) + small))


def scan_step_flops(lanes, n_heads, d_head, d_state):
    """Operations of the same: decay, outer product, add, and the
    contraction with C, one multiply and one add each per state element."""
    return float(lanes * 5 * n_heads * d_head * d_state)
