"""Parameters and bytes of a decoder whose layers are of two kinds, gated
short convolutions and grouped-query attention, with a dense MLP in its
leading layers and an expert layer held whole in the others (LFM2-MoE's
stack), from shapes alone; kept with the benchmark like ``flops.py`` and
``mla_flops.py``. Every function takes sizes, never a configuration's
name, and counts what the equations need, not what an implementation
does."""
from perfbench import mla_flops


def conv_layer_params(hidden):
    """The input projection to ``[B | C | z]`` and the output projection
    (the ``taps x hidden`` kernel apart: kilobytes)."""
    return 3 * hidden * hidden + hidden * hidden


def attention_layer_params(hidden, n_heads, n_kv_heads, head_dim):
    """q, k, v and o without bias and the two per-head norm weights."""
    return (hidden * (n_heads + 2 * n_kv_heads) * head_dim
            + n_heads * head_dim * hidden + 2 * head_dim)


def decode_weight_bytes(kinds, n_dense, vocab, hidden, dense_width,
                        expert_width, n_experts, taps, n_heads, n_kv_heads,
                        head_dim, itemsize=2):
    """Bytes of the parameters one decode step reads: every layer's mixer
    (by its kind: ``"conv"`` or anything else for attention) and two norms,
    the leading dense MLPs, ALL the experts' matrices of every other layer
    (at hundreds of lanes every expert is chosen by some token), the
    float32 routers and their biases, the final norm and the tied head
    once; of the embedding one row a lane (left out: kilobytes)."""
    n = vocab * hidden + hidden
    routers = 0
    for layer, kind in enumerate(kinds):
        n += 2 * hidden + (
            conv_layer_params(hidden) + taps * hidden if kind == "conv"
            else attention_layer_params(hidden, n_heads, n_kv_heads,
                                        head_dim))
        if layer < n_dense:
            n += mla_flops.gated_mlp_params(hidden, dense_width)
        else:
            n += n_experts * mla_flops.gated_mlp_params(hidden, expert_width)
            routers += hidden * n_experts + n_experts
    return float(n * itemsize + routers * 4)


def kv_bytes_per_position(attention_layers, n_kv_heads, head_dim,
                          itemsize=2):
    """Bytes one cached position of one lane holds: a key and a value a KV
    head in each ATTENTION layer; the convolution layers keep nothing per
    position."""
    return float(attention_layers * 2 * n_kv_heads * head_dim * itemsize)


def conv_tail_bytes(conv_layers, hidden, taps, itemsize=2):
    """Bytes of what a lane keeps of its convolutions: the last ``taps -
    1`` gated inputs of each convolution layer."""
    return float(conv_layers * (taps - 1) * hidden * itemsize)


def experts_step(rows, hidden, expert_width, n_experts, itemsize=2):
    """``{"flops", "bytes"}`` of one expert layer's grouped matmuls on a
    decode step of ``rows`` (token, expert) pairs with every expert held:
    ``mla_flops.held_experts_step``'s rule."""
    return mla_flops.held_experts_step(rows, hidden, expert_width,
                                       n_experts, itemsize)
