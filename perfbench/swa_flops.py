"""Operations and bytes of attention under a sliding window, and of a
model whose layers mix window and full attention over grouped queries with
experts of which a chip holds a share, from shapes alone; kept with the
benchmark like ``flops.py``. Recomputed operations are never counted.

A window flash call on ``[bh, t, d]`` queries with a window of ``w``
positions needs, of the ``t x t`` scores, the pairs ``0 <= i - j < w``:
``w * t - w * (w - 1) / 2`` of them (``t * (t + 1) / 2``, the causal
triangle, at ``w >= t``)."""
from perfbench import flops


def window_pairs(t, window):
    """(query, key) pairs of one head under the causal mask and a window."""
    w = min(window, t)
    return w * t - w * (w - 1) / 2.0


def window_flash_call_flops(kind, bh, t, d, window):
    """FLOPs one window flash call of ``kind`` (``flops.FLASH_MATMULS``'s
    names) needs: 2 * d a pair and matmul."""
    return 2.0 * bh * window_pairs(t, window) * d * flops.FLASH_MATMULS[kind]


def window_flash_call_bytes(kind, bh, kv_heads, t, d, itemsize=2):
    """Least HBM bytes of one window flash call: each operand read once,
    each result written once; q, o and their gradients at ``bh`` heads, K
    and V at their own ``kv_heads`` (the backward for dK and dV writes a
    QUERY head's each: the group's sum is another operation; lse and delta
    rows are small and left out). The band a strip holds is read again by
    the next strip: the kernel's traffic, not the algorithm's."""
    q_like = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}[kind]
    return float((q_like * bh + 2 * kv_heads) * t * d * itemsize)


def attention_pairs_per_token(layer_types, seq, window):
    """Mean (query, key) pairs a token and head, summed over the layers."""
    return sum(window_pairs(seq, window) if kind == "window"
               else window_pairs(seq, seq) for kind in layer_types) / seq


def train_flops_per_token(layer_types, hidden, heads, kv_heads, head_dim,
                          expert_width, experts_scored, experts_a_token,
                          vocab_size, seq, window):
    """Forward + backward FLOPs per token of what THIS chip computes: 6 per
    matmul parameter a token meets (q, k, v and the output projection at
    their grouped widths, the router over all ``experts_scored``, gate, up
    and down of the ``experts_a_token`` experts a token meets HERE, an
    expectation: top-k times the held share, what a balanced router sends;
    the cell's routed-here share says what the seeded one sent), the
    attention scores and values at each layer's own pairs (12 * head_dim a
    pair and head: 4 forward, 8 backward), and the head over the
    vocabulary slice it holds."""
    qkvo = hidden * head_dim * (2 * heads + 2 * kv_heads)
    layer = qkvo + hidden * experts_scored \
        + experts_a_token * 3 * hidden * expert_width
    return (6.0 * len(layer_types) * layer
            + 12.0 * heads * head_dim
            * attention_pairs_per_token(layer_types, seq, window)
            + 6.0 * vocab_size * hidden)
