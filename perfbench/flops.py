"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no change to the program can move them.
Recomputed operations (activation checkpointing) are never counted in a
model's FLOPs; a kernel's own count is per call, however often it is
called.
"""


def gpt_params_matmul(n_layer, n_embd, mlp_ratio=4):
    """Parameters that take part in a matmul per token, without the
    embedding tables: per layer qkv (3C^2), attention out (C^2), MLP
    (2 * r * C^2), with their biases and the two layer norms."""
    c, r = n_embd, mlp_ratio
    per_layer = (3 * c * c + 3 * c) + (c * c + c) \
        + (r * c * c + r * c) + (r * c * c + c) + 4 * c
    return n_layer * per_layer + 2 * c  # + final layer norm


def gpt_train_flops_per_token(n_layer, n_embd, vocab_size, seq, mlp_ratio=4):
    """Forward + backward FLOPs per token of a causal GPT with a tied head.

    6 per matmul parameter (copied from ``benchmarks/_util.
    gpt_flops_per_token``), the causal attention term 6 * L * C * seq
    (QK^T and PV at half the square, forward and twice that backward), and
    the LM head's matmul 6 * V * C, which the repo's count leaves out with
    the embedding although a tied head is a real [C, V] matmul per token.
    """
    return (6.0 * gpt_params_matmul(n_layer, n_embd, mlp_ratio)
            + 6.0 * n_layer * n_embd * seq
            + 6.0 * vocab_size * n_embd)


def bert_train_flops_per_token(n_layer, hidden, intermediate, vocab_size,
                               seq, label_share):
    """Forward + backward FLOPs per token of BERT pre-training.

    Encoder and MLM transform as ``benchmarks/bert_pretrain.run`` counts
    them, bidirectional attention 12 * L * C * seq, and the vocabulary
    decoder only on the labelled share of positions (the rest of that
    matmul is not required work, whatever the program computes).
    """
    c, i = hidden, intermediate
    n_nonembed = n_layer * (4 * c * c + 2 * c * i + 13 * c) + c * c + 3 * c
    return (6.0 * n_nonembed + 12.0 * n_layer * c * seq
            + 6.0 * vocab_size * c * label_share)


# One flash-attention call on [bh, t, d], causal: the block pairs above the
# diagonal are skipped, so every matmul runs over half the square.
FLASH_MATMULS = {
    # forward: S = QK^T, O = PV
    "fwd": 2,
    # backward for dQ: recompute S, dP = dO V^T, dQ = dS K
    "bwd_dq": 3,
    # backward for dK, dV: recompute S, dP = dO V^T, dV = P^T dO, dK = dS^T Q
    "bwd_dkv": 4,
}


def flash_call_flops(kind, bh, t, d, causal=True):
    """FLOPs one flash call of ``kind`` needs: 2 * t * t * d per matmul and
    head, halved when causal."""
    full = 2.0 * bh * t * t * d * FLASH_MATMULS[kind]
    return full / 2.0 if causal else full


def flash_call_bytes(kind, bh, t, d, itemsize=2):
    """Least HBM bytes of one flash call: each operand read once, each
    result written once (q, k, v, o and their gradients; lse/delta rows are
    small and left out)."""
    tensors = {"fwd": 4, "bwd_dq": 6, "bwd_dkv": 7}[kind]
    return float(tensors * bh * t * d * itemsize)


def roofline_seconds(flops, nbytes, peak):
    """Least seconds for ``flops`` and ``nbytes`` on a chip with ``peak``
    (an entry of peaks.json), and which of the two bounds it."""
    t_flops = flops / (peak["bf16_tflops"] * 1e12)
    t_bytes = nbytes / (peak["hbm_gb_per_s"] * 1e9)
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes
                                   else "memory")


def gpt_weight_bytes(n_layer, n_embd, vocab_size, n_positions, itemsize=2,
                     mlp_ratio=4):
    """Bytes of every parameter a decode step reads: the matmul
    parameters, the tied embedding (read as the head) and the positions."""
    n = gpt_params_matmul(n_layer, n_embd, mlp_ratio) \
        + vocab_size * n_embd + n_positions * n_embd
    return float(n * itemsize)


def kv_bytes_per_position(n_layer, n_embd, itemsize=2):
    """Bytes of keys and values one cached position of one lane holds."""
    return float(2 * n_layer * n_embd * itemsize)
