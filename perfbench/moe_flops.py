"""Operations and bytes of a sparse (mixture-of-experts) model, from shapes
alone; kept with the benchmark like ``flops.py``, which counts dense models.
Recomputed operations are never counted."""


def moe_params_active(n_layer, hidden, expert_width, experts, top_k):
    """Parameters that take part in a matmul for ONE token: per layer q, k,
    v and the attention output (4 C^2, no biases), the router (C * E) and
    the gate, up and down projections of the ``top_k`` experts the token is
    routed to (3 * C * F each). The other experts' weights do no work for
    this token; norms are not matmuls."""
    c, f = hidden, expert_width
    return n_layer * (4 * c * c + c * experts + top_k * 3 * c * f)


def moe_train_flops_per_token(n_layer, hidden, expert_width, experts, top_k,
                              vocab_size, seq):
    """Forward + backward FLOPs per token of a causal MoE decoder with an
    untied head: 6 per active matmul parameter, the causal attention term
    6 * L * C * seq and the head's 6 * V * C (``flops.
    gpt_train_flops_per_token`` with the active experts for the MLP)."""
    return (6.0 * moe_params_active(n_layer, hidden, expert_width, experts,
                                    top_k)
            + 6.0 * n_layer * hidden * seq + 6.0 * vocab_size * hidden)


def grouped_matmul_flops(rows, d_in, d_out):
    """One grouped matmul over ``rows`` rows sorted by group, each against
    its group's [d_in, d_out] matrix: 2 * rows * d_in * d_out, however the
    rows fall into groups. The backward passes have the same count: for the
    rows' gradient the matrices are transposed, for the matrices' gradient
    the rows are contracted."""
    return 2.0 * rows * d_in * d_out


def grouped_matmul_bytes(rows, d_in, d_out, groups, itemsize=2):
    """Least HBM bytes of one grouped matmul, forward or backward: the rows
    on both sides once and every group's matrix once."""
    return float((rows * (d_in + d_out) + groups * d_in * d_out) * itemsize)
