"""Parameters, operations and bytes of an attention-free block (power
retention at degree 2 in place of attention, then a gated MLP) from shapes
alone; kept with the benchmark like ``flops.py`` and ``ssm_flops.py``.
Every function takes sizes, never a configuration's name."""


def sym_dim(head_dim):
    """Entries of the symmetric square of a ``head_dim``-vector."""
    return head_dim * (head_dim + 1) // 2


def retention_params(hidden, n_heads, n_kv_heads, head_dim):
    """q, k, v and the output projection without bias, the gate's kernel
    and bias (one value per KV head), q's and k's per-head norm weight."""
    return (hidden * (n_heads + 2 * n_kv_heads) * head_dim
            + n_heads * head_dim * hidden
            + hidden * n_kv_heads + n_kv_heads + 2 * head_dim)


def gated_mlp_params(hidden, width):
    return 3 * hidden * width


def layer_params(hidden, width, n_heads, n_kv_heads, head_dim):
    """One block: mixer, MLP and the two norms' weights."""
    return (retention_params(hidden, n_heads, n_kv_heads, head_dim)
            + gated_mlp_params(hidden, width) + 2 * hidden)


def model_params(n_layer, vocab, hidden, **layer):
    """The whole model with an untied head and the final norm."""
    return n_layer * layer_params(hidden, **layer) + 2 * vocab * hidden \
        + hidden


def decode_weight_bytes(n_layer, vocab, hidden, itemsize=2, **layer):
    """Bytes of the parameters one decode step reads: every layer and the
    head once, and of the embedding one row a lane (left out: kilobytes)."""
    return float((n_layer * layer_params(hidden, **layer)
                  + vocab * hidden + hidden) * itemsize)


def state_bytes(n_kv_heads, head_dim, itemsize=4):
    """One lane's state ``S`` in one layer, at the symmetric width."""
    return float(n_kv_heads * sym_dim(head_dim) * head_dim * itemsize)


def norm_bytes(n_kv_heads, head_dim, itemsize=4):
    """One lane's normaliser ``z`` in one layer."""
    return float(n_kv_heads * sym_dim(head_dim) * itemsize)


def step_bytes(lanes, n_heads, n_kv_heads, head_dim, state_itemsize=4):
    """Least HBM bytes of one layer's recurrence for one token of ``lanes``
    lanes: every lane's ``S`` and ``z`` read and written once, at the
    symmetric width whatever layout a program stores, and q, y (float32
    ``[heads, head_dim]``), k, v (``[kv heads, head_dim]``) and the gate
    (``[kv heads]``) of each lane, which are small beside it."""
    small = 4 * (2 * n_heads * head_dim + 2 * n_kv_heads * head_dim
                 + n_kv_heads)
    return float(lanes * (2 * (state_bytes(n_kv_heads, head_dim,
                                           state_itemsize)
                               + norm_bytes(n_kv_heads, head_dim,
                                            state_itemsize)) + small))


def step_flops(lanes, n_heads, n_kv_heads, head_dim):
    """Operations of the same, per element of ``S``: the decay, the outer
    product and the add (3), and for each query head that reads the KV
    head a multiply and an add."""
    group = n_heads // n_kv_heads
    return float(lanes * n_kv_heads * sym_dim(head_dim) * head_dim
                 * (3 + 2 * group))
