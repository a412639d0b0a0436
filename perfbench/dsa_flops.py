"""Parameters, operations and bytes of a grouped-query decoder whose
attention runs over the positions a lightning indexer chooses, with a held
share of an expert layer (Keye-VL-2.0's block), from shapes alone; kept
with the benchmark like ``flops.py`` and ``mla_flops.py``. Every function
takes sizes, never a configuration's name, and counts the work the
MECHANISM needs, not what an implementation does: the indexer reads each
live position's one index key once a lane and layer, and attention reads
the chosen rows of keys and of values once. An implementation that reads
more (a gather that fetches whole tiles, a dense pass under a mask) shows
as a lower share of its roofline, and nothing can read over 100%."""


def attention_params(hidden, n_heads, n_kv_heads, head_dim):
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` without bias and the two
    per-head norm weights."""
    return (hidden * (n_heads + 2 * n_kv_heads) * head_dim
            + n_heads * head_dim * hidden + 2 * head_dim)


def indexer_params(hidden, ix_heads, ix_dim):
    """``W_qI``, ``W_kI``, ``W_w`` and the index key's LayerNorm."""
    return hidden * (ix_heads * ix_dim + ix_dim + ix_heads) + 2 * ix_dim


def decode_weight_bytes(n_layer, vocab, hidden, expert_width, held, n_routed,
                        n_heads, n_kv_heads, head_dim, ix_heads, ix_dim,
                        itemsize=2):
    """Bytes of the parameters one decode step reads: every layer's
    attention, indexer and norms, the HELD experts' matrices (with a few
    rows an expert nearly every held expert is touched, so all are
    counted), the float32 routers, the final norm and the head once; of
    the embedding one row a lane (left out: kilobytes)."""
    per_layer = (attention_params(hidden, n_heads, n_kv_heads, head_dim)
                 + indexer_params(hidden, ix_heads, ix_dim) + 2 * hidden
                 + held * 3 * hidden * expert_width)
    n = n_layer * per_layer + vocab * hidden + hidden
    return float(n * itemsize + n_layer * hidden * n_routed * 4)


def kv_bytes_per_position(n_layer, n_kv_heads, head_dim, itemsize=2):
    """Bytes of one cached position's keys and values over all layers."""
    return float(n_layer * 2 * n_kv_heads * head_dim * itemsize)


def index_key_bytes_per_position(n_layer, ix_dim, itemsize=2):
    """Bytes of one cached position's index keys over all layers."""
    return float(n_layer * ix_dim * itemsize)


def index_step(live_positions, ix_heads, ix_dim, itemsize=2):
    """``{"flops", "bytes"}`` of one layer's indexer scores on a decode
    step over ``live_positions`` cached positions in all (summed over the
    lanes), one query token a lane: each live position's index key read
    once, and per position and index head one ``ix_dim``-wide dot (the
    relu, the weight and the sum over heads are three operations a head
    beside ``2 ix_dim`` and are left out)."""
    return {"flops": 2.0 * live_positions * ix_heads * ix_dim,
            "bytes": float(live_positions * ix_dim * itemsize)}


def chosen_attention_step(selected_positions, n_heads, n_kv_heads, head_dim,
                          itemsize=2):
    """``{"flops", "bytes"}`` of one layer's attention over the chosen
    rows on a decode step: ``selected_positions`` rows in all (each lane's
    ``min(topk, live)``, summed), each row's keys and values read once,
    and per row and query head a ``head_dim``-wide score and a
    ``head_dim``-wide weighted sum."""
    return {"flops": 4.0 * selected_positions * n_heads * head_dim,
            "bytes": float(selected_positions * 2 * n_kv_heads * head_dim
                           * itemsize)}


def decode_step_bytes(weight_bytes, live_positions, selected_positions,
                      n_layer, n_kv_heads, head_dim, ix_dim, itemsize=2):
    """Bytes a whole decode step must move: the weights, every live index
    key and every chosen row of keys and values, over all layers."""
    return (weight_bytes
            + live_positions * index_key_bytes_per_position(
                n_layer, ix_dim, itemsize)
            + selected_positions * kv_bytes_per_position(
                n_layer, n_kv_heads, head_dim, itemsize))
