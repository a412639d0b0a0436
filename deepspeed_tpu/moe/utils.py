"""MoE param utilities (reference ``deepspeed/moe/utils.py``:
is_moe_param, split_params_into_different_moe_groups_for_optimizer).

In the pytree world a param is identified by its path, so the expert/
non-expert split is a path predicate instead of a tensor attribute."""

from typing import Any, Tuple

import jax

from deepspeed_tpu.utils.tree import path_str


def is_moe_param_path(path: str) -> bool:
    """True for expert-parallel params (sharded over ep, NOT reduced over it)."""
    return "experts/" in path or path.endswith("/experts")


def split_moe_params(params) -> Tuple[Any, Any]:
    """Partition a param pytree into (expert, non-expert) trees with None at
    the complementary leaves (reference splits torch param_groups)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    paths = [path_str(path) for path, _ in flat]

    def select(moe: bool):
        leaves = [
            leaf if is_moe_param_path(path) == moe else None
            for path, (_, leaf) in zip(paths, flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return select(True), select(False)


# rank of one layer's value of each counter the MoE layer sows; whatever
# leads it (a scan's layer axis, none for a layer on its own) is flattened
_COUNTER_RANK = {"routed": 0, "computed": 1, "chosen": 2, "gmm_tiles": 1,
                 "in_place": 0, "held": 0, "routed_here": 0,
                 "rows_moved": 0, "bias_changed": 0}


def routing_stats(model, params, batch):
    """What the MoE layers of ``model`` counted while routing ``batch``, by
    counter name with a leading layer axis: ``routed`` [layers] (the
    (token, expert) pairs each router asked for), ``computed`` [layers,
    experts] (the pairs whose expert output exists, counted from the
    dispatch or the experts' output) and, on the dropless path, ``chosen``
    [layers, tokens, k] (each token's chosen experts) and ``gmm_tiles``
    [layers, 3] (the grouped-matmul kernel's tiles, zeros where the layer
    traced ``ragged_dot``), ``in_place`` [layers] (1 where the kernel read
    the stacked parameters with the layer as an index),
    ``held`` [layers] (the experts whose matrices
    the layer holds: ``computed`` is of those), ``routed_here``
    [layers] (the pairs routed to them) and ``rows_moved`` [layers] (the
    sorted rows the dispatch fetched), and of layers with a correction
    bias ``bias_changed`` [layers] (the tokens whose chosen experts are not
    their k largest uncorrected scores). One forward program of its own,
    off the step."""
    import numpy as np
    from flax.traverse_util import flatten_dict

    from deepspeed_tpu.moe.layer import MOE_STATS

    def forward(params, batch):
        _, stats = model.apply({"params": params}, **batch,
                               mutable=[MOE_STATS])
        return stats[MOE_STATS]

    out = {}
    for path, (value,) in sorted(flatten_dict(
            jax.jit(forward)(params, batch)).items()):
        value = np.asarray(value)
        per_layer = value.shape[value.ndim - _COUNTER_RANK[path[-1]]:]
        out.setdefault(path[-1], []).append(value.reshape(-1, *per_layer))
    return {name: np.concatenate(v) for name, v in out.items()}


def publish_expert_load(model, params, batch):
    """Route ``batch`` once through ``model`` with its MoE layers' counters
    on (:func:`routing_stats`) and publish what they counted as one
    ``moe.load`` event on the telemetry bus (also returned):
    ``tokens_per_expert`` ([layers][experts] pairs each expert computed),
    ``max_over_mean`` (the fullest expert's load over the mean load, the
    largest over the layers; 1 is balanced) and ``tokens_dropped`` (pairs
    the routers asked for less pairs computed, over all layers; the
    dropless path computes every pair). A layer that holds a share of its
    experts (``MoE.experts_held``) counts the held ones alone: ``held``
    (how many a layer), ``routed_here`` (the pairs routed to them, all
    layers; ``tokens_dropped`` is then of those), ``routed`` (all the
    routers asked for) and ``rows_moved`` (the sorted rows the layers'
    dispatch fetched, all layers: ``routed_here`` where the row-fetch
    kernels move a share's rows, ``routed`` where XLA's gather moves every
    pair); ``experts_with_rows_share`` is the share of the
    counted experts that computed at least one pair, the layers' mean (at
    a decode step's few rows the matrices of the others need not be
    read). Of the dropless path's grouped
    matmuls it says which implementation the layers traced,
    ``grouped_matmul`` (``"pallas"``: the kernel of
    ``ops/pallas/grouped_matmul.py``; ``"xla"``: ``jax.lax.ragged_dot``;
    ``"none"``: the one-hot path has neither), the kernel's
    ``grouped_matmul_tiles`` ``[tm, tk, tn]`` and
    ``row_tile_visits_over_least``: the row-tile visits the kernel makes
    for these ``tokens_per_expert`` over ``rows / tm``, the worst layer's
    (1.0 when no expert's rows end inside a tile; each one that does is a
    tile computed twice, and an expert of no rows has one visit that
    computes nothing); host arithmetic on the counts; and
    ``expert_matrices``, ``"in_place"`` where the kernel read the stacked
    parameters with the layer as an index and ``"slice"`` where each layer
    took a tensor of its own (moe/experts.py ``expert_matrices``: the rule
    a training step of the same model and rows traces under). Where the
    routers' choice is corrected by a bias (``MoE.expert_bias``),
    ``bias_changed_share`` is the share of the (token, layer) choices
    whose chosen set is not the k largest uncorrected scores. A training
    loop calls it when it wants to look, never per step."""
    from deepspeed_tpu.telemetry import publish

    stats = routing_stats(model, params, batch)
    counts = stats["computed"]
    tiles, over_least = stats.get("gmm_tiles"), None
    path = "none" if tiles is None else "pallas" if tiles.any() else "xla"
    tiles = tiles[0].tolist() if path == "pallas" else None
    if tiles:
        from deepspeed_tpu.ops.pallas.grouped_matmul import row_tile_visits

        over_least = max(
            row_tile_visits(c, int(rows), tiles[0]) / (int(rows) / tiles[0])
            for c, rows in zip(counts, stats["routed"]))
    here = stats.get("routed_here", stats["routed"])
    corrected = {}
    if "bias_changed" in stats:
        # a layer's tokens are its pairs over k, and k is the chosen
        # experts' second axis
        corrected["bias_changed_share"] = float(
            stats["bias_changed"].sum() * stats["chosen"].shape[2]
            / stats["routed"].sum())
    return publish(
        "moe.load", tokens_per_expert=counts.tolist(),
        max_over_mean=float((counts.max(axis=1) / counts.mean(axis=1)).max()),
        tokens_dropped=int(here.sum() - counts.sum()),
        held=int(stats["held"][0]) if "held" in stats else counts.shape[1],
        experts_with_rows_share=float((counts > 0).mean()),
        routed=int(stats["routed"].sum()), routed_here=int(here.sum()),
        rows_moved=int(stats["rows_moved"].sum())
        if "rows_moved" in stats else None,
        grouped_matmul=path, grouped_matmul_tiles=tiles,
        row_tile_visits_over_least=over_least,
        expert_matrices=("in_place" if stats["in_place"].all() else "slice")
        if tiles else None, **corrected)
