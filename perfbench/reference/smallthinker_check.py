"""System against reference at the SmallThinker configuration's own widths
and layers, outside any timed window:

    python3 -m perfbench.reference.smallthinker_check --config
        <configuration file> --seeds <n> [<n> ...] [--controls]

For each seed: seeded weights in the configuration's parameter dtype and a
seeded batch (the configuration's ``reference.batch``); the system's loss,
routing and gradients (the program's ``GPT`` exactly as the training cell
builds it: compute dtype, layers by kind under their scans, recomputation,
the flash kernels with and without the window over grouped queries, the
held experts' grouped matmuls, the chunked head-and-loss) against
``perfbench/reference/smallthinker.py`` on the same weights. One JSON line
per side with

* ``loss``: absolute difference of the two losses;
* ``agreement``: per layer, the share of positions whose set of chosen
  experts is the reference's;
* ``grad_experts``, ``grad_attention_window``, ``grad_attention_full``,
  ``grad_router``: norm of the difference over the norm of the
  reference's gradient, over the layers that hold the group;

and whether each lies within the configuration's ``reference.limits``.
``--controls`` adds, on the first seed, three sides that must fall
OUTSIDE: the reference on weights rounded to 8 bits, the reference whose
router reads ``RMSNorm_2(h)`` (another model), and the reference with the
window switched off. Exits nonzero unless every system side is inside
every limit and every control is outside at least one.

The reference runs a layer at a time and twice: forward through the
layers keeping each layer's input, then backward through them in turn,
each layer's ``vjp`` made when it is needed (at 8,192 positions one
layer's float32 residuals are gigabytes; eight layers' do not fit a chip),
with a block's attention probabilities recomputed in it
(``smallthinker.attend(forget=True)``).
"""
import argparse
import functools
import json
import sys

import numpy as np

from perfbench.reference.olmoe_check import eight_bit, masks

GROUPS = {"grad_experts": ("mlp/experts/", None),
          "grad_attention_window": ("attn/", "window"),
          "grad_attention_full": ("attn/", "attention"),
          "grad_router": ("mlp/gate/", None)}


def group_leaves(layer, kind):
    """``{(group, path): leaf}`` of one layer's (or one kind's stacked)
    tree: the leaves of the compared groups, on the host."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(layer)[0]:
        key = "/".join(str(getattr(k, "key", k)) for k in path) + "/"
        for group, (mark, of_kind) in GROUPS.items():
            if mark in key and of_kind in (None, kind):
                out[group, key] = np.asarray(leaf)
    return out


@functools.lru_cache(maxsize=None)
def reference_programs(cfg, first, router_reads, window_layers):
    """The reference's layer of each kind and its head, jitted once per
    variant: every layer, seed and set of weights shares them."""
    import jax

    from perfbench.reference import smallthinker

    def layer(kind):
        windowed = kind == "window"

        @jax.jit
        def run(x, p):
            with jax.default_matmul_precision("highest"):
                return smallthinker.block(
                    x, p, window=cfg.sliding_window
                    if windowed and window_layers else None,
                    rotate=windowed, n_head=cfg.n_head,
                    n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
                    top_k=cfg.moe_top_k, first=first,
                    eps=cfg.layer_norm_epsilon, theta=cfg.rope_theta,
                    router_reads=router_reads, forget=True)

        return run

    @jax.jit
    def head(x, ln_f, lm_head, ids):
        with jax.default_matmul_precision("highest"):
            return smallthinker.head_loss(x, ln_f, lm_head, ids,
                                          cfg.layer_norm_epsilon)[0]

    return {kind: layer(kind) for kind in set(cfg.layer_types)}, head


def reference_side(host, ids, cfg, weights=lambda a: a, router_reads="block",
                   window_layers=True):
    """``(loss, chosen masks [L, N, E], [per layer (kind, {(group, path):
    gradient})])`` of the reference on the host's parameter tree, each leaf
    passed through ``weights`` and cast to float32 as it goes to the
    device, one layer at a time."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import smallthinker

    def put(tree, i=None):
        return jax.tree.map(lambda a: jnp.asarray(
            weights(a if i is None else a[i]), jnp.float32), tree)

    layers, head = reference_programs(cfg, cfg.moe_experts_held[0],
                                      router_reads, window_layers)
    places, at = [], dict.fromkeys(set(cfg.layer_types), 0)
    for kind in cfg.layer_types:
        places.append((kind, at[kind]))
        at[kind] += 1
    x = smallthinker.embed(put({"wte": host["wte"]}), ids)
    inputs, chosen = [], []
    for kind, i in places:
        inputs.append(x)
        x, c = layers[kind](x, put(host["h"][kind], i))
        chosen.append(np.asarray(c))
    ce, head_vjp = jax.vjp(lambda *a: head(*a, ids), x, put(host["ln_f"]),
                           put(host["lm_head"]))
    dx = head_vjp(jnp.float32(1.0))[0]
    grads = [None] * len(places)
    for n in reversed(range(len(places))):
        kind, i = places[n]
        _, vjp, _ = jax.vjp(layers[kind], inputs.pop(),
                            put(host["h"][kind], i), has_aux=True)
        dx, dp = vjp(dx)
        grads[n] = (kind, group_leaves(dp, kind))
        del dp, vjp
    return float(ce), np.stack(chosen), grads


def compare(side, ref, limits):
    loss, chosen, grads = side
    ref_loss, ref_chosen, ref_grads = ref
    out = {"loss": abs(loss - ref_loss), "loss_values": [loss, ref_loss],
           "agreement": (chosen == ref_chosen).all(-1).mean(-1).tolist()}
    diff, norm = dict.fromkeys(GROUPS, 0.0), dict.fromkeys(GROUPS, 0.0)
    for (_, got), (_, want) in zip(grads, ref_grads):   # layer by layer
        for (group, key), w in want.items():
            w = w.astype(np.float32)
            d = got[group, key].astype(np.float32) - w
            diff[group] += float(np.sum(np.square(d), dtype=np.float64))
            norm[group] += float(np.sum(np.square(w), dtype=np.float64))
    for g in GROUPS:
        out[g] = (diff[g] / norm[g]) ** 0.5
    # a limit the file does not state yet judges nothing (a first reading)
    def under(name, value, least=False):
        limit = limits.get(name)
        return None if limit is None else \
            bool(value >= limit if least else value <= limit)

    out["within"] = {
        "loss": under("loss", out["loss"]),
        "agreement": under("agreement", min(out["agreement"]), least=True),
        **{g: under(g, out[g]) for g in GROUPS}}
    out["inside_all_limits"] = all(v is True for v in out["within"].values())
    return out


def main(argv):
    p = argparse.ArgumentParser(prog="python -m perfbench.reference."
                                     "smallthinker_check")
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--controls", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT
    from deepspeed_tpu.moe.utils import routing_stats
    from perfbench import stats
    from perfbench.builders import _common, smallthinker_train

    config = stats.load_json(args.config)
    ref_cfg = config["reference"]
    limits, (rows, seq) = ref_cfg["limits"], ref_cfg["batch"]
    cfg = smallthinker_train.model_config(
        config, config["train"], config["max_position_embeddings"])
    model = GPT(cfg)
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "layers": cfg.layer_types,
        "hidden": cfg.n_embd, "experts": cfg.moe_num_experts,
        "held": cfg.moe_experts_held, "top_k": cfg.moe_top_k,
        "window": cfg.sliding_window, "batch": [rows, seq],
        "limits": limits}), flush=True)

    # jitted once: the seeds share their shapes, so they share programs
    init = jax.jit(lambda key, ids: model.init(key, ids)["params"])
    loss_and_grads = jax.jit(jax.value_and_grad(
        lambda p, ids: model.apply({"params": p}, ids, labels=ids)))

    def system_side(params, ids):
        loss, grads = loss_and_grads(params, ids)
        found = routing_stats(model, params,
                              {"input_ids": ids, "labels": ids})
        stacked = {kind: group_leaves(grads["h"][kind], kind)
                   for kind in set(cfg.layer_types)}
        at, layers = dict.fromkeys(stacked, 0), []
        for kind in cfg.layer_types:
            layers.append((kind, {k: v[at[kind]]
                                  for k, v in stacked[kind].items()}))
            at[kind] += 1
        return (float(loss), masks(found["chosen"], cfg.moe_num_experts),
                layers)

    ok, worst = True, {}
    for n, seed in enumerate(args.seeds):
        ids = jnp.asarray(np.random.default_rng([seed, 0]).integers(
            0, cfg.vocab_size, size=(rows, seq), dtype=np.int32))
        params = init(jax.random.PRNGKey(_common.program_seed(seed)),
                      ids[:, :128])
        system = system_side(params, ids)
        host = jax.tree.map(np.asarray, params)     # in their own dtype
        del params

        def reference(**more):
            return reference_side(host, ids, cfg, **more)

        ref = reference()
        sides = [("system", lambda: system)]
        if args.controls and n == 0:
            sides += [
                ("reference_8bit_weights",
                 lambda: reference(weights=eight_bit)),
                ("reference_router_reads_normed_stream",
                 lambda: reference(router_reads="mlp")),
                ("reference_without_the_window",
                 lambda: reference(window_layers=False))]
        for name, side in sides:
            row = compare(side(), ref, limits)
            print(json.dumps({"seed": seed, "side": name, **row}),
                  flush=True)
            if name == "system":
                ok = ok and row["inside_all_limits"]
                for k in ("loss", *GROUPS):
                    worst[k] = max(worst.get(k, 0.0), row[k])
                worst["agreement"] = min(worst.get("agreement", 1.0),
                                         *row["agreement"])
            else:
                ok = ok and not row["inside_all_limits"]
    print(json.dumps({"worst_of_system_over_seeds": worst, "limits": limits,
                      "ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
