"""System against reference at a configuration's own widths, outside any
timed window:

    python3 -m perfbench.reference.olmoe_check --config <configuration
        file> --seeds <n> [<n> ...] [--controls]

For each seed: seeded weights in the configuration's parameter dtype and a
seeded batch (the configuration's ``reference.batch``); the system's loss,
routing and gradients (the program's ``GPT`` exactly as the training cell
builds it: compute dtype, scanned layers, recomputation, flash kernel,
grouped matmuls) against the plain float32 reference on the same weights.
One JSON line per side with

* ``loss``: absolute difference of the two losses;
* ``agreement``: per layer, the share of positions whose set of chosen
  experts is the reference's;
* ``grad_experts``, ``grad_attention``, ``grad_router``: norm of the
  difference over the norm of the reference's gradient, over all layers;

and whether each lies within the configuration's ``reference.limits``.
``--controls`` adds, on the first seed, two sides that must fall OUTSIDE:
the reference on weights rounded to 8 bits (the nearest precision below
the configuration's) and the reference with renormalised top-k weights
(another model). Exits nonzero unless every system side is inside every
limit and every control is outside at least one.
"""
import argparse
import functools
import json
import sys

import numpy as np

GROUPS = {"grad_experts": "mlp/experts/", "grad_attention": "attn/",
          "grad_router": "mlp/gate/"}


def group_leaves(layer):
    """``{(group, path): leaf}`` of one layer's tree, the leaves of the
    compared groups only, on the host in the dtype they have."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(layer)[0]:
        key = "/".join(str(getattr(k, "key", k)) for k in path) + "/"
        for group, mark in GROUPS.items():
            if mark in key:
                out[group, key] = np.asarray(leaf)
    return out


def masks(chosen, experts):
    """[L, N, k] indices -> [L, N, E] bool."""
    out = np.zeros(chosen.shape[:2] + (experts,), bool)
    layer, token = np.indices(chosen.shape[:2])
    out[layer[..., None], token[..., None], chosen] = True
    return out


@functools.lru_cache(maxsize=None)
def reference_programs(cfg, renormalize):
    """The reference's one layer and its head, jitted once per routing
    variant: every layer, seed and set of weights shares them."""
    import jax

    from perfbench.reference import olmoe

    @jax.jit
    def layer(x, p):
        with jax.default_matmul_precision("highest"):
            x, chosen, balance, z = olmoe.block(
                x, p, n_head=cfg.n_head, top_k=cfg.moe_top_k,
                eps=cfg.layer_norm_epsilon, theta=cfg.rope_theta,
                renormalize=renormalize)
        return (x, balance, z), chosen

    @jax.jit
    def head(x, ln_f, lm_head, ids):
        with jax.default_matmul_precision("highest"):
            return olmoe.head_loss(x, ln_f, lm_head, ids,
                                   cfg.layer_norm_epsilon)[0]

    return layer, head


def reference_side(olmoe, host, ids, cfg, weights=lambda a: a,
                   renormalize=False):
    """``(loss, chosen masks, [per layer {(group, path): gradient}])`` of
    the reference on the host's parameter tree, each leaf passed through
    ``weights`` and cast to float32 as it goes to the device, one layer at
    a time (so the host holds one float32 copy of one leaf beside the
    parameters as they are, and the device each layer's weights once).
    Forward layer by layer, keeping each layer's ``vjp``, then backward
    through them in turn: the whole tree's float32 gradients beside the
    float32 weights do not fit a 16 GB chip, one layer's do; and one
    layer's program, compiled once, serves every layer."""
    import jax
    import jax.numpy as jnp

    def put(tree, i=None):
        return jax.tree.map(lambda a: jnp.asarray(
            weights(a if i is None else a[i]), jnp.float32), tree)

    layer, head = reference_programs(cfg, renormalize)
    n = cfg.n_layer
    x = olmoe.embed(put({"wte": host["wte"]}), ids)
    back, chosen, aux = [], [], jnp.float32(0.0)
    for i in range(n):
        (x, balance, z), vjp, c = jax.vjp(
            layer, x, put(host["h"]["block"], i), has_aux=True)
        aux = aux + (cfg.moe_aux_loss_coef * balance
                     + cfg.moe_z_loss_coef * z) / n
        back.append(vjp), chosen.append(np.asarray(c))
    ce, head_vjp = jax.vjp(lambda *a: head(*a, ids), x, put(host["ln_f"]),
                           put(host["lm_head"]))
    dx = head_vjp(jnp.float32(1.0))[0]
    grads = [None] * n
    for i in reversed(range(n)):
        dx, dp = back.pop()((dx, jnp.float32(cfg.moe_aux_loss_coef / n),
                             jnp.float32(cfg.moe_z_loss_coef / n)))
        grads[i] = group_leaves(dp)
        del dp
    return float(ce + aux), np.stack(chosen), grads


def compare(side, ref, limits):
    loss, chosen, grads = side
    ref_loss, ref_chosen, ref_grads = ref
    out = {"loss": abs(loss - ref_loss), "loss_values": [loss, ref_loss],
           "agreement": (chosen == ref_chosen).all(-1).mean(-1).tolist()}
    diff, norm = dict.fromkeys(GROUPS, 0.0), dict.fromkeys(GROUPS, 0.0)
    for got, want in zip(grads, ref_grads):         # layer by layer
        for (group, key), w in want.items():
            w = w.astype(np.float32)
            d = got[group, key].astype(np.float32) - w
            diff[group] += float(np.sum(np.square(d), dtype=np.float64))
            norm[group] += float(np.sum(np.square(w), dtype=np.float64))
    for g in GROUPS:
        out[g] = (diff[g] / norm[g]) ** 0.5
    out["within"] = {
        "loss": out["loss"] <= limits["loss"],
        "agreement": min(out["agreement"]) >= limits["agreement"],
        **{g: out[g] <= limits[g] for g in GROUPS}}
    out["inside_all_limits"] = all(out["within"].values())
    return out


def eight_bit(a):
    """Weights rounded to 8 bits, symmetric per output column."""
    a = a.astype(np.float32)
    if a.ndim < 2:
        return a
    scale = np.abs(a).max(axis=-2, keepdims=True) / 127.0
    return np.round(a / np.maximum(scale, 1e-30)) * scale


def main(argv):
    p = argparse.ArgumentParser(prog="python -m perfbench.reference."
                                     "olmoe_check")
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--controls", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT
    from deepspeed_tpu.moe.utils import routing_stats
    from perfbench import stats
    from perfbench.builders import _common, olmoe_train
    from perfbench.reference import olmoe

    config = stats.load_json(args.config)
    ref_cfg = config["reference"]
    limits, (rows, seq) = ref_cfg["limits"], ref_cfg["batch"]
    cfg = olmoe_train.model_config(config, config["train"],
                                   config["max_position_embeddings"])
    model = GPT(cfg)
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "layers": cfg.n_layer,
        "hidden": cfg.n_embd, "experts": cfg.moe_num_experts,
        "top_k": cfg.moe_top_k, "batch": [rows, seq], "limits": limits}),
        flush=True)

    # jitted once: the seeds share their shapes, so they share programs
    init = jax.jit(lambda key, ids: model.init(key, ids)["params"])
    loss_and_grads = jax.jit(jax.value_and_grad(
        lambda p, ids: model.apply({"params": p}, ids, labels=ids)))

    def system_side(params, ids):
        loss, grads = loss_and_grads(params, ids)
        found = routing_stats(model, params,
                              {"input_ids": ids, "labels": ids})
        stacked = group_leaves(grads["h"]["block"])
        return (float(loss), masks(found["chosen"], cfg.moe_num_experts),
                [{k: v[i] for k, v in stacked.items()}
                 for i in range(cfg.n_layer)])

    ok, worst = True, {}
    for n, seed in enumerate(args.seeds):
        ids = jnp.asarray(np.random.default_rng([seed, 0]).integers(
            0, cfg.vocab_size, size=(rows, seq), dtype=np.int32))
        params = init(jax.random.PRNGKey(_common.program_seed(seed)), ids)
        system = system_side(params, ids)
        host = jax.tree.map(np.asarray, params)     # in their own dtype
        del params

        def reference(**more):
            return reference_side(olmoe, host, ids, cfg, **more)

        ref = reference()
        sides = [("system", lambda: system)]
        if args.controls and n == 0:
            sides.append(("reference_8bit_weights",
                          lambda: reference(weights=eight_bit)))
            sides.append(("reference_renormalised_topk",
                          lambda: reference(renormalize=True)))
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
