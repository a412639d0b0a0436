#!/usr/bin/env python
"""Gradient-exchange wire bytes: int8 vs bf16 vs fp32 for the 1.3B config.

Answers "what actually crosses the interconnect per optimizer step?" using
the CommsLogger's ring-accounted ``wire_bytes`` (comm/logging.py
``wire_factor``) — no kernels run: each exchange is TRACED under
``jax.eval_shape`` over a shard_map'd dp axis, which is exactly when the
logger records op/payload/world, so the full 1.3B parameter set costs
seconds on a laptop.

Accounting conventions (also in docs/observability.md):

- per_exchange: wire bytes for ONE collective gradient exchange of the
  whole grad pytree (per device). The int8 path is the two-phase
  ``quantized_all_reduce`` — int8 payload PLUS its fp32 per-block scale
  sideband; invariantly ~0.5x bf16 per exchange, never below (the
  sideband is 4 bytes per ``block`` elements).
- per_step: wire bytes per OPTIMIZER step at ``--gas`` accumulation
  steps. The plain data path all-reduces into the replicated grad
  accumulator at every micro step (runtime/engine.py ``_fwd_bwd_fn``),
  so plain = gas x per_exchange; the compressed path ships worker grads
  once at the boundary (``runtime/grad_exchange.py`` ``update_core``), so int8 = 1 x
  per_exchange. This is the deployment-relevant ratio: at gas>=2 the
  int8 path is < 0.5x bf16 on the wire.

``fp32``/``bf16``/``int8`` exchange per-leaf; the ``*_bucketed`` modes
exchange through ``comm/bucketed.py`` plans (``tpu.grad_exchange``) —
deterministic size-bounded leaf buckets whose collectives form independent
dataflow chains XLA's latency-hiding scheduler can overlap, reported here
with bucket count and per-bucket payload/sideband wire bytes. Grouping
only changes block-padding waste, not the headline compression ratio —
the bucketed rows exist to pin down the per-bucket wire sizes the overlap
analysis in docs/performance.md reasons about.

  python benchmarks/communication/grad_exchange.py            # 1.3B
  python benchmarks/communication/grad_exchange.py --tiny     # CI-sized
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

# the accounting is trace-only: a virtual 8-device CPU mesh gives the same
# wire bytes as 8 real chips, so default to it unless the caller configured
# a backend themselves
if "JAX_PLATFORMS" not in os.environ:
    os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from deepspeed_tpu.comm import comm as dist  # noqa: E402
from deepspeed_tpu.comm.bucketed import (  # noqa: E402
    bucketed_all_reduce,
    bucketed_quantized_all_reduce,
    plan_for_tree,
)
from deepspeed_tpu.comm.compressed import quantized_all_reduce  # noqa: E402
from deepspeed_tpu.comm.logging import comms_logger  # noqa: E402

AXIS = "dp"


def grad_shapes_1p3b(model_name: str = "gpt2-1.3b", seq: int = 8):
    """Grad pytree avals for the 1.3B pure-bf16 config — the same
    ``eval_shape(model.init)`` the engine uses (runtime/engine.py
    ``_init_state``); grads share the param shapes/dtypes."""
    from deepspeed_tpu.models.transformer_lm import GPT, gpt2_config

    cfg = gpt2_config(model_name, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, scan_layers=True)
    model = GPT(cfg)
    rng = jax.random.PRNGKey(0)
    rngs = {"params": rng, "dropout": jax.random.fold_in(rng, 1)}
    ids = jnp.zeros((1, seq), jnp.int32)

    def init_fn(r):
        return model.init(r, input_ids=ids, deterministic=True)["params"]

    return jax.eval_shape(init_fn, rngs)


def grad_shapes_tiny():
    """Synthetic CI-sized grad set (~0.4M params, bf16)."""
    return {
        "embed": jax.ShapeDtypeStruct((1000, 64), jnp.bfloat16),
        "layers": {
            "attn": jax.ShapeDtypeStruct((4, 64, 192), jnp.bfloat16),
            "mlp": jax.ShapeDtypeStruct((4, 64, 256), jnp.bfloat16),
            "mlp_out": jax.ShapeDtypeStruct((4, 256, 64), jnp.bfloat16),
        },
        "ln": jax.ShapeDtypeStruct((64,), jnp.bfloat16),
    }


def measure_exchange(grads, fmt: str, mesh, block: int = 512,
                     bucket_mb: float = 4.0) -> dict:
    """Trace one whole-pytree gradient exchange in ``fmt`` and return the
    logger's wire accounting (bytes per device, ring-accounted).

    Bucketed modes (``bf16_bucketed`` / ``int8_bucketed``) exchange
    size-bounded leaf buckets — mutually independent collective chains
    XLA's latency-hiding scheduler can overlap — and report each bucket's
    wire bytes from its own ``.bucket<i>`` log record."""
    plan = (plan_for_tree(grads, bucket_mb)
            if fmt.endswith("_bucketed") else None)

    def exchange(g):
        if fmt == "int8":
            return jax.tree.map(
                lambda x: quantized_all_reduce(x, AXIS, block=block), g)
        if fmt == "int8_bucketed":
            out, _, _ = bucketed_quantized_all_reduce(
                g, AXIS, plan, block=block)
            return out
        if fmt == "bf16_bucketed":
            return bucketed_all_reduce(g, AXIS, plan,
                                       wire_dtype=jnp.bfloat16)
        wire = jnp.float32 if fmt == "fp32" else jnp.bfloat16
        return jax.tree.map(
            lambda x: dist.all_reduce(x.astype(wire), AXIS), g)

    mapped = shard_map(exchange, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
    was_enabled, was_all = comms_logger.enabled, comms_logger.prof_all
    comms_logger.reset()
    comms_logger.enabled = True
    comms_logger.prof_all = True
    try:
        jax.eval_shape(mapped, grads)
        counters = comms_logger.counters()
    finally:
        comms_logger.enabled, comms_logger.prof_all = was_enabled, was_all
        comms_logger.reset()
    out = {"wire_bytes": counters["total_wire_bytes"]}
    if fmt == "int8":
        out["payload_wire_bytes"] = counters.get(
            "quantized_all_reduce_wire_bytes", 0.0)
        out["sideband_wire_bytes"] = counters.get(
            "quantized_all_reduce.scales_wire_bytes", 0.0)
    if plan is not None:
        base = ("quantized_all_reduce" if fmt == "int8_bucketed"
                else "bucketed_all_reduce")
        buckets = []
        for b, n in enumerate(plan.bucket_sizes()):
            rec = {"elements": int(n),
                   "payload_wire_bytes": int(counters.get(
                       f"{base}.bucket{b}_wire_bytes", 0.0))}
            if fmt == "int8_bucketed":
                rec["sideband_wire_bytes"] = int(counters.get(
                    f"{base}.bucket{b}.scales_wire_bytes", 0.0))
            buckets.append(rec)
        out["bucket_count"] = plan.num_buckets
        out["buckets"] = buckets
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt2-1.3b")
    p.add_argument("--tiny", action="store_true",
                   help="synthetic ~0.4M-param grad set (CI/tests)")
    p.add_argument("--gas", type=int, default=2,
                   help="gradient accumulation steps for per_step "
                        "accounting (>=2 is the deployment config)")
    p.add_argument("--block", type=int, default=512,
                   help="int8 quantization block (engine default)")
    p.add_argument("--bucket-mb", type=float, default=4.0,
                   help="bucket byte budget for the *_bucketed modes "
                        "(tpu.grad_exchange.bucket_mb)")
    p.add_argument("--out", default=None,
                   help="results JSON path (default: "
                        "grad_exchange_results.json beside this script)")
    args = p.parse_args(argv)

    devs = np.array(jax.devices())
    mesh = Mesh(devs, (AXIS,))
    world = len(devs)

    grads = grad_shapes_tiny() if args.tiny else grad_shapes_1p3b(args.model)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(grads))

    formats = {}
    for fmt in ("fp32", "bf16", "int8", "bf16_bucketed", "int8_bucketed"):
        per_ex = measure_exchange(grads, fmt, mesh, block=args.block,
                                  bucket_mb=args.bucket_mb)
        # plain paths all-reduce every micro step; int8 and the bucketed
        # (deferred-boundary) modes ship worker grads ONCE per step
        exchanges = args.gas if fmt in ("fp32", "bf16") else 1
        formats[fmt] = {
            **{k: (int(v) if isinstance(v, float) else v)
               for k, v in per_ex.items()},
            "exchanges_per_step": exchanges,
            "per_step_wire_bytes": int(per_ex["wire_bytes"] * exchanges),
        }

    bf16_ex = formats["bf16"]["wire_bytes"]
    bf16_step = formats["bf16"]["per_step_wire_bytes"]
    result = {
        "benchmark": "grad_exchange_wire_bytes",
        "model": "tiny-synthetic" if args.tiny else args.model,
        "n_params": n_params,
        "world": world,
        "gas": args.gas,
        "block": args.block,
        "bucket_mb": args.bucket_mb,
        "accounting": "ring wire bytes per device, traced via eval_shape "
                      "(comm/logging.py wire_factor); per-leaf exchanges "
                      "for fp32/bf16/int8, size-bounded buckets "
                      "(comm/bucketed.py, independent collective chains "
                      "XLA can overlap) for *_bucketed",
        "formats": formats,
        "ratios": {
            "per_step_int8_bucketed_vs_bf16": round(
                formats["int8_bucketed"]["per_step_wire_bytes"]
                / formats["bf16"]["per_step_wire_bytes"], 4),
            "per_step_bf16_bucketed_vs_bf16": round(
                formats["bf16_bucketed"]["per_step_wire_bytes"]
                / formats["bf16"]["per_step_wire_bytes"], 4),
            "per_exchange_int8_vs_bf16": round(
                formats["int8"]["wire_bytes"] / bf16_ex, 4),
            "per_exchange_int8_vs_fp32": round(
                formats["int8"]["wire_bytes"]
                / formats["fp32"]["wire_bytes"], 4),
            "per_step_int8_vs_bf16": round(
                formats["int8"]["per_step_wire_bytes"] / bf16_step, 4),
            "per_step_int8_vs_fp32": round(
                formats["int8"]["per_step_wire_bytes"]
                / formats["fp32"]["per_step_wire_bytes"], 4),
        },
        "headline": "per_step_int8_vs_bf16",
    }
    print(json.dumps(result, indent=2))

    out = args.out or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "grad_exchange_results.json")
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    os.replace(tmp, out)
    print(f"# wrote {out}", file=sys.stderr)

    if args.gas >= 2 and \
            result["ratios"]["per_step_int8_vs_bf16"] >= 0.5:
        print("# FAIL: per-step int8 wire bytes not < 0.5x bf16",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
