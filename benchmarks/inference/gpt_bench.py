#!/usr/bin/env python
"""Inference latency benchmark (reference benchmarks/inference/gpt-bench.py):
prefill + per-token decode p50/p90 latency and tokens/sec for a GPT config
through deepspeed_tpu.init_inference.

  python benchmarks/inference/gpt_bench.py --model gpt2-125m --tokens 64

Its decode latencies (bf16 and --dtype int8, weight-only with per-layer
in-scan dequant) were last measured before PR 1 through a chip access that no longer exists; not measured on the current machine.
"""

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 3)[0])


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt2-125m")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--tokens", type=int, default=64)
    p.add_argument("--trials", type=int, default=5)
    p.add_argument("--dtype", default="bf16",
                   choices=["bf16", "fp32", "int8"])
    p.add_argument("--quality", action="store_true",
                   help="also report logit MSE vs a bf16 engine")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer_lm import GPT, gpt2_config

    # int8 = weight-only quantization over a bf16 compute graph
    cfg = gpt2_config(
        args.model,
        dtype=jnp.float32 if args.dtype == "fp32" else jnp.bfloat16,
        n_positions=args.prompt_len + args.tokens)
    engine = deepspeed_tpu.init_inference(
        GPT(cfg), dtype=args.dtype, replace_with_kernel_inject=True,
        seed=0)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(
        rng.randint(0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
        jnp.int32)

    def fence(x):
        return float(jnp.sum(jnp.asarray(x).astype(jnp.float32)))

    # warmup/compile
    out = engine.generate(ids, max_new_tokens=args.tokens, temperature=0.0)
    fence(out)

    e2e = []
    for _ in range(args.trials):
        t0 = time.time()
        out = engine.generate(ids, max_new_tokens=args.tokens,
                              temperature=0.0)
        fence(out)
        e2e.append(time.time() - t0)
    e2e = np.array(sorted(e2e))
    per_tok = e2e / args.tokens * 1e3

    print(f"model={args.model} batch={args.batch} "
          f"prompt={args.prompt_len} new_tokens={args.tokens}")
    print(f"end-to-end  p50={np.percentile(e2e, 50) * 1e3:.1f} ms  "
          f"p90={np.percentile(e2e, 90) * 1e3:.1f} ms")
    print(f"per-token   p50={np.percentile(per_tok, 50):.2f} ms  "
          f"p90={np.percentile(per_tok, 90):.2f} ms")
    print(f"throughput  {args.batch * args.tokens / np.median(e2e):.1f} "
          f"tokens/sec")

    if args.quality and args.dtype == "int8":
        # logit MSE vs the bf16 engine on the same prompt (reference
        # reports the analogous accuracy deltas for its int8 kernels)
        ref = deepspeed_tpu.init_inference(
            GPT(cfg), dtype="bf16", replace_with_kernel_inject=True,
            seed=0)
        lq = np.asarray(engine.forward(ids), dtype=np.float32)
        lr = np.asarray(ref.forward(ids), dtype=np.float32)
        mse = float(np.mean((lq - lr) ** 2))
        rel = mse / float(np.var(lr))
        print(f"quality     logit MSE={mse:.5f} "
              f"(relative to bf16 logit variance: {rel:.5f})")


if __name__ == "__main__":
    main()
