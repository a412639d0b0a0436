#!/usr/bin/env python
"""Advanced serving compositions in one script.

  python examples/serve_advanced.py --mode int8_tp     # int8 x tensor parallel
  python examples/serve_advanced.py --mode moe_ep      # expert-parallel MoE
  python examples/serve_advanced.py --mode streaming   # past-n_positions decode
  python examples/serve_advanced.py --mode continuous  # continuous batching

int8_tp:    weight-only int8 with the {q, scale} leaves sharded over tp
            (reference init_inference(mp_size=N, dtype=int8)).
moe_ep:     init_inference(ep_size=N) shards the expert stacks over an ep
            mesh axis — an 8-expert model at ep=4 holds 2 experts' weights
            per chip (reference DeepSpeedMoEInference EP groups).
streaming:  a window(+global)-trained rotary model decodes from the ring
            KV cache and generates PAST n_positions at O(window) memory
            (old window blocks evict; leading globals persist — the
            attention-sink pattern).
continuous: the continuous-batching scheduler serves ragged requests
            through a fixed pool of decode slots — a finished sequence's
            lane is refilled by chunked-prefilling the next prompt while
            the other lanes keep decoding; tokens stream per request as
            they land (docs/performance.md "Serving").

On one chip the tp/ep modes run with world size 1 (the sharding is a
no-op); on a mesh they shard as annotated — the same script serves both.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mode", default="streaming",
                   choices=["int8_tp", "moe_ep", "streaming", "continuous"])
    p.add_argument("--tokens", type=int, default=48)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig

    n = len(jax.devices())
    rng = np.random.RandomState(0)

    if args.mode == "int8_tp":
        # ~350M params: the size where weight-only int8 starts WINNING
        # (below ~200M decode is dispatch-bound and int8 measures slower)
        cfg = GPTConfig(vocab_size=50257, n_positions=256, n_embd=1024,
                        n_layer=24, n_head=16, dtype=jnp.bfloat16)
        engine = deepspeed_tpu.init_inference(
            GPT(cfg), mp_size=n, dtype="int8")
        ids = rng.randint(0, cfg.vocab_size, size=(2, 64)).astype(np.int32)
        out = engine.generate(ids, max_new_tokens=args.tokens)
    elif args.mode == "moe_ep":
        ep = n if n in (2, 4, 8) else 1
        cfg = GPTConfig(vocab_size=50257, n_positions=256, n_embd=512,
                        n_layer=4, n_head=8, dtype=jnp.bfloat16,
                        moe_num_experts=8, moe_top_k=2,
                        moe_eval_capacity_factor=2.0)
        engine = deepspeed_tpu.init_inference(
            GPT(cfg), ep_size=ep, dtype="bf16")
        ids = rng.randint(0, cfg.vocab_size,
                          size=(max(ep, 2), 64)).astype(np.int32)
        out = engine.generate(ids, max_new_tokens=args.tokens)
    elif args.mode == "continuous":
        from deepspeed_tpu.inference import ContinuousBatchingScheduler
        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import apply_sparse_attention

        cfg = GPTConfig(vocab_size=50257, n_positions=512, n_embd=256,
                        n_layer=4, n_head=8, dtype=jnp.bfloat16,
                        rotary=True, learned_positions=False)
        model = apply_sparse_attention(
            GPT(cfg), {"mode": "local_sliding_window", "block": 32,
                       "num_sliding_window_blocks": 3})  # ring = 64 slots
        engine = deepspeed_tpu.init_inference(model, dtype="bf16")
        sched = ContinuousBatchingScheduler(engine, slots=4)

        def stream(rid, token, done):
            print(f"  req {rid}: token {token}{'  <done>' if done else ''}")

        # ragged prompts, two of them LONGER than the 64-slot ring: those
        # admissions prefill in exact block-aligned chunks
        for n_prompt in (24, 80, 40, 150, 64, 96, 30, 55):
            sched.submit(list(rng.randint(1, cfg.vocab_size, size=n_prompt)),
                         max_new_tokens=min(args.tokens, 12),
                         stream_callback=stream)
        stats = sched.run()
        s = stats.summary()
        print(f"mode=continuous: {s['num_sequences']} sequences, "
              f"{s['total_generated_tokens']} tokens in "
              f"{s['wall_s']:.2f}s ({s['aggregate_tokens_per_s']:.1f} tok/s, "
              f"{s['decode_steps']} batched decode steps) on {n} device(s)")
        return
    else:  # streaming
        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import apply_sparse_attention

        cfg = GPTConfig(vocab_size=50257, n_positions=256, n_embd=768,
                        n_layer=12, n_head=12, dtype=jnp.bfloat16,
                        rotary=True, learned_positions=False)
        # ring (1+1)*64 + 64 globals = 192 slots < n_positions=256, so the
        # ring engages and the cap lifts
        model = apply_sparse_attention(
            GPT(cfg), {"mode": "bslongformer", "block": 64,
                       "num_sliding_window_blocks": 3,
                       "attention": "unidirectional"})
        engine = deepspeed_tpu.init_inference(model, dtype="bf16")
        ids = rng.randint(0, cfg.vocab_size, size=(1, 128)).astype(np.int32)
        # 128 + max(384, --tokens) positions through an n_positions=256
        # model: the ring evicts, generation keeps going past the cap
        out = engine.generate(ids, max_new_tokens=max(384, args.tokens),
                              temperature=0.8)

    print(f"mode={args.mode}: generated {np.asarray(out).shape[1]} tokens "
          f"per prompt on {n} device(s)")
    print(np.asarray(out)[:, :16], "...")


if __name__ == "__main__":
    main()
