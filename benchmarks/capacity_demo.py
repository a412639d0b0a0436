#!/usr/bin/env python
"""ZeRO-Infinity capacity demo: train GPT-2 2.7B on ONE chip.

The model is ~2x larger than what fits resident (pure-bf16 1.3B is the
single-chip ceiling without offload): `offload_param` keeps the scanned
layer stacks in pinned HOST memory and streams one layer into HBM per
scan iteration (gradients stream back out per layer, ops/streaming.py),
while `offload_optimizer` holds fp32 masters + moments on host with the
native fused Adam. Counterpart of the reference's "13B on one V100-32GB"
ZeRO-Offload/Infinity story (docs/_pages/training.md:293,
partition_parameters.py:537 remote_device).

Runs with micro 1 / seq 1024 / full remat / f32 streamed params — bf16
host slices abort the TPU compiler on a sublane alignment CHECK
(re-observed on jax 0.9.0 / libtpu 0.0.34 in PR 21, so the engine refuses
that combination on TPU; ops/streaming.py). Its step times were
last measured before PR 1 through a chip access that no longer exists; not measured on the current machine.

  python benchmarks/capacity_demo.py --model gpt2-2.7b --steps 3
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt2-2.7b")
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--micro", type=int, default=1)
    p.add_argument("--steps", type=int, default=3)
    args = p.parse_args()

    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer_lm import (
        GPT,
        gpt2_config,
        num_params,
    )
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader

    cfg = gpt2_config(
        args.model, n_positions=args.seq, dtype=jnp.bfloat16,
        param_dtype=jnp.float32,  # streamed host slices must be f32 here
        scan_layers=True, remat=True, remat_policy="full",
        param_offload=True)
    print(json.dumps({"model": args.model,
                      "params_b": round(num_params(cfg) / 1e9, 2)}),
          flush=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT(cfg), config={
        "train_micro_batch_size_per_gpu": args.micro,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {
            "stage": 0,
            "offload_param": {"device": "cpu"},
            "offload_optimizer": {"device": "cpu"},
        },
        "steps_per_print": 10 ** 9,
    })
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      size=(args.micro, args.seq)).astype(np.int32)
    it = iter(RepeatingLoader([{"input_ids": ids, "labels": ids}]))
    for i in range(args.steps):
        t0 = time.time()
        loss = float(engine.train_batch(it))
        print(json.dumps({"step": i + 1,
                          "seconds": round(time.time() - t0, 1),
                          "loss": round(loss, 4)}), flush=True)
        assert np.isfinite(loss)
    print(json.dumps({"capacity_demo": "ok"}), flush=True)


if __name__ == "__main__":
    main()
