#!/usr/bin/env python
"""GPT-2 1.3B single-chip pretraining throughput (BASELINE north star).

BASELINE.md's primary metric is "GPT-2 1.3B ZeRO-3 samples/sec/chip +
TFLOPS". On one chip the ZeRO axes are degenerate (dp=1), so this measures
the per-chip number the multi-chip run is normalised by. 1.3B only fits in
~12 GB HBM with pure-bf16 training (bf16 params AND bf16 Adam moments, no
fp32 masters — see README "Single-chip capacity"); that is the config
benched here.

Comparable published reference number: ZeRO-Offload trains a
bigger-than-HBM model on ONE V100 at >30 TFLOPS (reference
docs/_pages/training.md:293) — the same "single device, model at the
memory limit" story. vs_baseline uses that 30-TFLOPS figure.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from benchmarks._util import (
    analytic_step_metrics,
    gpt_flops_per_token,
    time_train_steps,
)
from deepspeed_tpu.models.transformer_lm import GPT, gpt2_config, num_params

BASELINE_TFLOPS = 30.0  # ZeRO-Offload, 1x V100: docs/_pages/training.md:293


def build(model_name="gpt2-1.3b", seq=1024, micro=6, remat_policy="full",
          zero_stage=1, topology=None, **model_overrides):
    """``(engine, batch, cfg)`` for the headline config: the engine through
    ``deepspeed_tpu.initialize`` and one seeded global batch. Shared with
    ``chip_smoke.py`` so the smoke drives exactly what the bench times."""
    # flash + full remat + micro 6 was chosen by a sweep from before PR 21,
    # stale: ROADMAP S3 (the memory it ran out of held a tree that is lazy
    # since PR 21; which policy and micro batch fit now is the chip's to say)
    cfg = gpt2_config(
        model_name, n_positions=seq, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, scan_layers=True, remat=True,
        remat_policy=remat_policy, use_flash_attention="auto",
        **model_overrides)
    model = GPT(cfg)
    ds_config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "optimizer": {"type": "FusedAdam",
                      "params": {"lr": 2e-4, "betas": [0.9, 0.95],
                                 "weight_decay": 0.1}},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=ds_config, topology=topology)
    gb = micro * engine.topology.data_parallel_size
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, cfg.vocab_size,
                                      size=(gb, seq)).astype(np.int32)}
    batch["labels"] = batch["input_ids"]
    return engine, batch, cfg


def run(model_name="gpt2-1.3b", seq=1024, micro=6, steps=6,
        remat_policy="full"):
    engine, batch, cfg = build(model_name, seq, micro, remat_policy)
    gb = batch["input_ids"].shape[0]
    dt = time_train_steps(engine, batch, steps=steps)

    n_params = num_params(cfg)
    fpt = gpt_flops_per_token(cfg, seq)
    n_dev = len(jax.devices())
    out = {
        "model": model_name,
        "n_params": n_params,
        "model_tflops": round(gb * seq * fpt / dt / 1e12 / n_dev, 2),
        "samples_per_sec": round(gb / dt / n_dev, 2),
        "ms_per_step": round(dt * 1000, 1),
        "seq": seq,
        "global_batch": gb,
        "n_devices": n_dev,
    }
    # what XLA actually scheduled (includes remat recompute the 6N count
    # deliberately excludes) — analytic_mfu is the hardware-honest number
    out.update(analytic_step_metrics(engine, dt))
    return out


if __name__ == "__main__":
    import json

    micro = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    print(json.dumps(run(micro=micro)))
