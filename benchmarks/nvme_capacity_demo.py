"""ZeRO-Infinity parameter NVMe tier capacity demo (real chip).

Proves the tier's memory equation: a model whose fp32 master + Adam
moments + bf16 compute copy (4*3 + 2 = 14 bytes/param) would blow past
the host window trains with host RSS growth bounded by the layer pool —
the full parameter set provably never materializes in RAM (reference
partitioned_param_swapper.py:35 buffer rings).

Run:  python benchmarks/nvme_capacity_demo.py [tpu]

Default backend is CPU, deliberately: there device buffers ARE host RAM,
so the measured RSS upper-bounds what a real TPU host would hold (which
keeps only the rotating window pinned).
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

if "tpu" not in sys.argv[1:]:
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models.pipeline_gpt import gpt_pipeline  # noqa: E402
from deepspeed_tpu.models.transformer_lm import GPTConfig  # noqa: E402


def rss_mb(key="VmRSS"):
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(key):
                return int(line.split()[1]) / 1024.0
    return float("nan")


def main(n_layer=24, n_embd=1024, seq=512, micro=4, steps=2):
    # small vocab: embed/head are DEVICE-RESIDENT by design (persistent
    # params), so a large vocab would dominate the measurement with
    # intentionally-resident state instead of the streamed stack
    cfg = GPTConfig(
        vocab_size=8192, n_positions=seq, n_embd=n_embd, n_layer=n_layer,
        n_head=n_embd // 64, dtype=jnp.bfloat16, scan_layers=False,
        dropout=0.0)
    nvme_dir = tempfile.mkdtemp(prefix="ds_tpu_nvme_")
    ds = {
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {
            "offload_param": {"device": "nvme", "nvme_path": nvme_dir}},
        "steps_per_print": 10 ** 9,
    }
    rss_before = rss_mb()
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=gpt_pipeline(cfg, num_stages=1), config=ds)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(micro, seq)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}

    losses, step_s = [], []
    for i in range(steps):
        t0 = time.time()
        losses.append(float(eng.train_batch(iter([batch]))))
        step_s.append(round(time.time() - t0, 1))

    # full streamed state that would otherwise live in RAM:
    # fp32 master + m + v + compute copy per streamed param
    streamed_params = sum(eng._sizes[1:1 + eng._n_stream])
    full_state_mb = streamed_params * (4 * 3 + 2) / 1e6
    peak_mb = rss_mb("VmHWM")
    disk_mb = sum(
        os.path.getsize(os.path.join(nvme_dir, "param_nvme", f))
        for f in os.listdir(os.path.join(nvme_dir, "param_nvme"))) / 1e6
    result = {
        "metric": "nvme_param_tier_rss_bound",
        "model": f"gpt_{n_layer}L_{n_embd}d",
        "streamed_params_m": round(streamed_params / 1e6, 1),
        "full_streamed_state_mb": round(full_state_mb),
        "disk_state_mb": round(disk_mb),
        "rss_before_mb": round(rss_before),
        "rss_peak_mb": round(peak_mb),
        "rss_growth_mb": round(peak_mb - rss_before),
        # the bound: training ran in less host RSS than even ONE copy of
        # the streamed state needs — and the growth is depth-invariant
        # (the window is 3 layer slots regardless of layer count), which
        # the 24L-vs-48L comparison in the committed artifact shows
        "rss_bounded": bool(peak_mb - rss_before < full_state_mb),
        "losses": [round(l, 3) for l in losses],
        "step_seconds": step_s,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
