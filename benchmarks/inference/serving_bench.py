#!/usr/bin/env python
"""Continuous batching vs sequential ``generate``: TTFT, per-token
latency, aggregate tokens/sec.

The serving story ROADMAP item 2 asks for: ≥16 concurrent streaming
sequences over a window-512 ring model (block 64, 15 sliding-window
blocks → (7+1)·64 = 512 ring slots), ragged prompt lengths spanning
sub-ring to >2× ring (long ones exercise the exact chunked admission
prefill), compared against serving the same requests one ``generate``
call at a time.

Methodology (docs/performance.md "Serving"):

* every request is submitted at t0; both modes serve the identical set;
* continuous batching: TTFT and per-token latency come from the
  scheduler's per-completion timestamps (first token lands at admission
  prefill; inter-token gap = completion window / (n-1));
* sequential: wall time is the sum of full ``generate`` calls; TTFT_i =
  the queue wait (sum of prior requests' full durations) plus request
  i's own prefill+first-token latency, measured once per request with a
  warm ``max_new_tokens=1`` call before the timed loop;
* each mode runs twice — first run pays every jit compile, the SECOND
  run is the one reported (steady-state serving, the regime that
  matters);
* aggregate tokens/sec = total generated tokens / mode wall time.

  python benchmarks/inference/serving_bench.py [--slots 16] [--quick]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 3)[0])

from benchmarks._util import run_with_retry  # noqa: E402


def _emit(obj):
    print(json.dumps(obj), flush=True)


def build_engine(window_blocks: int, block: int, n_positions: int):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
    from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import (
        apply_sparse_attention)

    cfg = GPTConfig(vocab_size=8192, n_positions=n_positions, n_embd=256,
                    n_layer=4, n_head=8, dtype=jnp.float32,
                    param_dtype=jnp.float32, rotary=True,
                    learned_positions=False, scan_layers=True)
    model = apply_sparse_attention(
        GPT(cfg), {"mode": "local_sliding_window", "block": block,
                   "num_sliding_window_blocks": window_blocks})
    return deepspeed_tpu.init_inference(model, dtype="fp32", seed=0)


def make_requests(num: int, block: int, seed: int = 0):
    """Ragged prompts from sub-ring to >2x ring; deterministic."""
    import numpy as np

    rng = np.random.default_rng(seed)
    # few distinct buckets (bounded compile count), long tail exercises
    # the chunked admission prefill (ring is 512 at the default layout)
    lens = [96, 224, 352, 480, 608, 736, 960, 1088]
    return [list(rng.integers(1, 8192, size=lens[i % len(lens)]))
            for i in range(num)]


def serve_continuous(eng, prompts, slots: int, max_new: int):
    from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler

    sched = ContinuousBatchingScheduler(eng, slots=slots)
    for p in prompts:
        sched.submit(p, max_new_tokens=max_new)
    stats = sched.run()
    return stats.summary()


def serve_sequential(eng, prompts, max_new: int, block: int):
    """The same request set, one warm ``generate`` call at a time."""
    import jax.numpy as jnp
    import numpy as np

    def padded(p):
        L = max(3 * block, ((len(p) + block - 1) // block) * block)
        ids = np.zeros((1, L), np.int32)
        m = np.zeros((1, L), bool)
        ids[0, :len(p)] = p
        m[0, :len(p)] = True
        return jnp.asarray(ids), jnp.asarray(m)

    # per-request prefill+first-token latency, warm (outside the wall)
    ttft1 = []
    for p in prompts:
        ids, m = padded(p)
        t0 = time.monotonic()
        np.asarray(eng.generate(ids, max_new_tokens=1, attention_mask=m))
        ttft1.append(time.monotonic() - t0)

    wall0 = time.monotonic()
    ttfts, per_token, total = [], [], 0
    for p, t1 in zip(prompts, ttft1):
        ids, m = padded(p)
        r0 = time.monotonic()
        out = np.asarray(eng.generate(ids, max_new_tokens=max_new,
                                      attention_mask=m))
        dt = time.monotonic() - r0
        ttfts.append((r0 - wall0) + t1)  # queue wait + own first token
        if max_new > 1:
            per_token.append(max(0.0, dt - t1) / (max_new - 1))
        total += out.shape[1]
    wall = time.monotonic() - wall0

    ttfts = sorted(ttfts)
    pts = sorted(per_token)

    def pct(xs, q):
        return float(xs[min(len(xs) - 1, int(q * len(xs)))]) if xs else 0.0

    return {
        "num_sequences": len(prompts),
        "total_generated_tokens": total,
        "wall_s": wall,
        "aggregate_tokens_per_s": total / wall if wall > 0 else 0.0,
        "ttft_s": {"mean": float(np.mean(ttfts)), "p50": pct(ttfts, 0.50),
                   "p95": pct(ttfts, 0.95)},
        "per_token_ms": {"mean": float(np.mean(pts)) * 1e3 if pts else 0.0,
                         "p50": pct(pts, 0.50) * 1e3,
                         "p95": pct(pts, 0.95) * 1e3},
    }


def run(args) -> dict:
    block, window_blocks = 64, 15
    ring = (window_blocks // 2 + 1) * block  # 512
    out = {
        "model": {"n_embd": 256, "n_layer": 4, "n_head": 8,
                  "vocab_size": 8192, "rotary": True, "dtype": "float32"},
        "layout": {"mode": "local_sliding_window", "block": block,
                   "num_sliding_window_blocks": window_blocks,
                   "ring_slots": ring, "window": ring},
        "slots": args.slots,
        "num_requests": args.requests,
        "max_new_tokens": args.max_new,
        "prompt_lens": sorted({len(p) for p in
                               make_requests(args.requests, block)}),
        "methodology": ("both modes serve the identical request set, "
                        "submitted at t0; second (warm) run reported; "
                        "sequential TTFT_i = queue wait + measured "
                        "prefill+first-token latency"),
    }
    eng = build_engine(window_blocks, block, args.n_positions)
    prompts = make_requests(args.requests, block)

    for name, fn in (
            ("continuous_batching",
             lambda: serve_continuous(eng, prompts, args.slots,
                                      args.max_new)),
            ("sequential_generate",
             lambda: serve_sequential(eng, prompts, args.max_new, block))):
        _emit({"event": "mode_start", "mode": name})
        fn()  # first run pays every compile
        res, err = run_with_retry(fn, name, retries=1)
        if err is not None:
            out[name] = {"error": err}
            out["partial"] = True
        else:
            out[name] = res
            _emit({"event": "mode_done", "mode": name,
                   "tokens_per_s": round(res["aggregate_tokens_per_s"], 1)})

    cb = out.get("continuous_batching", {})
    seq = out.get("sequential_generate", {})
    if "aggregate_tokens_per_s" in cb and "aggregate_tokens_per_s" in seq:
        out["throughput_speedup"] = round(
            cb["aggregate_tokens_per_s"] / seq["aggregate_tokens_per_s"], 2)
        out["ttft_p95_speedup"] = round(
            seq["ttft_s"]["p95"] / cb["ttft_s"]["p95"], 2) \
            if cb["ttft_s"]["p95"] > 0 else None
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--slots", type=int, default=16)
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--max-new", type=int, default=48)
    p.add_argument("--n-positions", type=int, default=2048)
    p.add_argument("--out", default=None)
    # --quick: tiny shape sanity run (CI smoke); does NOT overwrite the
    # committed results unless --out is given
    p.add_argument("--quick", action="store_true")
    a = p.parse_args()
    if a.quick:
        a.slots, a.requests, a.max_new = 4, 6, 8

    res, err = run_with_retry(lambda: run(a), "serving_bench", retries=0)
    if res is None:
        res = {"partial": True, "error": err}
    here = os.path.dirname(os.path.abspath(__file__))
    path = a.out or os.path.join(here, "serving_bench_results.json")
    if a.quick and a.out is None:
        path = os.path.join(here, "serving_bench_quick.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=2)
        f.write("\n")
    _emit({"event": "results_written", "path": path})
    print(json.dumps(res, indent=2))
    sys.exit(0 if not res.get("partial") else 1)


if __name__ == "__main__":
    main()
