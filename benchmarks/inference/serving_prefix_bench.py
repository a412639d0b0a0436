#!/usr/bin/env python
"""Serving front door under a bursty, prefix-skewed trace: continuous
batching WITH the shared-prefix KV cache vs cold continuous batching vs
sequential ``generate``.

The headline serving artifact (``make serve-bench``; replaces the
uniform-trace bench, which survives as ``make serve-bench-uniform``).
The trace comes from ``prefix_trace.make_bursty_prefix_trace``: a few
block-aligned system prompts with zipf-ish popularity, bursty arrivals,
user-turn lengths congruent mod the layout block (docstring there
explains why congruence is what makes prefixes reusable).

Methodology (extends serving_bench's):

* identical request set for all three modes, submitted at t0;
* each mode runs the trace twice; the SECOND run is reported. For the
  prefix mode the cache persists across both runs, so the reported run
  is the steady state a long-lived replica serves from (run 1 detects +
  materializes the prefixes; its hit-rate is reported separately as the
  cold-start ramp);
* prefix hit/miss/eviction counters are deltas over the reported run;
* the router section is simulated placement (route_trace) of the same
  trace across N replicas — affinity vs spill rates, no processes.

Exit is nonzero unless prefix-cache p95 TTFT is STRICTLY better than
cold continuous batching with a positive hit rate — the acceptance bar,
enforced where the evidence is produced.

  python benchmarks/inference/serving_prefix_bench.py [--quick]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 3)[0])

from benchmarks._util import run_with_retry  # noqa: E402
from benchmarks.inference.prefix_trace import (  # noqa: E402
    make_bursty_prefix_trace)


def _emit(obj):
    print(json.dumps(obj), flush=True)


def serve_cb(eng, prompts, slots: int, max_new: int, prefix: bool,
             promote_after: int = 2, sched=None):
    """One scheduler pass over the trace; returns (summary, scheduler).
    Pass ``sched`` back in to reuse a warm prefix cache."""
    from deepspeed_tpu.serving import build_serving

    if sched is None:
        cfg = {"slots": slots}
        if prefix:
            cfg["prefix_cache"] = {"promote_after": promote_after}
        sched = build_serving(eng, cfg)
    before = sched.prefix_cache.stats() if prefix else None
    for p in prompts:
        sched.submit(p, max_new_tokens=max_new)
    stats = sched.run()
    out = stats.summary()
    if prefix:
        after = sched.prefix_cache.stats()
        served = after["hits"] + after["misses"] - \
            before["hits"] - before["misses"]
        out["prefix"] = {
            "hits": after["hits"] - before["hits"],
            "misses": after["misses"] - before["misses"],
            "hit_rate": ((after["hits"] - before["hits"]) / served
                         if served else 0.0),
            "insertions": after["insertions"] - before["insertions"],
            "evictions": after["evictions"] - before["evictions"],
            "entries": after["entries"],
            "bytes_used": after["bytes_used"],
            "budget_bytes": after["budget_bytes"],
        }
    return out, sched


def run(args) -> dict:
    from benchmarks.inference.serving_bench import (build_engine,
                                                    serve_sequential)
    from deepspeed_tpu.serving import PrefixRouter, route_trace

    block, window_blocks = 64, 15
    ring = (window_blocks // 2 + 1) * block  # 512
    prompts, meta = make_bursty_prefix_trace(
        args.requests, block=block, seed=0,
        num_prefixes=args.prefixes, burst_len=args.burst)
    out = {
        "model": {"n_embd": 256, "n_layer": 4, "n_head": 8,
                  "vocab_size": 8192, "rotary": True, "dtype": "float32"},
        "layout": {"mode": "local_sliding_window", "block": block,
                   "num_sliding_window_blocks": window_blocks,
                   "ring_slots": ring, "window": ring},
        "slots": args.slots,
        "max_new_tokens": args.max_new,
        "trace": {k: meta[k] for k in
                  ("num_prefixes", "prefix_lens", "weights", "burst_len",
                   "suffix_base", "pad_offset")},
        "num_requests": args.requests,
        "prompt_lens": sorted(set(meta["prompt_lens"])),
        "methodology": ("identical bursty prefix-skewed trace for all "
                        "modes, submitted at t0; second (warm) run "
                        "reported; the prefix cache persists across both "
                        "runs, so the reported run is replica steady "
                        "state; prefix counters are reported-run deltas"),
    }
    eng = build_engine(window_blocks, block, args.n_positions)

    # --- continuous batching + prefix cache (cache warm across runs) --
    _emit({"event": "mode_start", "mode": "cb_prefix_cache"})
    ramp, sched = serve_cb(eng, prompts, args.slots, args.max_new,
                           prefix=True)
    res, err = run_with_retry(
        lambda: serve_cb(eng, prompts, args.slots, args.max_new,
                         prefix=True, sched=sched)[0],
        "cb_prefix_cache", retries=1)
    if err is None:
        res["cold_start_ramp"] = {"hit_rate": ramp["prefix"]["hit_rate"],
                                  "insertions": ramp["prefix"]["insertions"]}
        out["cb_prefix_cache"] = res
        _emit({"event": "mode_done", "mode": "cb_prefix_cache",
               "tokens_per_s": round(res["aggregate_tokens_per_s"], 1),
               "hit_rate": round(res["prefix"]["hit_rate"], 3)})
    else:
        out["cb_prefix_cache"] = {"error": err}
        out["partial"] = True

    # --- cold continuous batching (the PR 8 baseline) -----------------
    _emit({"event": "mode_start", "mode": "cb_cold"})
    serve_cb(eng, prompts, args.slots, args.max_new, prefix=False)
    res, err = run_with_retry(
        lambda: serve_cb(eng, prompts, args.slots, args.max_new,
                         prefix=False)[0],
        "cb_cold", retries=1)
    if err is None:
        out["cb_cold"] = res
        _emit({"event": "mode_done", "mode": "cb_cold",
               "tokens_per_s": round(res["aggregate_tokens_per_s"], 1)})
    else:
        out["cb_cold"] = {"error": err}
        out["partial"] = True

    # --- sequential generate (the pre-PR-8 baseline) ------------------
    _emit({"event": "mode_start", "mode": "sequential_generate"})
    serve_sequential(eng, prompts, args.max_new, block)
    res, err = run_with_retry(
        lambda: serve_sequential(eng, prompts, args.max_new, block),
        "sequential_generate", retries=1)
    if err is None:
        out["sequential_generate"] = res
        _emit({"event": "mode_done", "mode": "sequential_generate",
               "tokens_per_s": round(res["aggregate_tokens_per_s"], 1)})
    else:
        out["sequential_generate"] = {"error": err}
        out["partial"] = True

    # --- simulated multi-replica placement of the same trace ----------
    router = PrefixRouter(args.replicas, align=block, spill_slack=2)
    placed = route_trace(router, prompts)
    out["router_simulation"] = {
        "replicas": args.replicas,
        "placement_counts": [placed.count(i) for i in range(args.replicas)],
        **router.stats(),
        "note": ("hash-affine with depth spill; live multi-process "
                 "routing: examples/serve_router.py"),
    }

    pf = out.get("cb_prefix_cache", {})
    cold = out.get("cb_cold", {})
    if "ttft_s" in pf and "ttft_s" in cold:
        out["ttft_p95_prefix_vs_cold"] = round(
            cold["ttft_s"]["p95"] / pf["ttft_s"]["p95"], 2) \
            if pf["ttft_s"]["p95"] > 0 else None
        out["throughput_prefix_vs_cold"] = round(
            pf["aggregate_tokens_per_s"] / cold["aggregate_tokens_per_s"],
            2)
        # the acceptance bar, enforced at the evidence source
        if not (pf["ttft_s"]["p95"] < cold["ttft_s"]["p95"]
                and pf["prefix"]["hit_rate"] > 0):
            out["partial"] = True
            out["headline_check"] = (
                "FAILED: prefix p95 ttft "
                f"{pf['ttft_s']['p95']:.3f}s vs cold "
                f"{cold['ttft_s']['p95']:.3f}s, hit rate "
                f"{pf['prefix']['hit_rate']:.3f}")
        else:
            out["headline_check"] = "ok"
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--slots", type=int, default=16)
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--max-new", type=int, default=48)
    p.add_argument("--n-positions", type=int, default=2048)
    p.add_argument("--prefixes", type=int, default=3)
    p.add_argument("--burst", type=int, default=4)
    p.add_argument("--replicas", type=int, default=4)
    p.add_argument("--out", default=None)
    # --quick: tiny shape sanity run (CI smoke); does NOT overwrite the
    # committed results unless --out is given
    p.add_argument("--quick", action="store_true")
    a = p.parse_args()
    if a.quick:
        a.slots, a.requests, a.max_new, a.burst = 4, 8, 8, 2

    here = os.path.dirname(os.path.abspath(__file__))
    path = a.out or os.path.join(here, "serving_bench_prefix_results.json")
    if a.quick and a.out is None:
        path = os.path.join(here, "serving_bench_prefix_quick.json")
    t0 = time.monotonic()
    res, err = run_with_retry(lambda: run(a), "serving_prefix_bench",
                              retries=0)
    if res is None:
        res = {"partial": True, "error": err}
    res["bench_wall_s"] = round(time.monotonic() - t0, 1)
    with open(path, "w") as f:
        json.dump(res, f, indent=2)
        f.write("\n")
    _emit({"event": "results_written", "path": path})
    print(json.dumps(res, indent=2))
    sys.exit(0 if not res.get("partial") else 1)


if __name__ == "__main__":
    main()
