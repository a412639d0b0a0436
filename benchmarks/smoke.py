#!/usr/bin/env python
"""Step-time gate for the chip: a few steps of the headline configs,
ms/step compared against ``benchmarks/expected.json``, nonzero exit outside
the tolerance band. Refuses anything but a TPU (a CPU timing is not a
device number) and runs everything in this one process, freeing each
engine before the next is built.

  python benchmarks/smoke.py             # gate against expected.json
  python benchmarks/smoke.py --refresh   # re-measure and rewrite expected.json

``expected.json`` is not in the repo at present: the numbers it held were
last measured before PR 1 through a chip access that no longer exists, and
nothing has re-measured them on the current machine. Until a run with
``--refresh`` on the chip reseeds it (copy what it prints into the file),
the gate fails by saying so. Whether the program starts on the chip at all
is ``chip_smoke.py``'s job, not this script's.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EXPECTED_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "expected.json")
TOLERANCE = 0.10


def _int8_decode_ms(trials: int = 3, tokens: int = 64) -> float:
    """p50 per-token decode ms for 1.3B weight-only int8."""
    import time

    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer_lm import GPT, gpt2_config

    cfg = gpt2_config("gpt2-1.3b", dtype=jnp.bfloat16, n_positions=256)
    eng = deepspeed_tpu.init_inference(GPT(cfg), dtype="int8", seed=0)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(1, 128)), jnp.int32)

    import jax

    jax.block_until_ready(
        eng.generate(ids, max_new_tokens=tokens))  # warm/compile
    times = []
    for _ in range(trials):
        t0 = time.time()
        jax.block_until_ready(eng.generate(ids, max_new_tokens=tokens))
        times.append((time.time() - t0) / tokens * 1e3)
    return float(np.percentile(times, 50))


def measure(steps: int, fast: bool = False) -> dict:
    from benchmarks import bert_pretrain, gpt_pretrain

    out = {}
    r = bert_pretrain.run("bert-large", seq=128, micro=64, remat=True,
                          remat_policy="selective", steps=steps)
    out["bert_large_seq128_micro64"] = r["ms_per_step"]
    # 350M (not the 1.3B north star): same engine hot path, 3x faster to
    # materialize, and micro 8 selective-remat is its measured sweet spot
    r = gpt_pretrain.run("gpt2-350m", seq=1024, micro=8, steps=steps,
                         remat_policy="selective")
    out["gpt2_350m_seq1024_micro8"] = r["ms_per_step"]
    if fast:
        return out
    # the other committed headlines, so a regression in any of them fails
    # a gate instead of shipping as a one-shot artifact:
    # (a) BERT seq-512 throughput
    r = bert_pretrain.run("bert-large", seq=512, micro=16, remat=True,
                          remat_policy="selective", steps=steps)
    out["bert_large_seq512_micro16"] = r["ms_per_step"]
    # (b) block-sparse BERT at 4k (the 2.1x sparse win)
    from benchmarks.sparse_attention_bench import run_one as sparse_run_one

    out["bert_large_seq4096_micro1_bigbird"] = round(sparse_run_one(
        {"mode": "bigbird", "block": 128, "num_random_blocks": 1,
         "num_sliding_window_blocks": 3, "num_global_blocks": 1},
        4096, 1, steps), 1)
    # (c) 1.3B int8 weight-only decode
    out["gpt2_1p3b_int8_decode_b1_ms_per_token"] = round(
        _int8_decode_ms(), 2)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--refresh", action="store_true",
                   help="rewrite expected.json from a fresh measurement")
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--tolerance", type=float, default=TOLERANCE)
    p.add_argument("--fast", action="store_true",
                   help="gate only the two train-step configs (skips the "
                        "seq512/sparse/int8 headlines)")
    args = p.parse_args()

    from benchmarks._util import backend_preflight

    if not backend_preflight()["ok"]:
        print("PERF GATE REFUSED: no TPU backend — this gate only times "
              "the chip")
        return 1
    from deepspeed_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()

    if not args.refresh and not os.path.exists(EXPECTED_PATH):
        # never self-greenlight: a missing baseline must fail loudly, not
        # get silently rewritten from a possibly-regressed build
        print(f"PERF GATE FAILED: {EXPECTED_PATH} is missing — restore it "
              f"from git, or deliberately reseed with --refresh")
        return 1
    got = measure(args.steps, fast=args.fast)
    if args.refresh:
        # merge, never truncate: a --fast refresh must not silently delete
        # (and so disarm) the gates it did not re-measure
        merged = {}
        if os.path.exists(EXPECTED_PATH):
            with open(EXPECTED_PATH) as f:
                merged = json.load(f)
        merged.update(got)
        with open(EXPECTED_PATH, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {EXPECTED_PATH}: {json.dumps(merged)}")
        return 0

    with open(EXPECTED_PATH) as f:
        expected = json.load(f)
    failures = []
    for name, want in sorted(expected.items()):
        have = got.get(name)
        if have is None:
            if args.fast:
                continue  # --fast deliberately measures a subset
            failures.append(f"{name}: no measurement (bench removed?)")
            continue
        ratio = have / want
        band = "OK" if abs(ratio - 1.0) <= args.tolerance else "FAIL"
        print(f"{band} {name}: {have:.1f} ms/step (expected {want:.1f}, "
              f"{(ratio - 1.0) * 100:+.1f}%)")
        if band == "FAIL":
            failures.append(name)
    if failures:
        print(f"PERF GATE FAILED: {failures} — if intentional, rerun with "
              f"--refresh and commit expected.json with the delta explained")
        return 1
    print("perf gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
