#!/usr/bin/env python
"""Benchmarks: the two BASELINE headline workloads on one TPU chip.

Prints one JSON line per workload,
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
with the north-star metric LAST:

1. BERT-Large MLM pretrain, seq 128 — the reference's headline
   single-device number is 64 TFLOPS / 272 samples-per-sec on one V100
   (BASELINE.md, reference docs/_posts/2020-05-28-fastest-bert-training.md:36).
   Harness: benchmarks/bert_pretrain.py.
2. GPT-2 1.3B pretrain (BASELINE "Target configs" #3, the north star) —
   pure-bf16, largest single-chip training config; vs_baseline is the
   reference's single-device model-at-the-memory-limit number (ZeRO-Offload
   >30 TFLOPS on one V100, docs/_pages/training.md:293).
   Harness: benchmarks/gpt_pretrain.py.

One process holds the chip for the whole run: the backend is looked at
once, in-process, and anything but a TPU is refused with a nonzero exit
(a CPU timing is not a device number); the BERT engine is freed before
the 1.3B one is built. A workload that fails prints a JSON line with an
"error" field and the run exits nonzero — no retry: on the chip a failed
workload is a failed run.

Other harnesses: benchmarks/train_sweep.py, benchmarks/long_context.py,
benchmarks/inference/gpt_bench.py, benchmarks/communication/run_all.py.
"""

import json
import sys

sys.path.insert(0, __file__.rsplit("/", 1)[0])

from benchmarks._util import backend_preflight, run_with_retry  # noqa: E402

# Peak dense bf16 per chip comes from the table in
# profiling/step_profiler.py, keyed on the detected device kind (v5e ->
# 197, the public spec) — the honest MFU denominator; a device kind the
# table does not know is an error. The A100 fleet the reference reports
# against runs ~157/312 = 50% MFU at the same scale, so matching MFU is
# the apples-to-apples "matches the reference" claim; vs_baseline keeps
# the reference's own published number as denominator and
# vs_baseline_metric names exactly which number that is.


def _emit(obj):
    print(json.dumps(obj), flush=True)


def _analytic_fields(r: dict) -> dict:
    """Pass through the compiled-step cost-analysis numbers when the
    harness produced them (benchmarks/_util.analytic_step_metrics)."""
    keys = ("analytic_tflops", "analytic_mfu", "analytic_flops_per_step",
            "hbm_gb_per_s")
    return {k: r[k] for k in keys if k in r}


def main() -> int:
    pre = backend_preflight(emit=_emit)
    if not pre["ok"]:
        _emit({"metric": "bench_aborted", "error": pre["error"]})
        return 1

    import jax

    from deepspeed_tpu.profiling.step_profiler import peak_tflops
    from deepspeed_tpu.utils.compile_cache import ensure_compile_cache

    dev = jax.devices()[0]
    _emit({"event": "backend_preflight_ok", "backend": pre["backend"],
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
           "compile_cache_dir": ensure_compile_cache()})
    peak = peak_tflops(dev)[0]

    from benchmarks import bert_pretrain, gpt_pretrain

    failures = 0

    # retries=0: on the chip a failed workload is a failed run; the call
    # only turns the exception into an evidence line and an error string
    r, err = run_with_retry(
        lambda: bert_pretrain.run("bert-large", seq=128, micro=64,
                                  remat=True, remat_policy="selective",
                                  steps=10),
        "bert_large_seq128", retries=0, emit=_emit)
    if r is not None:
        _emit({
            "metric": "bert_large_seq128_train_tflops_per_chip",
            "value": r["model_tflops"],
            "unit": "TFLOPS",
            "mfu": round(r["model_tflops"] / peak, 3),
            "vs_baseline": round(
                r["model_tflops"] / bert_pretrain.BASELINE_TFLOPS, 3),
            "vs_baseline_metric": "reference headline 64 TFLOPS on one V100 "
                                  "(docs/_posts/2020-05-28-fastest-bert-"
                                  "training.md)",
            "samples_per_sec": r["samples_per_sec"],
            "samples_per_sec_vs_baseline": round(
                r["samples_per_sec"] / bert_pretrain.BASELINE_SAMPLES_SEC, 3),
            "ms_per_step": r["ms_per_step"],
            "seq_len": r["seq"],
            "global_batch": r["global_batch"],
            "n_devices": r["n_devices"],
            **_analytic_fields(r),
        })
    else:
        failures += 1
        _emit({"metric": "bert_large_seq128_train_tflops_per_chip",
               "value": None, "unit": "TFLOPS", "error": err})

    # free the BERT engine's device buffers (engine<->adapter cycle needs a
    # GC pass) before the 1.3B model takes nearly all of HBM
    import gc

    gc.collect()

    g, err = run_with_retry(gpt_pretrain.run, "gpt2_1.3b_seq1024",
                            retries=0, emit=_emit)
    if g is not None:
        mfu = g["model_tflops"] / peak
        _emit({
            "metric": "gpt2_1.3b_seq1024_train_tflops_per_chip",
            "value": g["model_tflops"],
            "unit": "TFLOPS",
            "mfu": round(mfu, 3),
            "mfu_reference_a100_fleet": 0.50,  # 157/312 published A100 MFU
            # the honest headline ratio: matched-scale MFU vs the reference's
            # published A100-fleet utilization. The only single-DEVICE 1.3B
            # number the reference publishes is a ZeRO-Offload config (30
            # TFLOPS, docs/_pages/training.md:293) — beating an offload config
            # from HBM is not a like-for-like win, so that ratio is reported
            # under its own name below, not as vs_baseline.
            "vs_baseline": round(mfu / 0.50, 3),
            "vs_baseline_metric": "MFU vs the reference A100 fleet's ~50% "
                                  "MFU at the same scale (157/312 published)",
            "vs_v100_zero_offload_30tflops": round(
                g["model_tflops"] / gpt_pretrain.BASELINE_TFLOPS, 3),
            "samples_per_sec": g["samples_per_sec"],
            "ms_per_step": g["ms_per_step"],
            "seq_len": g["seq"],
            "global_batch": g["global_batch"],
            "n_devices": g["n_devices"],
            **_analytic_fields(g),
        })
    else:
        failures += 1
        _emit({"metric": "gpt2_1.3b_seq1024_train_tflops_per_chip",
               "value": None, "unit": "TFLOPS", "error": err})

    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
