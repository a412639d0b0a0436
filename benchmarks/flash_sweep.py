#!/usr/bin/env python
"""Pallas flash attention vs XLA einsum attention: training-step TFLOPS
across sequence lengths (decides use_flash_attention="auto"; SURVEY §2.4
flash rows).

Measured (GPT-2 125M, one v5e chip, 8192 tokens/batch, selective remat):

    seq   micro   XLA TFLOPS   flash TFLOPS   winner
    128     64      55.7          45.3        XLA
    512     16      44.9          49.2        flash
    2048     4      25.1          46.7        flash (1.9x)
    4096     2      12.4          47.6        flash (3.8x)

=> FLASH_AUTO_MIN_SEQ = 512 (models/transformer_lm.py): the [T, T] score
materialization XLA does stops fitting VMEM-friendly tiles past ~512.

  python benchmarks/flash_sweep.py --model gpt2-125m --seqs 128 512 2048 4096

``--kernels`` times the three Mosaic calls alone (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``, each on its own: the forward runs
twice a layer under full remat, so each kernel may want its own blocks) at
one ``(bh, t, d)`` over the candidate grid, one JSON row a candidate: what
``PRETUNED`` in ``ops/pallas/autotune.py`` is chosen from (PERF.md section
6, PR 45). It times compiled kernels only: it exits nonzero where JAX's
backend is no TPU, and every row names the ``device_kind`` it was read on.

  python benchmarks/flash_sweep.py --kernels --bh 96 --t 1024 --d 128
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks._util import gpt_flops_per_token, time_train_steps  # noqa: E402


def run(model_name, seq, flash, micro, steps=5):
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer_lm import GPT, gpt2_config

    cfg = gpt2_config(model_name, n_positions=seq, dtype=jnp.bfloat16,
                      scan_layers=True, remat=True,
                      remat_policy="selective",
                      use_flash_attention=flash)
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT(cfg), config={
        "train_micro_batch_size_per_gpu": micro,
        "bf16": {"enabled": True},
        "optimizer": {"type": "FusedAdam", "params": {"lr": 6e-4}},
        "steps_per_print": 10 ** 9,
    })
    gb = micro * engine.topology.data_parallel_size
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(gb, seq)).astype(np.int32)
    dt = time_train_steps(engine, {"input_ids": ids, "labels": ids},
                          steps=steps)
    fpt = gpt_flops_per_token(cfg, seq)
    return round(gb * seq * fpt / dt / 1e12, 2), round(dt * 1e3, 1)


def default_candidates(t):
    """Divisor-filtered (block_q, block_k) grid around the MXU-friendly
    power-of-two sizes, bounded so the f32 score tile stays well under a
    VMEM core (block_q*block_k <= 512*1024 -> 2 MB)."""
    from deepspeed_tpu.ops.pallas.common import largest_divisor_block

    sizes = [b for b in (128, 256, 512, 1024) if b <= t and t % b == 0]
    if not sizes:  # short/odd seq: fall back to the divisor heuristic sizes
        sizes = sorted({largest_divisor_block(t, w)
                        for w in (128, 256, 512)})
    return [(bq, bk) for bq in sizes for bk in sizes
            if bq * bk <= 512 * 1024]


def kernel_candidates(t, granules=(128, 256, 512)):
    """``(block_q, block_k, granule)`` over :func:`default_candidates`'
    pairs and strips of the whole sequence; ``fit_blocks`` folds the ones
    a kernel cannot launch onto those it can, and :func:`sweep_kernels`
    times each distinct launch once."""
    pairs = default_candidates(t) + [(t, 512), (512, t)]
    return [(bq, bk, g) for bq, bk in dict.fromkeys(pairs)
            for g in granules]


def time_chain(call, x, chain=8, iters=5):
    """Milliseconds a call of ``call`` takes on the device: ``chain`` calls
    in one program, each fed the one before (same shape and dtype), so
    neither the host's dispatch nor a fusion around the call is timed."""
    import time

    import jax

    def run(x):
        for _ in range(chain):
            x = call(x)
        return x

    run = jax.jit(run)
    jax.block_until_ready(run(x))
    t0 = time.perf_counter()
    for _ in range(iters):
        y = run(x)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / (iters * chain) * 1e3


def sweep_kernels(bh, t, d, dtype="bfloat16", causal=True, candidates=None,
                  kernels=None, out=print):
    """Time each kernel alone at ``[bh, t, d]`` over ``candidates`` and
    ``out`` a JSON row a distinct launch: the blocks as fitted, ms a call,
    the share of its roofline as the benchmark's ``flash_roofline`` reads
    it (``perfbench/flops.py``'s FLOPs and bytes, ``peaks.json``'s chip; a
    device the table lacks gets no share) and the tiles computed over the
    tiles needed. The kernels run compiled (``interpret=False``), never
    interpreted: a time comes from a chip, so no TPU is an error. Returns
    the rows."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import flops

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"flash_sweep --kernels times compiled kernels on a TPU; JAX's "
            f"backend here is {jax.default_backend()!r}")
    device_kind = jax.devices()[0].device_kind
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    with open(os.path.join(os.path.dirname(flops.__file__),
                           "peaks.json")) as f:
        peak = json.load(f).get(device_kind)
    dtype = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rng.randn(bh, t, d), dtype) for _ in range(4))
    scale = 1.0 / np.sqrt(d)
    o, lse = fa._call_fwd(q, k, v, None, scale, causal, fa.fit_blocks(
        fa.KERNEL_FWD, t, causal, 512, 512), interpret=False)
    delta = fa.row_delta(o, do)
    calls = {
        fa.KERNEL_FWD: lambda b: lambda x: fa._call_fwd(
            x, k, v, None, scale, causal, b, interpret=False)[0],
        fa.KERNEL_BWD_DQ: lambda b: lambda x: fa._call_dq(
            (x, k, v, do, lse, delta), None, scale, causal, b,
            interpret=False),
        fa.KERNEL_BWD_DKV: lambda b: lambda x: fa._call_dkv(
            (q, x, v, do, lse, delta), None, scale, causal, b,
            interpret=False)[0],
    }
    rows = []
    for kernel in kernels or fa.KERNELS:
        kind = kernel[len("flash_"):]  # fwd, bwd_dq, bwd_dkv
        least = peak and flops.roofline_seconds(
            flops.flash_call_flops(kind, bh, t, d, causal),
            flops.flash_call_bytes(kind, bh, t, d, dtype.itemsize), peak)[0]
        seen = set()
        for wanted in candidates or kernel_candidates(t):
            blocks = fa.fit_blocks(kernel, t, causal, *wanted)
            if blocks in seen:
                continue
            seen.add(blocks)
            row = {"device_kind": device_kind, "kernel": kernel, "bh": bh,
                   "t": t, "d": d, "causal": causal, **blocks._asdict()}
            counts = fa.tile_counts(kernel, t, causal, blocks)
            row["computed_over_needed"] = round(
                counts["tiles_computed"] / counts["tiles_needed"], 4)
            try:
                ms = time_chain(calls[kernel](blocks),
                                k if kernel == fa.KERNEL_BWD_DKV else q)
                row["ms"] = round(ms, 4)
                if least:
                    row["roofline_pct"] = round(100 * least / (ms / 1e3), 2)
            except Exception as e:  # a launch the compiler refuses: say so
                row["error"] = str(e).strip().splitlines()[-1][:160]
            rows.append(row)
            out(json.dumps(row))
    return rows


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--kernels", action="store_true",
                   help="time the three kernels alone over the candidates")
    p.add_argument("--bh", type=int, default=96)
    p.add_argument("--t", type=int, default=1024)
    p.add_argument("--d", type=int, default=128)
    p.add_argument("--non-causal", action="store_true")
    p.add_argument("--model", default="gpt2-125m")
    p.add_argument("--seqs", type=int, nargs="+",
                   default=[128, 512, 2048, 4096])
    p.add_argument("--tokens-per-batch", type=int, default=8192)
    args = p.parse_args()

    if args.kernels:
        import functools

        sweep_kernels(args.bh, args.t, args.d, causal=not args.non_causal,
                      out=functools.partial(print, flush=True))
        return

    for seq in args.seqs:
        micro = max(1, args.tokens_per_batch // seq)
        row = {"model": args.model, "seq": seq, "micro": micro}
        for flash in (False, True):
            try:
                tflops, ms = run(args.model, seq, flash, micro)
                row["flash" if flash else "xla"] = tflops
                row[("flash" if flash else "xla") + "_ms"] = ms
            except Exception as e:
                row["flash" if flash else "xla"] = f"error: {str(e)[:80]}"
            gc.collect()
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
