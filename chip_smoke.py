#!/usr/bin/env python3
"""Does the system still start on the chip?

Drives the main path once, through the entry points a user calls, at the
full width of GPT-2 1.3B, and checks what comes out by the repo's own
means. With no arguments (there are none) it is the strict run:

1. ``kernels``  every Pallas kernel once, compiled, at a production
   shape, forward and backward, against its plain ``jnp`` reference.
2. ``train``    ``deepspeed_tpu.initialize`` -> ``engine.train_batch``:
   the config ``benchmarks/gpt_pretrain.py`` builds (1.3B, seq 1024,
   micro 6, bf16, full remat, flash "auto", FusedAdam, ZeRO-1, default
   telemetry/sentinel), 8 optimizer steps on one chip on a seeded batch.
3. ``train_warm``  the same phase again in a fresh process: its compile
   seconds next to the first run's show the persistent compile cache.
4. ``serve``    ``init_inference(dtype="bf16")`` -> ``build_serving``
   -> ``ContinuousBatchingScheduler.run``: 4 seeded prompts of 128-512
   tokens, 32 greedy tokens each, checked against ``engine.generate``.
5. ``four_chip``  only where JAX reports >= 4 devices: the trainer under
   ZeRO-3 over fsdp=4, and what the server does on four devices.

One process per chip: this parent never imports JAX. It runs the phases
as children, one after another, each in a fresh interpreter that holds
the chip alone and releases it by exiting. On a host with several chips
the one-chip phases see only the first (``ONE_CHIP_ENV``). A phase that fails ends the run
with a nonzero exit code; nothing is retried and nothing is skipped. Every
phase first checks the device and exits nonzero unless it is a TPU whose
``device_kind`` is in the peak table — there is no CPU fallback. Inputs
and weights come from seeds; no git, no network.

The observations printed per phase (compile seconds, ms per step, peak
bytes) are smoke observations, not benchmark numbers.

Tests import this module and call the phase functions at tiny shapes with
``strict=False`` (CPU, Pallas interpret mode); the command line has no
switch that weakens the run.
"""

import functools
import json
import os
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
TOTAL_BUDGET_S = 1150.0  # the caller allows 1200 s, compilation included
PHASES = ("kernels", "train", "train_warm", "serve", "four_chip")

# relative L2 error against the f32 reference, by input dtype (bf16 has
# ~3 decimal digits; the same bound tests/unit/test_ops.py uses)
REL_L2_TOL = {"bfloat16": 3e-2, "float32": 2e-3}
# the flash kernels' own: twice what they read on the v5e (0.0020-0.0032 at
# 512, 1,024 and 4,096 positions, PERF.md section 6, PR 45, where the
# kernels before it read 0.0019-0.0026; the lse within 0.005 of theirs).
# They round ``q * scale`` to bf16
# once more than ``scale * dot`` in float32 did: a change that rounds yet
# more shows here, not under the 3e-2 every bf16 kernel is given
FLASH_REL_L2_TOL = {"bfloat16": 6e-3, "float32": 2e-3}
FLASH_LSE_TOL = {"bfloat16": 2e-2, "float32": 1e-3}
# two runs of the same seeded bf16 training (fresh process, or ZeRO-3 over
# four chips on the same effective batch) may differ by reduction order
LOSS_TRAJECTORY_TOL = 0.1
# what restricts a child to the host's first chip (libtpu reads these at
# start-up); set only on hosts where the first child saw more than one
ONE_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"}


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# device check + compile accounting (children only: these import JAX)
# ---------------------------------------------------------------------------
def device_report() -> dict:
    """Print the device as JAX reports it plus versions and the compile
    cache directory; raise SystemExit unless it is a TPU in the peak
    table. Runs before any model is built."""
    import jax
    import jaxlib

    from deepspeed_tpu.profiling.step_profiler import peak_tflops
    from deepspeed_tpu.utils.compile_cache import ensure_compile_cache

    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # reported as null; no TPU without it
        libtpu = None
    devs = jax.devices()
    info = {
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "compile_cache_dir": ensure_compile_cache(),
        "compile_cache_env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    }
    emit(info)
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX platform is {devs[0].platform!r}, not 'tpu' "
            "— refusing to run (there is no CPU fallback)")
    peak_tflops(devs[0])  # raises on a device_kind the table lacks
    return info


class CompileCounter:
    """XLA backend compilations and persistent-cache hits since it was
    made, read from the program's own build log (telemetry/builds.py: a hit
    is a row too, its duration the retrieval and load)."""

    def __init__(self):
        from deepspeed_tpu.telemetry.builds import build_log

        build_log.listen()      # the kernels phase passes no entry point
        self._rows, self._mark = build_log.rows, len(build_log.rows)

    def _compiled(self):
        return [r for r in self._rows[self._mark:]
                if r["stage"] == "compile_or_load"]

    @property
    def compiles(self) -> int:
        return len(self._compiled())

    def named(self, program: str) -> int:
        return sum(1 for r in self._compiled() if r["program"] == program)

    def snapshot(self) -> dict:
        rows = self._compiled()
        return {"n_compiles": len(rows),
                "compile_s": round(sum(r["end"] - r["start"]
                                       for r in rows), 2),
                "persistent_cache_hits": sum(
                    1 for r in rows if r["cache_hit"])}


def builds_report(events, floor_s=0.1) -> dict:
    """Where a phase's set-up went, by the program's own account: the
    seconds of each build stage (unions), and one row per program built
    (its ``program.built`` event) that took ``floor_s`` or more; the quick
    ones, mostly one-operation eager programs, are summed."""
    from deepspeed_tpu.telemetry.builds import build_log

    fields = ("program", "key", "nth", "trace_s", "lower_s",
              "compile_or_load_s", "cache_hit", "since_entry_s")
    costs = [sum(ev.get(k, 0.0) for k in fields[3:6]) for ev in events]
    slow = [ev for ev, c in zip(events, costs) if c >= floor_s]
    return {
        "stage_seconds": {k: round(v, 3) for k, v in
                          build_log.snapshot()["seconds"].items()},
        "programs_built": len(events),
        "first_dispatch_s": round(sum(
            d["first_dispatch_s"] for d in build_log.dispatches), 3),
        "columns": list(fields),
        "programs": [[round(ev[k], 3) if isinstance(ev.get(k), float)
                      else ev.get(k) for k in fields] for ev in slow],
        "quicker_programs": {
            "count": len(events) - len(slow),
            "seconds": round(sum(c for c in costs if c < floor_s), 3)},
    }


def _memory(device=None) -> dict:
    import jax

    # live buffers and XLA's per-program temporaries are two pools on the
    # TPU allocator: peak_bytes_in_use does not include the latter
    stats = (device or jax.local_devices()[0]).memory_stats() or {}
    return {k: stats.get(k) for k in (
        "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")}


def _rel_l2(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def _mosaic_calls(hlo_text: str) -> int:
    return hlo_text.count('custom_call_target="tpu_custom_call"')


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def _attention_vs_reference(what, attn, ref_attn, q, k, v, w, strict,
                            tols=REL_L2_TOL):
    """Forward and backward of kernel ``attn(q, k, v)`` against
    ``ref_attn`` on the f32 upcast of the same inputs (loss = sum(o * w)).
    Returns (rel-L2 errors of o/dq/dk/dv, their tolerance, Mosaic calls in
    the compiled kernel step); raises when an error exceeds the tolerance
    or, if ``strict``, when fwd + dq + dkv are not three Mosaic calls."""
    import jax
    import jax.numpy as jnp

    def loss(fn, q, k, v):
        o = fn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(fn, q, k, v), argnums=(0, 1, 2),
            has_aux=True))

    step = value_and_grads(attn)
    (_, o), grads = step(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, o_ref), grads_ref = value_and_grads(ref_attn)(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    errs = {"o": _rel_l2(o, o_ref)}
    errs.update({f"d{n}": _rel_l2(g, gr)
                 for n, g, gr in zip("qkv", grads, grads_ref)})
    tol = tols[jnp.dtype(q.dtype).name]
    bad = {n: e for n, e in errs.items() if not e < tol}
    if bad:
        raise AssertionError(f"{what}: rel-L2 {bad} > {tol}")
    mosaic = _mosaic_calls(step.lower(q, k, v).compile().as_text())
    if strict and mosaic < 3:
        raise AssertionError(
            f"{what}: expected fwd+dq+dkv Mosaic custom calls in the "
            f"compiled HLO, found {mosaic}")
    return {n: round(e, 5) for n, e in errs.items()}, tol, mosaic


def _check_flash(t, d, heads, batch, dtype, segments: bool, strict: bool):
    """flash fwd+bwd (the schedule ``resolve_schedule`` gives the shape,
    printed as ``plan``) vs f32 einsum, at the kernels' own tolerance; the
    forward's saved logsumexp vs the f32 scores' (without segments)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.flash_attention import (
        _fwd,
        flash_attention,
        resolve_schedule,
        schedule_plan,
    )

    rng = np.random.RandomState(t + d + int(segments))
    shape = (batch, t, heads, d)
    q, k, v, w = (jnp.asarray(rng.randn(*shape), dtype) for _ in range(4))
    seg = None
    if segments:
        # packed rows: ragged documents plus trailing padding (segment 0)
        cuts = np.sort(rng.randint(1, t - t // 8, size=(batch, 3)), axis=1)
        pos = np.arange(t)[None, :]
        seg_np = 1 + (pos >= cuts[:, :1]) + (pos >= cuts[:, 1:2]) \
            + (pos >= cuts[:, 2:3])
        seg_np = np.where(pos >= t - t // 8, 0, seg_np)
        seg = jnp.asarray(seg_np, jnp.int32)

    def ref_attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        keep = jnp.tril(jnp.ones((t, t), bool))[None, None]
        if seg is not None:
            keep = keep & (seg[:, None, :, None] == seg[:, None, None, :])
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    schedule, source = resolve_schedule(t, d, dtype, True,
                                        lane_aligned=segments)
    errs, tol, mosaic = _attention_vs_reference(
        f"flash t={t} d={d} segments={segments}",
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        segment_ids=seg),
        ref_attn, q, k, v, w, strict, tols=FLASH_REL_L2_TOL)
    out = {"kernel": "flash", "seq": t, "head_dim": d, "heads": heads,
           "batch": batch, "dtype": jnp.dtype(dtype).name,
           "segments": segments,
           "plan": {"source": source, **schedule_plan(t, True, schedule)},
           "mosaic_calls": mosaic, "tol": tol, "rel_l2": errs}
    if not segments:
        scale = 1.0 / np.sqrt(d)
        lse = jax.jit(lambda q, k, v: _fwd(q, k, v, None, scale, True,
                                           schedule[0])[1])(q, k, v)

        def lse_ref(q, k):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
            return jax.nn.logsumexp(s, axis=-1).reshape(batch * heads, t)

        with jax.default_matmul_precision("highest"):
            want = jax.jit(lse_ref)(q.astype(jnp.float32),
                                    k.astype(jnp.float32))
        out["lse_max_abs"] = float(jnp.max(jnp.abs(lse[..., 0] - want)))
        out["lse_tol"] = FLASH_LSE_TOL[jnp.dtype(dtype).name]
        if not out["lse_max_abs"] < out["lse_tol"]:
            raise AssertionError(
                f"flash t={t} d={d}: lse off by {out['lse_max_abs']} > "
                f"{out['lse_tol']}")
    return out


def _check_fused_adam(shape, strict: bool):
    """Pallas fused AdamW on one bf16 leaf vs the same update in jnp."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.fused_adam import fused_adamw_update

    rng = np.random.RandomState(7)
    p = jnp.asarray(rng.randn(*shape) * 0.02, jnp.bfloat16)
    g = jnp.asarray(rng.randn(*shape) * 1e-2, jnp.float32)
    m = jnp.asarray(rng.randn(*shape) * 1e-3, jnp.float32)
    v = jnp.asarray(rng.rand(*shape) * 1e-5, jnp.float32)
    hp = dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    lr, step = 2e-4, 3

    fused = jax.jit(lambda p, g, m, v: fused_adamw_update(
        p, g, m, v, lr, step, **hp))
    pn, mn, vn = fused(p, g, m, v)

    def ref(p, g, m, v):
        m2 = hp["b1"] * m + (1 - hp["b1"]) * g
        v2 = hp["b2"] * v + (1 - hp["b2"]) * g * g
        upd = (m2 / (1 - hp["b1"] ** step)) / (
            jnp.sqrt(v2 / (1 - hp["b2"] ** step)) + hp["eps"])
        p32 = p.astype(jnp.float32)
        return p32 - lr * (upd + hp["weight_decay"] * p32), m2, v2

    pr, mr, vr = jax.jit(ref)(p, g, m, v)
    # m and v stay f32; the param comes back rounded to bf16, so it can
    # only agree with the f32 result to bf16 resolution
    errs = {"m": _rel_l2(mn, mr), "v": _rel_l2(vn, vr),
            "p": _rel_l2(pn, pr)}
    tols = {"m": REL_L2_TOL["float32"], "v": REL_L2_TOL["float32"],
            "p": 2.0 ** -8}
    bad = {n: e for n, e in errs.items() if not e < tols[n]}
    if bad:
        raise AssertionError(f"fused adamw {shape}: rel-L2 {bad} > {tols}")
    mosaic = _mosaic_calls(fused.lower(p, g, m, v).compile().as_text())
    if strict and mosaic < 1:
        raise AssertionError("fused adamw: no Mosaic custom call in HLO")
    return {"kernel": "fused_adamw", "shape": list(shape),
            "mosaic_calls": mosaic, "tol": tols,
            "rel_l2": {n: round(e, 6) for n, e in errs.items()}}


def _check_splash(t, block, heads, d, dtype, strict: bool):
    """The block-sparse (splash) kernel on the BigBird layout
    benchmarks/smoke.py trains BERT-L under, vs the masked-dense path."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig
    from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
        block_sparse_attention,
        dense_blocksparse_attention,
    )

    layout = np.asarray(BigBirdSparsityConfig(
        num_heads=heads, block=block, num_random_blocks=1,
        num_sliding_window_blocks=3, num_global_blocks=1,
    ).make_layout(t))
    rng = np.random.RandomState(11)
    shape = (1, t, heads, d)
    q, k, v, w = (jnp.asarray(rng.randn(*shape), dtype) for _ in range(4))

    kw = dict(layout=layout, block=block, causal=False)
    errs, tol, mosaic = _attention_vs_reference(
        f"splash t={t}", functools.partial(block_sparse_attention, **kw),
        functools.partial(dense_blocksparse_attention, **kw),
        q, k, v, w, strict)
    return {"kernel": "splash_bigbird", "seq": t, "block": block,
            "heads": heads, "head_dim": d, "dtype": jnp.dtype(dtype).name,
            "active_blocks": int(layout.sum()), "mosaic_calls": mosaic,
            "tol": tol, "rel_l2": errs}


def _check_decode_attention(layers, lanes, positions, kv_heads, heads, d,
                            dtype, strict: bool):
    """The block-skipping decode attention over a stacked KV leaf, lanes
    with left padding and clocks all over the cache (one with nothing
    visible, one full), vs the masked softmax on the f32 upcast."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.randn(lanes, heads, d), dtype)
    kc, vc = (jnp.asarray(rng.randn(layers, lanes, positions, kv_heads, d),
                          dtype) for _ in range(2))
    first = rng.randint(0, positions // 2, lanes)
    clock = np.minimum(first + rng.randint(0, positions, lanes),
                       positions - 1)
    first[0], clock[0] = positions - 1, 0      # nothing visible
    first[-1], clock[-1] = 0, positions - 1    # every row
    valid = jnp.asarray(np.arange(positions)[None, :] >= first[:, None])
    layer = layers - 1
    fn = jax.jit(decode_attention)
    got = fn(q, kc, vc, valid, jnp.asarray(clock), jnp.int32(layer))
    visible = valid & (jnp.arange(positions)[None, :]
                       <= jnp.asarray(clock)[:, None])
    qg = q.astype(jnp.float32).reshape(lanes, kv_heads, heads // kv_heads, d)
    att = jnp.einsum("bhgd,bkhd->bhgk", qg,
                     kc[layer].astype(jnp.float32)) / np.sqrt(d)
    att = jax.nn.softmax(
        jnp.where(visible[:, None, None, :], att, -1e30), axis=-1)
    ref = jnp.einsum("bhgk,bkhd->bhgd", att,
                     vc[layer].astype(jnp.float32)).reshape(lanes, heads, d)
    mosaic = _mosaic_calls(fn.lower(
        q, kc, vc, valid, jnp.asarray(clock),
        jnp.int32(layer)).compile().as_text())
    err = _rel_l2(got[1:], ref[1:])
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    if not bool(jnp.isfinite(got.astype(jnp.float32)).all()) or err > tol:
        raise AssertionError(f"decode_attention rel-L2 {err:.3e} > {tol}")
    if strict and mosaic != 1:
        raise AssertionError(f"decode_attention: {mosaic} Mosaic calls")
    return {"kernel": "decode_attn", "layers": layers, "lanes": lanes,
            "positions": positions, "kv_heads": kv_heads, "heads": heads,
            "head_dim": d, "dtype": jnp.dtype(dtype).name,
            "mosaic_calls": mosaic, "tol": tol, "rel_l2": round(err, 6)}


def _scattered(rows, ok, positions):
    """``[lanes, positions]`` bool on the host: True at ``rows`` where
    ``ok``."""
    import numpy as np

    mine = np.zeros((rows.shape[0], positions), bool)
    np.logical_or.at(mine, (np.arange(rows.shape[0])[:, None],
                            np.asarray(rows)), np.asarray(ok))
    return mine


def _check_selected_attention(layers, lanes, positions, kv_heads, heads, d,
                              ix_heads, ix_dim, topk, dtype, strict: bool):
    """One decode step of attention over the rows an indexer chooses
    (ops/indexed_attention.py ``decode_step``: scores over a layer's index
    keys, ``top_k``'s set of them by a threshold (``_check_selection`` has
    that alone), then the chosen rows out of the stacked leaves, either
    gathered or as the lanes' live blocks under the chosen mask by the
    kernel ``decode_attn``, as ``reads_blocks`` says of the lanes' clocks),
    lanes with left padding and a sixth to five sixths of the cache live
    (one under ``topk``, one full), vs its plain form on the f32 upcast:
    dense scores, the ``topk`` best of each lane's visible positions as a
    mask computed ON THE HOST, a masked softmax over every position. Twice:
    at ``topk``, where the rule reads blocks, and at a quarter of it, where
    it gathers; the program holds both forms (one Mosaic call), and each
    form is also run alone on the first case's rows. The sets may differ by
    a position at the boundary where two float32 scores differ in their
    last bit. On the chip (``strict``) it also times each form alone, which is
    what the rule's two constants rest on: every lane holding a quarter, a
    half and the whole of the cache, and a mix of short and long lanes; the
    result is written to ``chiprun_out/selected.json`` too."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import indexed_attention as ia
    from deepspeed_tpu.ops.pallas.decode_attention import (
        block_positions,
        live_blocks,
    )

    rng = np.random.RandomState(17)
    q = jnp.asarray(rng.randn(lanes, heads, d), dtype)
    q_idx = jnp.asarray(rng.randn(lanes, ix_heads, ix_dim), dtype)
    w = jnp.asarray(rng.randn(lanes, ix_heads) * 0.03, jnp.float32)
    kc, vc = (jnp.asarray(rng.randn(layers, lanes, positions, kv_heads, d),
                          dtype) for _ in range(2))
    ki = jnp.asarray(rng.randn(layers, lanes, positions, ix_dim), dtype)
    rng = np.random.RandomState(18)
    live = rng.randint(positions // 6, positions * 5 // 6 + 1, lanes)
    live[0], live[-1] = max(topk // 2, 1), positions   # under topk; all
    first = np.array([rng.randint(0, positions - n + 1) for n in live])
    clock = first + live - 1
    at = np.arange(positions)[None, :]
    visible = jnp.asarray((at >= first[:, None]) & (at <= clock[:, None]))
    layer = jnp.int32(layers - 1)
    scale = 1.0 / np.sqrt(d)
    block = block_positions(positions, kv_heads, d, jnp.dtype(dtype).itemsize)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4

    with jax.default_matmul_precision("highest"):
        dots = jnp.einsum("bjd,bsd->bjs", q_idx.astype(jnp.float32),
                          ki[layers - 1].astype(jnp.float32))
        index = np.asarray(jnp.sum(jax.nn.relu(dots) * w[..., None], 1))
        qg = q.astype(jnp.float32).reshape(lanes, kv_heads,
                                           heads // kv_heads, d)
        att = jnp.einsum("bhgd,bkhd->bhgk", qg,
                         kc[layers - 1].astype(jnp.float32)) * scale

    def plain(k):
        """The plain form's own choice of ``k``, by no function of the
        module under test: a stable sort of the float32 scores on the
        host (ties to the lower row), the rows a lane cannot see last."""
        order = np.argsort(-np.where(np.asarray(visible), index, -np.inf),
                           axis=1, kind="stable")
        chosen = np.zeros((lanes, positions), bool)
        for b, n in enumerate(np.minimum(live, k)):
            chosen[b, order[b, :n]] = True
        with jax.default_matmul_precision("highest"):
            p = jax.nn.softmax(jnp.where(
                jnp.asarray(chosen)[:, None, None, :], att, -1e30), axis=-1)
            ref = jnp.einsum("bhgk,bkhd->bhgd", p,
                             vc[layers - 1].astype(jnp.float32))
        return chosen, ref.reshape(lanes, heads, d)

    # each form alone: the two branches of ``decode_step``'s conditional
    # (the leaves are arguments: a closed-over array is a constant of the
    # program, 1.6 GB each at the cell's shape)
    forms = {
        "blocks": jax.jit(lambda kc, vc, chosen, r, o, c:
                          ia.attend_chosen_blocks(
                              q, kc, vc, layer, chosen, c, block, dtype)),
        "rows": jax.jit(lambda kc, vc, chosen, r, o, c:
                        ia.attend_chosen_rows(q, kc, vc, layer, r, o, scale,
                                              dtype))}
    cases, mosaic = [], None
    for k in (topk, max(topk // 4, 1)):
        fn = jax.jit(lambda *a, k=k: ia.decode_step(*a, k, dtype))
        args = (q, q_idx, w, kc, vc, ki, layer, visible,
                jnp.asarray(clock, jnp.int32))
        got, rows, ok = fn(*args)
        chosen, ref = plain(k)
        mine, want = _scattered(rows, ok, positions), np.minimum(live, k)
        if not (mine.sum(1) == want).all() \
                or (mine & ~np.asarray(visible)).any():
            raise AssertionError("decode_step chose rows a lane cannot see, "
                                 f"or not min(topk, live): {mine.sum(1)}")
        agree = float((mine & chosen).sum() / want.sum())
        err = _rel_l2(got, ref)
        if not bool(jnp.isfinite(got.astype(jnp.float32)).all()) \
                or err > tol or agree < 0.999:
            raise AssertionError(
                f"selected attention (topk {k}) rel-L2 {err:.3e} > {tol} or "
                f"sets agree {agree:.5f} < 0.999")
        cases.append({"topk": k, "reads_blocks": bool(ia.reads_blocks(
            jnp.asarray(first, jnp.int32), jnp.asarray(clock, jnp.int32),
            block, min(k, positions))),
            "sets_agree": round(agree, 6), "rel_l2": round(err, 6)})
        if mosaic is None:
            mosaic = _mosaic_calls(fn.lower(*args).compile().as_text())
            mask = ia.chosen_set(jnp.asarray(index), visible, k)
            alone = {name: _rel_l2(form(kc, vc, mask, rows, ok, args[-1]),
                                   ref) for name, form in forms.items()}
            if max(alone.values()) > tol:
                raise AssertionError(f"a form alone: rel-L2 {alone} > {tol}")
    if [c["reads_blocks"] for c in cases] != [True, False]:
        raise AssertionError(f"the rule did not take both sides: {cases}")
    if strict and mosaic != 1:
        raise AssertionError(f"selected attention: {mosaic} Mosaic calls")

    cost = "not measured (no chip)"
    if strict:
        cost = []
        gathered = lanes * min(topk, positions)
        whole = np.full(lanes, positions)
        mix = np.where(np.arange(lanes) % 2, positions, min(topk, positions))
        for name, held in (("quarter", whole // 4), ("half", whole // 2),
                           ("whole", whole), ("short_and_long", mix)):
            vis = jnp.asarray(at < held[:, None])
            clk = jnp.asarray(held - 1, jnp.int32)
            mask = ia.chosen_set(jnp.asarray(index), vis, topk)
            rows, ok = ia.choose(jnp.asarray(index), vis, topk)
            lo, hi = live_blocks(np.zeros(lanes, int), held - 1, block)
            read = int(((hi - lo + 1) * block).sum())
            times = {}
            for form_name, form in forms.items():
                form(kc, vc, mask, rows, ok, clk).block_until_ready()
                t1 = time.perf_counter()
                for _ in range(20):
                    out = form(kc, vc, mask, rows, ok, clk)
                out.block_until_ready()
                times[form_name] = (time.perf_counter() - t1) / 20
            cost.append({
                "lanes_hold": name, "live": int(held.sum()),
                "positions_in_blocks": read,
                "blocks_ms": round(times["blocks"] * 1e3, 4),
                "rows_ms": round(times["rows"] * 1e3, 4),
                "ns_a_position_in_blocks": round(
                    times["blocks"] * 1e9 / read, 3),
                "ns_a_chosen_position_by_rows": round(
                    times["rows"] * 1e9 / gathered, 3),
                "rule_reads_blocks": bool(ia.reads_blocks(
                    jnp.zeros(lanes, jnp.int32), clk, block,
                    min(topk, positions)))})
    out = {"kernel": "selected_attention (decode_attn | XLA gathers)",
           "layers": layers, "lanes": lanes, "positions": positions,
           "kv_heads": kv_heads, "heads": heads, "head_dim": d,
           "index_heads": ix_heads, "index_dim": ix_dim, "topk": topk,
           "block": block, "live": [int(live.min()), int(live.max())],
           "dtype": jnp.dtype(dtype).name, "mosaic_calls": mosaic,
           "tol": tol, "cases": cases,
           "alone_rel_l2": {n: round(e, 6) for n, e in alone.items()},
           "rule_ns": {"a_position_in_blocks": ia._NS_A_BLOCK_POSITION,
                       "a_chosen_row": ia._NS_A_CHOSEN_ROW},
           "cost": cost}
    if strict:
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "selected.json"),
                  "w") as fh:
            json.dump(out, fh, indent=1)
    return out


def _check_retention_step(layers, lanes, kv_heads, heads, d, strict: bool):
    """One token of power retention through the kernel that walks the
    stacked state where it lies, vs the plain ``retention_step`` on that
    layer's slice; the other layers must come back untouched."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import power_retention as pr

    rng = np.random.RandomState(17)
    D = pr.sympow2_width(d)
    S = jnp.asarray(rng.randn(layers, lanes, kv_heads, d, D), jnp.float32)
    z = jnp.asarray(np.abs(rng.randn(layers, lanes, kv_heads, D)),
                    jnp.float32)
    q = jnp.asarray(1.0 + rng.randn(lanes, heads, d), jnp.float32)
    k, v = (jnp.asarray(1.0 + rng.randn(lanes, kv_heads, d), jnp.float32)
            for _ in range(2))
    log_g = jnp.asarray(-np.abs(rng.randn(lanes, kv_heads)) * 0.05,
                        jnp.float32)
    layer = layers - 1
    want = pr.retention_step(S[layer], z[layer], q, k, v, log_g, 1e-6)
    untouched = np.asarray(S[0])
    fn = jax.jit(pr.retention_step_stacked, donate_argnums=(0, 1))
    mosaic = _mosaic_calls(fn.lower(
        S, z, jnp.int32(layer), q, k, v, log_g, 1e-6).compile().as_text())
    y, S, z = fn(S, z, jnp.int32(layer), q, k, v, log_g, 1e-6)
    errs = {"y": _rel_l2(y, want[0]), "S": _rel_l2(S[layer], want[1]),
            "z": _rel_l2(z[layer], want[2])}
    tol = 1e-4
    if max(errs.values()) > tol or not np.array_equal(
            np.asarray(S[0]), untouched):
        raise AssertionError(f"retention_step rel-L2 {errs} > {tol}")
    if strict and mosaic != 1:
        raise AssertionError(f"retention_step: {mosaic} Mosaic calls")
    return {"kernel": "ret_step", "layers": layers, "lanes": lanes,
            "kv_heads": kv_heads, "heads": heads, "head_dim": d,
            "mosaic_calls": mosaic, "tol": tol,
            "rel_l2": {n: round(e, 8) for n, e in errs.items()}}


def _check_ssd_step(layers, lanes, heads, groups, d_head, d_state,
                    strict: bool):
    """One token of the Mamba-2 recurrence through the kernel that walks
    the stacked state where it lies, vs the plain ``ssd_step`` on that
    layer's slice; the other layers must come back untouched."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import ssd

    rng = np.random.RandomState(19)
    S = jnp.asarray(rng.randn(layers, lanes, heads, d_head, d_state),
                    jnp.float32)
    x = jnp.asarray(rng.randn(lanes, heads, d_head), jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(rng.randn(lanes, heads) - 2.0)),
                     jnp.float32)
    A = -jnp.exp(jnp.asarray(rng.randn(heads), jnp.float32))
    Bm, Cm = (jnp.asarray(rng.randn(lanes, groups, d_state), jnp.float32)
              for _ in range(2))
    D = jnp.asarray(rng.randn(heads), jnp.float32)
    layer = layers - 1
    want = ssd.ssd_step(S[layer], x, dt, A, Bm, Cm, D)
    untouched = np.asarray(S[0])
    fn = jax.jit(ssd.ssd_step_stacked, donate_argnums=(0,))
    mosaic = _mosaic_calls(fn.lower(
        S, jnp.int32(layer), x, dt, A, Bm, Cm, D).compile().as_text())
    y, S = fn(S, jnp.int32(layer), x, dt, A, Bm, Cm, D)
    errs = {"y": _rel_l2(y, want[0]), "S": _rel_l2(S[layer], want[1])}
    tol = 1e-4
    if max(errs.values()) > tol or not np.array_equal(
            np.asarray(S[0]), untouched):
        raise AssertionError(f"ssd_step rel-L2 {errs} > {tol}")
    if strict and mosaic != 1:
        raise AssertionError(f"ssd_step: {mosaic} Mosaic calls")
    return {"kernel": "ssm_step", "layers": layers, "lanes": lanes,
            "heads": heads, "groups": groups, "d_head": d_head,
            "d_state": d_state, "mosaic_calls": mosaic, "tol": tol,
            "rel_l2": {n: round(e, 8) for n, e in errs.items()}}


def _check_grouped_matmul(rows, d_in, d_out, groups, dtype, strict: bool):
    """The experts' grouped matmul through the kernels, forward and both
    gradients, vs ``jax.lax.ragged_dot`` and its autodiff on ragged groups
    that end inside tiles, one of them empty, the last rows uncovered."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

    rng = np.random.RandomState(23)
    lhs = jnp.asarray(rng.randn(rows, d_in), dtype)
    rhs = jnp.asarray(rng.randn(groups, d_in, d_out) / np.sqrt(d_in), dtype)
    cot = jnp.asarray(rng.randn(rows, d_out), dtype)
    sizes = rng.multinomial(rows - rows // 16,
                            rng.dirichlet(np.ones(groups)))
    sizes[groups // 2] = 0
    sizes = jnp.asarray(sizes, jnp.int32)

    def both(matmul):
        def run(lhs, rhs, sizes, cot):
            out, vjp = jax.vjp(lambda a, w: matmul(a, w, sizes), lhs, rhs)
            return (out,) + vjp(cot)
        return jax.jit(run)

    fn = both(grouped_matmul)
    mosaic = _mosaic_calls(
        fn.lower(lhs, rhs, sizes, cot).compile().as_text())
    got, want = fn(lhs, rhs, sizes, cot), both(jax.lax.ragged_dot)(
        lhs, rhs, sizes, cot)
    # over the rows the groups cover: past them the kernels give zeros,
    # which is what the program counts dropped pairs from, and what the
    # compiler's call leaves there on the TPU is reported, not compared
    covered = int(sizes.sum())
    errs = {"out": _rel_l2(got[0][:covered], want[0][:covered]),
            "d_lhs": _rel_l2(got[1][:covered], want[1][:covered]),
            "d_rhs": _rel_l2(got[2], want[2])}
    past = {n: float(np.abs(np.asarray(t[0][covered:], np.float32)).max())
            for n, t in (("kernel", got), ("ragged_dot", want))}
    tol = 4e-3  # both round an f32 sum once to bf16, in another order
    if max(errs.values()) > tol or past["kernel"] != 0 or np.asarray(
            got[1][covered:], np.float32).any():
        raise AssertionError(
            f"grouped_matmul rel-L2 {errs} > {tol}, or rows past the "
            f"groups not zero: {past}")
    if strict and mosaic != 3:
        raise AssertionError(f"grouped_matmul: {mosaic} Mosaic calls")
    return {"kernel": "ragged-dot-gmm", "rows": rows, "d_in": d_in,
            "d_out": d_out, "groups": groups, "dtype": jnp.dtype(dtype).name,
            "mosaic_calls": mosaic, "tol": tol,
            "largest_past_the_groups": past,
            "rel_l2": {n: round(e, 8) for n, e in errs.items()}}


def _deepseek_sizes(heads, q_rank, kv_rank, nope, rope, v_dim, routed_over,
                    held, n_group, topk_group, top_k):
    """``perfbench/reference/deepseek_v2.py``'s sizes for a check's
    shapes, with the published YaRN numbers."""
    from perfbench.reference import deepseek_v2 as reference

    return reference.sizes({
        "attention_bias": False, "topk_method": "group_limited_greedy",
        "scoring_func": "softmax", "norm_topk_prob": False,
        "moe_layer_freq": 1, "hidden_act": "silu",
        "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
        "num_attention_heads": heads, "qk_nope_head_dim": nope,
        "qk_rope_head_dim": rope, "v_head_dim": v_dim,
        "kv_lora_rank": kv_rank, "rope_theta": 10000,
        "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 0.707,
                         "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096},
        "first_k_dense_replace": 1, "n_group": n_group,
        "topk_group": topk_group, "num_experts_per_tok": top_k,
        "routed_scaling_factor": 16,
        "moe": {"routed_over": routed_over, "experts_held": list(held)}})


def _deepseek_config(hidden, heads, q_rank, kv_rank, nope, rope, v_dim,
                     positions, dtype, **moe):
    from deepspeed_tpu.models.transformer_lm import GPTConfig, MLAConfig

    return GPTConfig(
        vocab_size=256, n_positions=positions, n_embd=hidden, n_layer=1,
        n_head=heads, norm="rmsnorm", layer_norm_epsilon=1e-6,
        activation="silu", gated_mlp=True, use_bias=False, rotary=True,
        learned_positions=False, tie_word_embeddings=False, dtype=dtype,
        param_dtype=dtype, use_flash_attention=False,
        mla=MLAConfig(q_rank=q_rank, kv_rank=kv_rank, nope_dim=nope,
                      rope_dim=rope, v_dim=v_dim, yarn_factor=40.0,
                      yarn_original_positions=4096, yarn_mscale=0.707,
                      yarn_mscale_all_dim=0.707), **moe)


def _check_latent_decode(lanes, positions, hidden, heads, q_rank, kv_rank,
                         nope, rope, v_dim, dtype, strict: bool):
    """One decode step of latent attention, the absorbed form over a
    lane cache of ragged lengths, vs the plain reference's NON-absorbed
    sum over the same cached latents (every position's keys and values
    decompressed per head, float32). The cached rows are random: the
    check is of this step's arithmetic, not of how they came to be."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.latent_attention import Lane, LatentAttention
    from perfbench.reference import deepseek_v2 as reference

    cfg = _deepseek_config(hidden, heads, q_rank, kv_rank, nope, rope,
                           v_dim, positions, dtype)
    s = _deepseek_sizes(heads, q_rank, kv_rank, nope, rope, v_dim, 16,
                        (0, 2), 8, 3, 6)
    rng = np.random.RandomState(23)
    held = rng.randint(positions // 4, positions - 1, size=lanes)
    first = rng.randint(0, 8, size=lanes)           # a bucket's padding
    rows = np.arange(positions)
    valid = (rows[None] >= first[:, None]) & (rows[None] <= held[:, None])
    lane = Lane(
        latent=jnp.asarray(rng.randn(1, lanes, positions, kv_rank), dtype),
        rope_key=jnp.asarray(rng.randn(1, lanes, positions, rope), dtype),
        valid=jnp.asarray(valid), index=jnp.asarray(held, jnp.int32),
        fresh=False)
    x = jnp.asarray(rng.randn(lanes, 1, hidden), dtype)
    mixer = LatentAttention(cfg)
    params = mixer.init(jax.random.PRNGKey(3), x)["params"]

    def step(params, x, lane):
        return mixer.apply({"params": params}, x, lane=lane, cache_layer=0)

    fn = jax.jit(step)
    y, after = fn(params, x, lane)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def plain(b):
        """The reference's per-head sum for lane ``b``."""
        n, t = int(held[b]), int(held[b]) + 1
        xb = x[b].astype(jnp.float32)                        # [1, hidden]
        c_q = reference.rms_norm(
            reference.mm(xb, p32["q_a"]["kernel"]),
            p32["q_a_norm"]["scale"], s["eps"])
        q = reference.mm(c_q, p32["q_b"]["kernel"]).reshape(
            1, heads, nope + rope)
        q_rope = reference.rotary(q[..., nope:], s, n)
        c_new, r_new = reference.latents(xb, p32, s, n)
        c = jnp.concatenate([lane.latent[0, b, :n].astype(jnp.float32),
                             c_new])
        r = jnp.concatenate([lane.rope_key[0, b, :n].astype(jnp.float32),
                             r_new])
        kv = reference.mm(c, p32["kv_b"]["kernel"]).reshape(
            t, heads, nope + v_dim)
        scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope],
                             precision=reference.HIGHEST)
                  + jnp.einsum("qhd,kd->hqk", q_rope, r,
                               precision=reference.HIGHEST)) * s["scale"]
        seen = jnp.asarray(valid[b, :t] | (rows[:t] == n))
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               -1)
        out = jnp.einsum("hqk,khd->qhd", probs, kv[..., nope:],
                         precision=reference.HIGHEST)
        return reference.mm(out.reshape(1, heads * v_dim),
                            p32["c_proj"]["kernel"]), c_new, r_new

    errs = {"y": 0.0, "latent": 0.0, "rope_key": 0.0}
    for b in range(lanes):
        want, c_new, r_new = plain(b)
        n = int(held[b])
        errs["y"] = max(errs["y"], _rel_l2(y[b], want))
        errs["latent"] = max(errs["latent"],
                             _rel_l2(after.latent[0, b, n], c_new[0]))
        errs["rope_key"] = max(errs["rope_key"],
                               _rel_l2(after.rope_key[0, b, n], r_new[0]))
    tol = 1e-4 if jnp.dtype(dtype) == jnp.float32 else 2e-2
    if max(errs.values()) > tol:
        raise AssertionError(f"latent decode step rel-L2 {errs} > {tol}")
    return {"kernel": "mla_absorbed_decode", "lanes": lanes,
            "positions": positions, "heads": heads, "kv_rank": kv_rank,
            "rope_dim": rope, "tol": tol,
            "rel_l2": {n: round(e, 8) for n, e in errs.items()}}


def _check_latent_decode_attention(layers, lanes, positions, heads, kv_rank,
                                   rope, dtype, strict: bool):
    """The latent decode kernel over the stacked latent and rotary-key
    leaves, lanes with left padding and clocks all over the cache (one
    with nothing visible, one full), at the model's own block, vs the
    masked softmax over the f32 upcast of the same rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.latent_decode_attention import (
        block_positions,
        latent_decode_attention,
        step_plan,
    )

    rng = np.random.RandomState(29)
    q_lat = jnp.asarray(rng.randn(lanes, heads, kv_rank), dtype)
    q_rope = jnp.asarray(rng.randn(lanes, heads, rope), dtype)
    latent = jnp.asarray(rng.randn(layers, lanes, positions, kv_rank), dtype)
    rope_key = jnp.asarray(rng.randn(layers, lanes, positions, rope), dtype)
    first = rng.randint(0, 64, lanes)               # a bucket's padding
    clock = np.minimum(first + rng.randint(0, positions, lanes),
                       positions - 1)
    first[0], clock[0] = positions - 1, 0      # nothing visible
    first[-1], clock[-1] = 0, positions - 1    # every row
    valid = jnp.asarray(np.arange(positions)[None, :] >= first[:, None])
    layer, scale = layers - 1, 1.0 / np.sqrt(kv_rank // 4 + rope)
    block = block_positions(positions, kv_rank, jnp.dtype(dtype).itemsize)

    def kernel(q_lat, q_rope, latent, rope_key, valid, clock, layer):
        return latent_decode_attention(
            q_lat, q_rope, latent, rope_key, step_plan(valid, clock, block),
            layer, scale=scale)

    fn = jax.jit(kernel)
    args = (q_lat, q_rope, latent, rope_key, valid, jnp.asarray(clock),
            jnp.int32(layer))
    got = fn(*args)
    visible = valid & (jnp.arange(positions)[None, :]
                       <= jnp.asarray(clock)[:, None])
    lat32 = latent[layer].astype(jnp.float32)
    att = (jnp.einsum("bhr,bkr->bhk", q_lat.astype(jnp.float32), lat32,
                      precision="highest")
           + jnp.einsum("bhd,bkd->bhk", q_rope.astype(jnp.float32),
                        rope_key[layer].astype(jnp.float32),
                        precision="highest")) * scale
    att = jax.nn.softmax(jnp.where(visible[:, None, :], att, -1e30), axis=-1)
    ref = jnp.einsum("bhk,bkr->bhr", att, lat32, precision="highest")
    mosaic = _mosaic_calls(fn.lower(*args).compile().as_text())
    err = _rel_l2(got[1:], ref[1:])
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    if not bool(jnp.isfinite(got.astype(jnp.float32)).all()) or err > tol:
        raise AssertionError(
            f"latent_decode_attention rel-L2 {err:.3e} > {tol}")
    if strict and mosaic != 1:
        raise AssertionError(
            f"latent_decode_attention: {mosaic} Mosaic calls")
    return {"kernel": "mla_decode_attn", "layers": layers, "lanes": lanes,
            "positions": positions, "block": block, "heads": heads,
            "kv_rank": kv_rank, "rope_dim": rope,
            "dtype": jnp.dtype(dtype).name, "mosaic_calls": mosaic,
            "tol": tol, "rel_l2": round(err, 6)}


def _check_held_experts(tokens, hidden, width, routed_over, held, dtype,
                        strict: bool):
    """One expert layer that holds a share of the experts its router
    scores (group-limited choice of 6 among 8 groups' best 3, weights x
    16, two shared experts) vs the plain reference, which computes every
    held expert on every token and weighs it by the token's choice."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.moe.layer import MOE_STATS, MoE
    from perfbench.reference import deepseek_v2 as reference

    layer = MoE(d_model=hidden, d_hidden=width, num_experts=routed_over, k=6,
                drop_tokens=False, gated_experts=True, n_shared=2, n_group=8,
                topk_group=3, routed_scale=16.0, experts_held=held,
                dtype=dtype, param_dtype=dtype)
    s = _deepseek_sizes(4, 8, 8, 4, 4, 4, routed_over, held, 8, 3, 6)
    x = jnp.asarray(np.random.RandomState(29).randn(tokens, hidden), dtype)
    params = layer.init(jax.random.PRNGKey(5), x)["params"]

    def run(params, x):
        (y, *_), stats = layer.apply({"params": params}, x,
                                     mutable=[MOE_STATS])
        return y, stats[MOE_STATS]

    fn = jax.jit(run)
    mosaic = _mosaic_calls(fn.lower(params, x).compile().as_text())
    y, stats = fn(params, x)
    here = int(stats["routed_here"][0])
    want = reference.moe(
        x.astype(jnp.float32),
        jax.tree.map(lambda a: a.astype(jnp.float32), params), s)
    err = _rel_l2(y, want)
    tol = 1e-4 if jnp.dtype(dtype) == jnp.float32 else 2e-2
    if err > tol or int(stats["computed"][0].sum()) != here:
        raise AssertionError(
            f"held experts rel-L2 {err} > {tol}, or "
            f"{int(stats['computed'][0].sum())} pairs computed of {here}")
    if strict and mosaic != 3:
        raise AssertionError(f"held experts: {mosaic} Mosaic calls")
    return {"kernel": "moe_held_experts", "tokens": tokens, "held": held[1],
            "routed_over": routed_over, "routed_here": here,
            "mosaic_calls": mosaic, "tol": tol, "rel_l2": round(err, 8)}


def _check_grouped_matmul_stack(layers, rows, d_in, d_out, groups, dtype,
                                strict: bool):
    """The forward product inside a scan over the layers, the matrices
    read where they lie in the stack (``gmm(..., layer=)``), against the
    same kernel on each layer's slice: bitwise, and no copy of a layer's
    matrices in the compiled loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm

    rng = np.random.RandomState(29)
    lhs = jnp.asarray(rng.randn(rows, d_in), dtype)
    stack = jnp.asarray(
        rng.randn(layers, groups, d_in, d_out) / np.sqrt(d_in), dtype)
    # an eighth of the rows on the groups, as one chip's share of a layer
    sizes = jnp.asarray(rng.multinomial(rows // 8, np.ones(groups) / groups),
                        jnp.int32)

    # (the products are summed in the carry: stacked as the scan's output
    # the compiler fuses the call into the stack's update, and that fusion
    # does not fit VMEM at these widths)
    def add(acc, product):
        return acc + product.astype(jnp.float32)

    @jax.jit
    def scanned(lhs, stack, sizes):
        return jax.lax.scan(
            lambda acc, n: (add(acc, gmm(lhs, stack, sizes, layer=n)), None),
            jnp.zeros((rows, d_out), jnp.float32), jnp.arange(layers))[0]

    text = scanned.lower(lhs, stack, sizes).compile().as_text()
    mosaic = _mosaic_calls(text)
    hlo = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    copies = sum(f"= {hlo}[{groups},{d_in},{d_out}]" in line
                 and " parameter(" not in line for line in text.splitlines())
    got = np.asarray(scanned(lhs, stack, sizes))
    want = jnp.zeros((rows, d_out), jnp.float32)
    for n in range(layers):
        want = add(want, gmm(lhs, stack[n], sizes))
    want = np.asarray(want)
    if not (got == want).all() or not want.any():
        raise AssertionError("gmm over the stack is not the slices' product")
    if strict and (mosaic != 1 or copies):
        raise AssertionError(f"gmm over the stack: {mosaic} Mosaic calls, "
                             f"{copies} whole-layer results")
    return {"kernel": "ragged-dot-gmm[stack]", "layers": layers,
            "rows": rows, "d_in": d_in, "d_out": d_out, "groups": groups,
            "dtype": jnp.dtype(dtype).name, "mosaic_calls": mosaic,
            "whole_layer_results": copies, "bitwise": True}


def _check_selection(lanes, positions, topk, strict: bool):
    """The selection of a decode step alone (ops/indexed_attention.py
    ``chosen_set`` and ``choose``: the set by a search for the ``topk``-th
    largest score and among its ties, its positions in ascending order by
    a running count, nothing sorted) against ``lax.top_k`` of the masked
    scores and a scatter of its rows on the host, which it has to equal to
    the row: on random scores and on scores full of ties and zeros of both
    signs (a relu's weighted sum gives both), lanes with left padding that
    see half of ``topk``, a sixth to five sixths of the cache, and all of
    it. On the chip (``strict``) at ``positions`` and at 131,072
    positions, where it also times ``top_k`` against the selection by
    stage (each inside one program of 50 turns, so that no dispatch is in
    the time; the stages alone add up to more than the whole, whose passes
    the compiler merges); the result is written to ``chiprun_out/
    selection.json`` too."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import indexed_attention as ia

    def top_k(scores, visible):
        vals, rows = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf),
                                   min(topk, scores.shape[-1]))
        return rows, vals > -jnp.inf

    stages = {
        "top_k": top_k,
        "set": lambda s, v: ia.chosen_set(s, v, topk),
        # of a set that is at hand, handed over in ``visible``'s place
        "rows": lambda s, chosen: ia.rows_of(chosen & (s > -jnp.inf), topk),
        "set_and_rows": lambda s, v: (ia.chosen_set(s, v, topk),
                                      ia.choose(s, v, topk))}
    turns = 50

    def ms_a_turn(stage, scores, visible):
        def many(scores, visible):
            def turn(_, carry):
                out = stage(scores + carry, visible)
                return 1e-38 * sum(jnp.sum(o.astype(jnp.float32))
                                   for o in jax.tree_util.tree_leaves(out))
            return jax.lax.fori_loop(0, turns, turn, jnp.float32(0))
        fn = jax.jit(many)
        fn(scores, visible).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t1 = time.perf_counter()
            fn(scores, visible).block_until_ready()
            best = min(best, (time.perf_counter() - t1) / turns)
        return round(best * 1e3, 4)

    cases, cost = [], []
    for n in (positions, 131072) if strict else (positions,):
        rng = np.random.RandomState(19)
        held = rng.randint(n // 6, n * 5 // 6 + 1, lanes)
        held[0], held[-1] = max(topk // 2, 1), n
        first = np.array([rng.randint(0, n - h + 1) for h in held])
        at = np.arange(n)[None, :]
        visible = jnp.asarray((at >= first[:, None])
                              & (at < (first + held)[:, None]))
        tied = rng.randint(-2, 3, (lanes, n)).astype(np.float32)
        tied[rng.rand(lanes, n) < 0.3] = -0.0
        drawn = {"random": rng.randn(lanes, n).astype(np.float32),
                 "ties_and_both_zeros": tied}
        for name, scores in drawn.items():
            scores = jnp.asarray(scores)
            want = _scattered(*top_k(scores, visible), n)
            chosen, (rows, ok) = jax.jit(stages["set_and_rows"])(scores,
                                                                 visible)
            rows, ok = np.asarray(rows), np.asarray(ok)
            if not np.array_equal(np.asarray(chosen), want):
                raise AssertionError(
                    f"the chosen set is not top_k's ({name}, {n} positions)")
            if not all(r[o].tolist() == np.flatnonzero(w).tolist()
                       for r, o, w in zip(rows, ok, want)) \
                    or (ok.sum(1) != np.minimum(held, topk)).any():
                raise AssertionError(
                    f"the rows are not the set's ({name}, {n} positions)")
            cases.append({"positions": n, "scores": name,
                          "set_is_top_ks": True, "rows_are_the_sets": True})
        if strict:
            scores = jnp.asarray(drawn["random"])
            chosen = ia.chosen_set(scores, visible, topk)
            cost.append({"positions": n, "topk": topk, **{
                f"{name}_ms": ms_a_turn(
                    stage, scores, chosen if name == "rows" else visible)
                for name, stage in stages.items()}})
    out = {"kernel": "selection (chosen_set, choose | lax.top_k)",
           "lanes": lanes, "positions": positions, "topk": topk,
           "cases": cases, "cost": cost or "not measured (no chip)"}
    if strict:
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "selection.json"),
                  "w") as fh:
            json.dump(out, fh, indent=1)
    return out


# the Keye-VL serve cell's decode step: 32 lanes of 24,576 positions (two
# layers of the stacked leaves), 4 KV heads of 128 under 32 query heads, 16
# index heads of 64, 2,048 chosen of 4k-20k live
SELECTED_SHAPE = (2, 32, 24576, 4, 32, 128, 16, 64, 2048)


def phase_kernels(flash_shapes=((1024, 128, 16, 6), (512, 64, 16, 2),
                                (4096, 128, 16, 1)),
                  adam_shape=(2048, 8192), splash=(4096, 128, 16, 64),
                  decode_shapes=((2, 16, 1024, 16, 16, 128),
                                 (2, 8, 1408, 4, 20, 128)),
                  retention_shape=(2, 8, 8, 40, 128),
                  ssd_shape=(2, 8, 32, 2, 128, 256),
                  gmm_shape=(8192, 2048, 1024, 64),
                  gmm_stack_shape=(2, 1536, 5120, 1536, 20),
                  latent_shape=(8, 2944, 5120, 128, 1536, 512, 128, 64,
                                128),
                  held_experts_shape=(256, 5120, 1536, 160, (0, 20)),
                  latent_kernel_shape=(2, 16, 2944, 128, 512, 64),
                  selected_shape=None, dtype=None, strict=True) -> dict:
    """Each Pallas kernel once at a production shape, forward and
    backward, against plain ``jnp``. ``flash_shapes`` rows are
    ``(seq, head_dim, heads, batch)`` (the 1.3B cell's 96 heads of 1,024
    positions a chip, and OLMoE's 4,096 positions, each under the schedule
    the table gives it); the first also runs with ``segment_ids``.
    ``splash`` is ``(seq, block, heads, head_dim)``;
    ``decode_shapes`` rows are ``(layers, lanes, positions, kv_heads,
    heads, head_dim)`` of a stacked KV leaf (full heads, grouped heads);
    ``retention_shape`` is ``(layers, lanes, kv_heads, heads, head_dim)``
    of a stacked retention state; ``ssd_shape`` is ``(layers, lanes,
    heads, groups, d_head, d_state)`` of a stacked Mamba-2 state;
    ``gmm_shape`` is ``(rows, d_in, d_out, groups)`` of a grouped matmul
    over rows sorted by group, ``gmm_stack_shape`` ``(layers, rows, d_in,
    d_out, groups)`` of the forward product over a stack of layers'
    matrices read in place; ``latent_shape`` is ``(lanes, positions,
    hidden, heads, q_rank, kv_rank, nope, rope, v_dim)`` of one decode
    step of latent attention, and ``held_experts_shape`` ``(tokens,
    hidden, width, experts scored, (first, count) held)`` of one expert
    layer that holds a share, both against ``perfbench/reference/
    deepseek_v2.py``; ``latent_kernel_shape`` is ``(layers, lanes,
    positions, heads, kv_rank, rope_dim)`` of the stacked latent leaves
    under the latent decode kernel (``latent_shape``'s step builds its
    ``Lane`` without a plan and keeps the einsums); ``selected_shape`` is
    ``(layers, lanes, positions, kv_heads, heads, head_dim, index heads,
    index dim, topk)`` of one decode step of attention over the rows an
    indexer chooses, and of the selection alone at its lanes, positions
    and ``topk`` (None: neither is run; ``python chip_smoke.py`` runs them
    at ``SELECTED_SHAPE``, the Keye-VL cell's)."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.common import interpret

    dtype = dtype or jnp.bfloat16
    counter = CompileCounter()
    t0 = time.time()
    if strict and interpret():
        raise AssertionError("Pallas interpret() is True on the chip")
    checks = []
    for i, (t, d, heads, batch) in enumerate(flash_shapes):
        checks.append(_check_flash(t, d, heads, batch, dtype, False, strict))
        if i == 0:
            checks.append(
                _check_flash(t, d, heads, batch, dtype, True, strict))
    checks.append(_check_fused_adam(adam_shape, strict))
    checks.append(_check_splash(*splash, dtype, strict))
    checks.extend(_check_decode_attention(*shape, dtype, strict)
                  for shape in decode_shapes)
    checks.append(_check_retention_step(*retention_shape, strict))
    checks.append(_check_ssd_step(*ssd_shape, strict))
    checks.extend(_check_grouped_matmul(*gmm_shape, dt, strict)
                  for dt in dict.fromkeys((dtype, jnp.float32)))
    checks.append(_check_grouped_matmul_stack(*gmm_stack_shape, dtype,
                                              strict))
    checks.append(_check_latent_decode(*latent_shape, dtype, strict))
    checks.append(_check_held_experts(*held_experts_shape, dtype, strict))
    checks.append(_check_latent_decode_attention(*latent_kernel_shape, dtype,
                                                 strict))
    if selected_shape is not None:
        checks.append(_check_selected_attention(*selected_shape, dtype,
                                                strict))
        checks.append(_check_selection(selected_shape[1], selected_shape[2],
                                       selected_shape[8], strict))
    for c in checks:
        emit({"phase": "kernels", "check": c})
    return {"phase": "kernels", "ok": True, "n_checks": len(checks),
            "interpret": interpret(), "wall_s": round(time.time() - t0, 1),
            **counter.snapshot(), **_memory()}


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------
def _train(name, *, model, seq, micro, steps, zero_stage, devices, tile_rows,
           strict, model_overrides):
    import numpy as np

    from benchmarks.gpt_pretrain import build
    from deepspeed_tpu.ops.pallas.common import interpret
    from deepspeed_tpu.parallel.mesh import MeshTopology
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader

    counter = CompileCounter()
    t0 = time.time()
    # default mesh (dp=-1) over the devices this phase owns; ZeRO-3 moves
    # dp onto fsdp itself (runtime/layout.py)
    topology = MeshTopology(devices=list(devices))
    engine, batch, cfg = build(model, seq, micro, zero_stage=zero_stage,
                               topology=topology, **(model_overrides or {}))
    if tile_rows:
        # every data shard sees the SAME `micro` seeded rows: the mean loss
        # and mean gradient equal the one-chip run's, so the trajectories
        # must agree to reduction-order noise
        rows = batch["input_ids"][:micro]
        reps = batch["input_ids"].shape[0] // micro
        batch = {"input_ids": np.tile(rows, (reps, 1))}
        batch["labels"] = batch["input_ids"]
    it = iter(RepeatingLoader([batch]))
    losses, step_s, compiles_per_step = [], [], []
    for _ in range(steps):
        n0, t1 = counter.compiles, time.time()
        losses.append(float(engine.train_batch(it)))
        step_s.append(time.time() - t1)
        compiles_per_step.append(counter.compiles - n0)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: loss did not fall: {losses}")
    if strict and interpret():
        raise AssertionError("Pallas interpret() is True on the chip")
    programs = engine.compiled_step_programs()
    hlo = programs["train_step"].as_text()
    mosaic = _mosaic_calls(hlo)
    sample = next((ln.strip()[:300] for ln in hlo.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln), None)
    mem = engine.compiled_step_memory() or {}
    if strict and mosaic < 1:
        raise AssertionError(
            f"{name}: attention is not the Mosaic custom call — no "
            "tpu_custom_call in the compiled train step's HLO")
    out = {
        "phase": name, "ok": True, "model": model, "seq": seq,
        "micro": micro, "global_batch": int(batch["input_ids"].shape[0]),
        "n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
        "zero_stage": zero_stage, "mesh": str(engine.topology),
        "steps": steps, "first_loss": round(losses[0], 4),
        "last_loss": round(losses[-1], 4),
        "losses": [round(x, 4) for x in losses],
        "first_step_s": round(step_s[0], 2),
        "steady_ms_per_step": round(
            1e3 * float(np.mean(step_s[2:] or step_s[-1:])), 1),
        "compiles_per_train_batch": compiles_per_step,
        "train_step_compiles": counter.named("jit(train_step)"),
        "mosaic_calls_in_step_hlo": mosaic, "mosaic_call_sample": sample,
        "interpret": interpret(),
        "compiled_step_bytes": {
            k: int(mem[f"train_step_{k}"]) for k in (
                "argument_bytes", "output_bytes", "temp_bytes",
                "alias_bytes") if f"train_step_{k}" in mem},
        "wall_s": round(time.time() - t0, 1),
        **counter.snapshot(), **_memory(devices[0]),
    }
    return out, engine, hlo


def phase_train(name="train", model="gpt2-1.3b", seq=1024, micro=6, steps=8,
                strict=True, **model_overrides) -> dict:
    """The item-1 trainer on ONE chip (the first device), whatever the
    host holds."""
    import jax

    return _train(name, model=model, seq=seq, micro=micro, steps=steps,
                  zero_stage=1, devices=jax.devices()[:1], tile_rows=False,
                  strict=strict, model_overrides=model_overrides)[0]


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------
def _placement(tree) -> dict:
    """Where a pytree of jax Arrays lives: bytes per device id, and the
    distinct sharding specs."""
    import jax

    per_dev, specs = {}, set()
    for leaf in jax.tree.leaves(tree):
        if not isinstance(leaf, jax.Array):
            continue
        specs.add(str(getattr(leaf.sharding, "spec", leaf.sharding)))
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    return {"bytes_per_device": {str(k): v for k, v in sorted(
        per_dev.items())}, "specs": sorted(specs)}


def _serve_requests(sched, prompts, new_tokens, vocab):
    for p in prompts:
        sched.submit(p, max_new_tokens=new_tokens)
    t0 = time.time()
    stats = sched.run()
    wall = time.time() - t0
    got = {c.request_id: c.tokens for c in stats.completions}
    if len(got) != len(prompts):
        raise AssertionError(
            f"serve: {len(got)} of {len(prompts)} requests completed")
    for rid, toks in got.items():
        if len(toks) != new_tokens or not all(
                0 <= int(t) < vocab for t in toks):
            raise AssertionError(
                f"serve: request {rid} returned {len(toks)} tokens "
                f"(want {new_tokens}) or an out-of-vocabulary id: {toks}")
    ids = sorted(got)
    return [got[i] for i in ids], wall, stats


def _solo_generate(engine, prompt, bucket, new_tokens):
    """``engine.generate`` on one prompt, padded to the scheduler's prompt
    bucket under an attention mask (generate left-aligns it), which is the
    geometry admission prefill uses — the repo's own parity check
    (tests/unit/test_serving.py)."""
    import jax.numpy as jnp
    import numpy as np

    L = -(-len(prompt) // bucket) * bucket
    ids = np.zeros((1, L), np.int32)
    mask = np.zeros((1, L), bool)
    ids[0, :len(prompt)] = prompt
    mask[0, :len(prompt)] = True
    out = engine.generate(jnp.asarray(ids), max_new_tokens=new_tokens,
                          attention_mask=jnp.asarray(mask))
    return np.asarray(out)[0].tolist()


def _run_server(engine, sched, cfg, *, slots, prompt_range, new_tokens):
    """One seeded prompt per slot, lengths drawn from ``prompt_range``:
    served twice (cold, then warm) and checked against ``generate``."""
    import numpy as np

    rng = np.random.default_rng(0)
    prompt_lens = np.random.default_rng(1).integers(
        prompt_range[0], prompt_range[1] + 1, size=slots)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
               for n in prompt_lens]
    cold, cold_s, _ = _serve_requests(sched, prompts, new_tokens,
                                      cfg.vocab_size)
    warm, warm_s, stats = _serve_requests(sched, prompts, new_tokens,
                                          cfg.vocab_size)
    if warm != cold:
        raise AssertionError("serve: a second pass over the same requests "
                             "returned different greedy tokens")
    solo = [_solo_generate(engine, p, sched.prompt_bucket, new_tokens)
            for p in prompts]
    matched = [i for i, (a, b) in enumerate(zip(cold, solo)) if a == b]
    # greedy bf16 decode at batch `slots` and at batch 1 are different
    # programs: one rounding flip on a near-tie forks the sequence for good
    first_diff = [next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                       None) for a, b in zip(cold, solo)]
    if not matched:
        raise AssertionError(
            "serve: no request's tokens equal engine.generate on the same "
            f"prompt (first differing position per request: {first_diff})")
    return {
        "requests": len(prompts), "slots": slots,
        "prompt_lens": [int(n) for n in prompt_lens],
        "new_tokens": new_tokens,
        "matched_generate": matched,
        "first_token_differing_from_generate": first_diff,
        "decode_steps": stats.decode_steps,
        "cold_run_s": round(cold_s, 2),
        "steady_ms_per_token": round(
            1e3 * warm_s / (len(prompts) * new_tokens), 2),
        "steady_ms_per_decode_step": round(
            1e3 * warm_s / max(stats.decode_steps, 1), 2),
    }


def _serve_model(model, seq, model_overrides):
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT, gpt2_config

    cfg = gpt2_config(model, n_positions=seq, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, scan_layers=True,
                      use_flash_attention="auto", **(model_overrides or {}))
    return GPT(cfg), cfg


def phase_serve(model="gpt2-1.3b", seq=1024, slots=4, prompt_range=(128, 512),
                new_tokens=32, strict=True, **model_overrides) -> dict:
    """The same architecture through init_inference -> build_serving ->
    ContinuousBatchingScheduler.run."""
    import deepspeed_tpu
    from deepspeed_tpu import serving

    counter = CompileCounter()
    t0 = time.time()
    module, cfg = _serve_model(model, seq, model_overrides)
    engine = deepspeed_tpu.init_inference(module, dtype="bf16", seed=0)
    if strict and engine.topology.num_devices != 1:
        raise AssertionError(
            "serve: this is the one-chip phase but the engine took "
            f"{engine.topology} — ONE_CHIP_ENV did not take on this host")
    sched = serving.build_serving(engine, {"slots": slots})
    res = _run_server(engine, sched, cfg, slots=slots,
                      prompt_range=prompt_range, new_tokens=new_tokens)
    return {"phase": "serve", "ok": True, "model": model,
            "n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
            "mesh": str(engine.topology), **res,
            "params": _placement(engine.params),
            "wall_s": round(time.time() - t0, 1),
            **counter.snapshot(), **_memory()}


# ---------------------------------------------------------------------------
# phase: four_chip
# ---------------------------------------------------------------------------
def _collectives(hlo_text: str) -> dict:
    ops = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
           "collective-permute")
    return {op: hlo_text.count(f" {op}(") + hlo_text.count(f" {op}-start(")
            for op in ops}


def _mosaic_result_batches(hlo_text: str) -> list:
    """Leading dims of what each Mosaic call returns (the kernels work on
    [batch*heads, seq, head_dim]): one chip's rows, or everybody's."""
    import re

    dims = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"= \(?\w+\[(\d+),", line)
            if m:
                dims.add(int(m.group(1)))
    return sorted(dims)


def phase_four_chip(model="gpt2-1.3b", seq=1024, micro=6, steps=8, slots=4,
                    prompt_range=(128, 512), new_tokens=32, n_devices=4,
                    strict=True, **model_overrides) -> dict:
    """ZeRO-3 over fsdp=n_devices at the item-1 shape, then what the
    4-request server does on the same devices."""
    import gc

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler

    devices = jax.devices()[:n_devices]
    out, engine, hlo = _train(
        "four_chip", model=model, seq=seq, micro=micro, steps=steps,
        zero_stage=3, devices=devices, tile_rows=True, strict=strict,
        model_overrides=model_overrides)
    heads = engine.module.config.n_head
    out.update({
        "params": _placement(engine.params),
        "opt_state": _placement(engine.optimizer_adapter.state),
        "peak_bytes_in_use_per_device": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices],
        "collectives_in_step_hlo": _collectives(hlo),
        "mosaic_result_leading_dims": _mosaic_result_batches(hlo),
        "per_chip_batch_x_heads": micro * heads,
    })
    if strict and out["mosaic_result_leading_dims"] != [micro * heads]:
        raise AssertionError(
            "four_chip: the flash custom calls do not work on exactly one "
            f"chip's rows (leading dims {out['mosaic_result_leading_dims']}"
            f", micro*heads = {micro * heads}): operands are being "
            "gathered, or the HLO could not be read")
    del engine, hlo
    gc.collect()

    # the server on the same devices: build_serving must refuse a mesh
    # whose data axes exceed 1 (lanes and caches are not sharded; every
    # chip would redo every lane) ...
    module, cfg = _serve_model(model, seq, model_overrides)
    inf = deepspeed_tpu.init_inference(module, dtype="bf16", seed=0)
    refused = None
    try:
        serving.build_serving(inf, {"slots": slots})
    except NotImplementedError as e:
        refused = str(e)
    if strict and refused is None:
        raise AssertionError(
            "four_chip: build_serving accepted a dp>1 mesh on TPU")
    # ... and driving the scheduler directly shows what it refuses
    sched = ContinuousBatchingScheduler(inf, slots=slots)
    res = _run_server(inf, sched, cfg, slots=slots,
                      prompt_range=prompt_range, new_tokens=new_tokens)
    out["server"] = {
        "mesh": str(inf.topology), "build_serving_refused": refused,
        **res, "params": _placement(inf.params),
        "lane_cache_empty": _placement(sched._empty_cache()),
        "lane_cache_prefilled": _placement(inf._chunked_prefill(
            jnp.zeros((1, sched.prompt_bucket), jnp.int32),
            jnp.ones((1, sched.prompt_bucket), bool))[1]),
        "lane_tokens": "host numpy [slots]; handed to each decode step "
                       "as an uncommitted array",
    }
    return out


# ---------------------------------------------------------------------------
# children and parent
# ---------------------------------------------------------------------------
def run_phase(name: str, fn) -> None:
    """Print the phase's result line, then where its set-up went: on a
    new host, its first minute."""
    from deepspeed_tpu.telemetry import telemetry_bus

    built = []

    def on_event(ev):
        if ev["kind"] == "program.built":
            built.append(ev)

    telemetry_bus.subscribe(on_event)
    try:
        emit(fn())
    finally:
        telemetry_bus.unsubscribe(on_event)
    emit({"phase": name, "builds": builds_report(built)})


def _child(name: str) -> int:
    """Run one phase in this (fresh) process. Any exception propagates:
    the traceback goes to stderr and the exit code is nonzero."""
    sys.path.insert(0, REPO)
    info = device_report()
    if name == "four_chip" and info["device"]["count"] < 4:
        emit({"phase": "four_chip", "ok": True, "ran": False,
              "reason": f"JAX reports {info['device']['count']} device(s); "
                        "the four-chip phase needs 4"})
        return 0
    fn = {"kernels": lambda: phase_kernels(selected_shape=SELECTED_SHAPE),
          "train": phase_train,
          "train_warm": lambda: phase_train(name="train_warm"),
          "serve": phase_serve, "four_chip": phase_four_chip}[name]
    run_phase(name, fn)
    return 0


def _run_child(name: str, deadline: float, one_chip: bool):
    """Spawn one phase, echo its stdout, return (exit code, JSON lines).
    The child leads its own process group, which is killed on the way out
    whatever happens, so nothing it started outlives this run."""
    env = dict(os.environ)
    if one_chip:
        env.update(ONE_CHIP_ENV)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    code = f"import sys, chip_smoke; sys.exit(chip_smoke._child({name!r}))"
    proc = subprocess.Popen([sys.executable, "-c", code], cwd=REPO, env=env,
                            stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    lines = []
    timer = threading.Timer(max(deadline - time.time(), 1.0),
                            lambda: os.killpg(proc.pid, signal.SIGKILL))
    try:
        timer.start()
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.startswith("{"):
                try:
                    lines.append(json.loads(line))
                except ValueError:
                    pass
        return proc.wait(), lines
    finally:
        timer.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def main() -> int:
    deadline = time.time() + TOTAL_BUDGET_S
    device, results = None, {}
    for name in PHASES:
        # the first child reports the host's devices; after that the
        # one-chip phases of a multi-chip host see only the first chip
        one_chip = (device is not None and device["count"] > 1
                    and name != "four_chip")
        rc, lines = _run_child(name, deadline, one_chip)
        for obj in lines:
            if "device" in obj and "jax" in obj and not one_chip:
                device = obj["device"]
            if obj.get("phase") == name and "ok" in obj:
                results[name] = obj
        if rc != 0 or name not in results:
            print(f"chip_smoke: phase {name!r} failed (exit code {rc})",
                  file=sys.stderr)
            return rc or 1
    cold, warm = results["train"], results["train_warm"]
    emit({"phase": "compile_cache",
          "train_compile_s": cold["compile_s"],
          "train_warm_compile_s": warm["compile_s"],
          "train_first_step_s": cold["first_step_s"],
          "train_warm_first_step_s": warm["first_step_s"],
          "train_warm_persistent_cache_hits": warm["persistent_cache_hits"]})
    if max(abs(a - b) for a, b in zip(warm["losses"], cold["losses"])) \
            > LOSS_TRAJECTORY_TOL:
        print("chip_smoke: the warm train run left the cold run's loss "
              f"trajectory: {warm['losses']} vs {cold['losses']}",
              file=sys.stderr)
        return 1
    four = results["four_chip"]
    if four.get("ran", True):
        drift = max(abs(a - b) for a, b in zip(four["losses"],
                                               cold["losses"]))
        emit({"phase": "four_chip_vs_one_chip", "max_abs_loss_diff": drift,
              "one_chip": cold["losses"], "four_chip": four["losses"]})
        if drift > LOSS_TRAJECTORY_TOL:
            print("chip_smoke: ZeRO-3 on four chips left the one-chip loss "
                  f"trajectory (max |diff| {drift})", file=sys.stderr)
            return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
