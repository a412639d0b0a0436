"""Step-config autotuner: HBM-bounded (remat_policy, micro_batch, flash)
search for a model config and device (ROADMAP item 3).

The search space is the one the 1.3B plateau analysis exposed: which
activations to keep (``_remat_policy`` in ``models/transformer_lm.py``),
how large a micro batch the remaining HBM headroom buys, and whether the
flash kernel replaces dense attention. Candidates are **pruned
analytically first**: every candidate's full train step (fwd + bwd +
optimizer tail) is AOT-lowered from avals only — the
``benchmarks/memory_report.py`` pattern, no parameter ever materializes —
and its ``memory_analysis()`` peak working set is checked against the
``DEVICE_HBM_GIB`` ceiling (``telemetry/memory.py``). A candidate over
the ceiling is **never executed**, so the search cannot OOM a real
device. Survivors are then live-benchmarked (fenced wall-clock + the
step profiler's analytic-MFU arithmetic: XLA cost-analysis FLOPs over
measured time over the ``HW_PEAK_BF16_TFLOPS`` table) when a backend
that can run them is present, and scored by a calibrated roofline
prediction when it is not (searching a v4/v5e config from a CPU host).

Resolution order for :func:`get_step_config` — the exact
mem -> disk -> PRETUNED -> live chain of ``ops/pallas/autotune.py``:

1. in-memory cache (one lookup per process per key)
2. on-disk JSON cache — only where ``$DS_TPU_STEP_AUTOTUNE_CACHE``
   names a file (no default location: the engine's step must not depend
   on what an earlier run left in a home directory), keyed
   ``device_kind|nN|model|seq|dtype`` (N = device count, so an elastic
   topology change re-tunes); corrupt files warn once and fall
   through, overwritten by the next tuned write.
3. shipped :data:`PRETUNED` table — seeds from the committed
   ``benchmarks/mfu_search_results.json`` search artifact.
4. live search, IF enabled (``autotune=True`` or
   ``DS_TPU_STEP_AUTOTUNE=1``): runs :func:`search` and persists the
   winner to (2).
5. ``None`` — the engine keeps its configured settings unchanged.

Every cached/pretuned entry is re-validated (:func:`_valid`) before use:
the remat policy must resolve through ``_remat_policy`` and the micro
batch must be a positive int, so a stale or hand-edited cache can never
push an invalid config into the engine.
"""

import dataclasses
import json
import os
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

_CACHE_ENV = "DS_TPU_STEP_AUTOTUNE_CACHE"
_AUTOTUNE_ENV = "DS_TPU_STEP_AUTOTUNE"

# Spec HBM bandwidth per jax device in GB/s — the roofline's memory term.
# Same keying/ordering convention as DEVICE_HBM_GIB (first substring
# match wins; v2/v3 per-core). Sources: Google TPU system-architecture
# pages. No CPU entry: predictions for a CPU target are not meaningful.
DEVICE_HBM_GBPS = (
    ("v6e", 1640.0),
    ("v6 lite", 1640.0),
    ("v5p", 2765.0),
    ("v5e", 819.0),
    ("v5 lite", 819.0),
    ("v5", 2765.0),
    ("v4", 1228.0),
    ("v3", 450.0),
    ("v2", 350.0),
)

# Measured anchor for roofline calibration: the r4 1.3B seq-1024 bench
# (flash + full remat + micro 6 on one v5e chip) hit 104.08 analytic
# TFLOPS. ``calibrate_compute_efficiency`` solves the additive roofline
# t = F/(c*peak) + B/bw for the compute-efficiency fraction c at this
# point, so predictions are pinned to a real measurement rather than to
# the marketing peak.
CALIBRATION_ANCHOR = {
    "model": "gpt2-1.3b", "seq": 1024, "micro_batch": 6,
    "remat_policy": "full", "flash": True,
    "measured_analytic_tflops": 104.08, "device_kind": "TPU v5e",
}
_DEFAULT_COMPUTE_EFF = 0.55  # fallback c when no anchor fits the solve

# (device_kind, model, seq, dtype) -> winner entry. Seeds from the
# committed search artifact (benchmarks/mfu_search_results.json): on
# v4/v5p the winner is flash + full remat at micro 8 — selective
# policies self-defeat at this scale (save_dots' dense bound busts v4's
# 32 GiB from micro 6 up, and where it fits its extra held activations
# buy less MFU than a bigger micro batch does). The v5e rows are the
# *benched* reality from gpt_pretrain.py (flash + full remat + micro 6
# measured on chip; micro 7/8 and every selective policy OOM the
# 16 GiB ceiling). A live search (DS_TPU_STEP_AUTOTUNE=1) overwrites
# these via the disk cache.
PRETUNED: Dict[Tuple[str, str, int, str], Dict[str, Any]] = {}
for _kind in ("TPU v4", "TPU v5p"):
    PRETUNED[(_kind, "gpt2-1.3b", 1024, "bfloat16")] = {
        "remat_policy": "full", "micro_batch": 8, "flash": True}
for _kind in ("TPU v5 lite", "TPU v5e"):
    PRETUNED[(_kind, "gpt2-1.3b", 1024, "bfloat16")] = {
        "remat_policy": "full", "micro_batch": 6, "flash": True}

_lock = threading.Lock()
_mem_cache: Dict[str, Dict[str, Any]] = {}
_disk_warned = False


@dataclasses.dataclass(frozen=True)
class StepCandidate:
    """One point of the search space."""

    remat_policy: str
    micro_batch: int
    flash: Any  # True | False (never "auto": the search decides)

    def label(self) -> str:
        return (f"{self.remat_policy}/micro{self.micro_batch}/"
                f"{'flash' if self.flash else 'dense'}")


# ---------------------------------------------------------------------------
# cache plumbing (the ops/pallas/autotune.py pattern)
# ---------------------------------------------------------------------------

def cache_path() -> Optional[str]:
    """The disk cache file, or None when ``$DS_TPU_STEP_AUTOTUNE_CACHE`` is
    unset (then nothing is read from or written to disk)."""
    return os.environ.get(_CACHE_ENV) or None


def cache_key(device_kind: str, model: str, seq: int, dtype,
              num_devices: int = 1) -> str:
    """``device_kind|nN|model|seq|dtype`` — the device COUNT is part of the
    key so an elastic resume on a shrunk/grown slice re-tunes instead of
    reusing the old topology's remat×micro winner (the HBM headroom and
    per-device batch landscape both move with N)."""
    import jax.numpy as jnp

    return (f"{device_kind}|n{int(num_devices)}|{model}|{int(seq)}|"
            f"{jnp.dtype(dtype).name}")


def _load_disk_cache() -> Dict[str, Dict[str, Any]]:
    global _disk_warned
    path = cache_path()
    if path is None or not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict):
            raise ValueError(f"expected a JSON object, got {type(data)}")
        return data
    except (OSError, ValueError) as e:
        if not _disk_warned:
            _disk_warned = True
            warnings.warn(
                f"ignoring corrupt step-autotune cache {path!r} ({e}); "
                "falling back to pretuned/live resolution — the next "
                "search rewrites it", RuntimeWarning)
        return {}


def _store_disk_cache(key: str, entry: Dict[str, Any]) -> None:
    path = cache_path()
    if path is None:
        return
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    data = _load_disk_cache()
    data[key] = entry
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def _valid(entry) -> Optional[Dict[str, Any]]:
    """Sanity-check a cached/pretuned winner before it reaches the engine:
    the policy must resolve through ``_remat_policy`` and the micro batch
    must be a positive int. Returns a normalized copy or None."""
    if not isinstance(entry, dict):
        return None
    from deepspeed_tpu.models.transformer_lm import _remat_policy

    try:
        policy = str(entry["remat_policy"])
        _remat_policy(policy)  # raises ValueError on unknown names
        micro = int(entry["micro_batch"])
        flash = bool(entry["flash"])
    except (KeyError, TypeError, ValueError):
        return None
    if micro < 1:
        return None
    out = dict(entry)
    out.update(remat_policy=policy, micro_batch=micro, flash=flash)
    return out


def clear_memory_cache() -> None:
    """Test hook: drop the per-process memoization (disk cache untouched)."""
    global _disk_warned
    with _lock:
        _mem_cache.clear()
        _disk_warned = False


def model_key(cfg) -> str:
    """Stable model identity for cache keys: the GPT2_SIZES name when the
    trunk dimensions match a named size, else a dimensions signature."""
    from deepspeed_tpu.models.transformer_lm import GPT2_SIZES

    for name, dims in GPT2_SIZES.items():
        if all(getattr(cfg, k, None) == v for k, v in dims.items()):
            return name
    return (f"gpt-l{cfg.n_layer}-d{cfg.n_embd}-h{cfg.n_head}"
            f"-v{cfg.vocab_size}")


# ---------------------------------------------------------------------------
# device tables
# ---------------------------------------------------------------------------

def _table_lookup(table, kind: str) -> Optional[float]:
    kind = (kind or "").lower()
    for sub, val in table:
        if sub in kind:
            return val
    return None


def device_ceiling_bytes(device_kind: Optional[str] = None,
                         override_gib: Optional[float] = None
                         ) -> Tuple[Optional[int], str]:
    """HBM ceiling for a *named* target device — unlike
    ``telemetry.memory.hbm_bytes`` this never needs a backend, so a CPU
    host can run the search against a v4/v5e ceiling."""
    from deepspeed_tpu.telemetry.memory import DEVICE_HBM_GIB, hbm_bytes

    if override_gib:
        return int(override_gib * 1024 ** 3), "config override"
    if device_kind:
        gib = _table_lookup(DEVICE_HBM_GIB, device_kind)
        if gib is not None:
            return int(gib * 1024 ** 3), f"table[{device_kind}]"
        return None, f"no HBM table entry for {device_kind!r}"
    return hbm_bytes()


def device_peak_and_bw(device_kind: str) -> Tuple[Optional[float],
                                                  Optional[float]]:
    """(peak bf16 TFLOPS, HBM GB/s) for a named device kind, or Nones."""
    from deepspeed_tpu.profiling.step_profiler import HW_PEAK_BF16_TFLOPS

    return (_table_lookup(HW_PEAK_BF16_TFLOPS, device_kind),
            _table_lookup(DEVICE_HBM_GBPS, device_kind))


# ---------------------------------------------------------------------------
# analytic pruning: avals-only AOT lowering (benchmarks/memory_report.py)
# ---------------------------------------------------------------------------

def _build_model(model: str, seq: int, dtype, cand: StepCandidate,
                 model_overrides: Optional[Dict[str, Any]] = None):
    from deepspeed_tpu.models.transformer_lm import GPT, gpt2_config

    overrides = dict(model_overrides or {})
    cfg = gpt2_config(
        model, n_positions=seq, dtype=dtype, param_dtype=dtype,
        scan_layers=True, remat=True, remat_policy=cand.remat_policy,
        use_flash_attention=cand.flash, **overrides)
    return GPT(cfg)


def _make_tx():
    # the benched pure-bf16 recipe (gpt_pretrain.py / memory_report.py):
    # moments inherit the bf16 param dtype, no fp32 masters
    import optax

    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(2e-4, b1=0.9, b2=0.95, weight_decay=0.1))


def _build_step(model, tx):
    import jax
    import optax

    def train_step(params, opt_state, batch, rng):
        def loss_fn(p):
            return model.apply(p, batch["input_ids"],
                               labels=batch["labels"],
                               deterministic=False,
                               rngs={"dropout": rng})

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1))


def analyze_candidate(model: str, seq: int, dtype, cand: StepCandidate,
                      model_overrides: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, float]:
    """AOT memory + cost analysis of one candidate's full train step from
    avals only — nothing executes, nothing materializes. Returns the
    ``compiled_memory_analysis`` dict merged with XLA cost metrics."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.profiling.flops_profiler.profiler import cost_analysis
    from deepspeed_tpu.telemetry.memory import compiled_memory_analysis

    m = _build_model(model, seq, dtype, cand, model_overrides)
    ids = jax.ShapeDtypeStruct((cand.micro_batch, seq), jnp.int32)
    batch = {"input_ids": ids, "labels": ids}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(m.init, rng, ids)
    tx = _make_tx()
    opt_state = jax.eval_shape(tx.init, params)
    step = _build_step(m, tx)
    # one compile serves both reads: the second lower() is a cache hit
    mem = compiled_memory_analysis(step, params, opt_state, batch, rng)
    cost = cost_analysis(step, params, opt_state, batch, rng)
    out = dict(mem)
    out.update(cost)
    return out


# ---------------------------------------------------------------------------
# roofline prediction (CPU host searching for a TPU target)
# ---------------------------------------------------------------------------

def calibrate_compute_efficiency(anchor_flops: float, anchor_bytes: float
                                 ) -> Tuple[float, str]:
    """Solve t = F/(c*peak) + B/bw for c at the measured anchor point
    (``CALIBRATION_ANCHOR``). The anchor's F/B come from the SAME analytic
    pipeline that scores candidates, so the calibration and the
    predictions share every modeling bias. Clamped to (0, 1]."""
    a = CALIBRATION_ANCHOR
    peak, bw = device_peak_and_bw(a["device_kind"])
    if not (peak and bw and anchor_flops > 0):
        return _DEFAULT_COMPUTE_EFF, "default (no anchor tables)"
    t_meas = anchor_flops / (a["measured_analytic_tflops"] * 1e12)
    t_mem = anchor_bytes / (bw * 1e9)
    t_compute = t_meas - t_mem
    if t_compute <= 0:  # anchor claims memory-bound: solve degenerates
        return _DEFAULT_COMPUTE_EFF, "default (anchor memory-bound)"
    c = anchor_flops / (peak * 1e12 * t_compute)
    c = max(0.01, min(1.0, c))
    return c, (f"solved at {a['model']} seq{a['seq']} "
               f"micro{a['micro_batch']} flash on {a['device_kind']} = "
               f"{a['measured_analytic_tflops']} TFLOPS")


def predict_step(flops: float, bytes_accessed: float, device_kind: str,
                 compute_eff: float) -> Dict[str, float]:
    """Additive-roofline step-time/MFU prediction for a target device:
    t = F/(c*peak) + B/bw; predicted analytic MFU = F/(t*peak)."""
    peak, bw = device_peak_and_bw(device_kind)
    if not (peak and bw and flops > 0):
        return {}
    t_compute = flops / (compute_eff * peak * 1e12)
    t_memory = bytes_accessed / (bw * 1e9)
    t = t_compute + t_memory
    tflops = flops / t / 1e12
    return {
        "predicted_step_s": t,
        # where the predicted time goes — the roofline's two terms
        "predicted_compute_s": t_compute,
        "predicted_memory_s": t_memory,
        "predicted_analytic_tflops": round(tflops, 2),
        "predicted_analytic_mfu": round(tflops / peak, 4),
    }


# ---------------------------------------------------------------------------
# live benchmark (the step profiler's analytic-MFU arithmetic)
# ---------------------------------------------------------------------------

def live_benchmark(model: str, seq: int, dtype, cand: StepCandidate,
                   model_overrides: Optional[Dict[str, Any]] = None,
                   steps: int = 3, warmup: int = 1,
                   measure_fused: bool = True) -> Dict[str, Any]:
    """Execute one candidate's real train step and measure it: fenced
    wall-clock over ``steps`` iterations, XLA cost-analysis FLOPs of the
    compiled program, and analytic MFU against the hardware peak table —
    the identical arithmetic the step profiler reports. With
    ``measure_fused`` the optimizer tail is also timed as a separate
    program (the two-program fwd/bwd + apply split) so the winner records
    whether fusing the tail into the step pays wall-clock."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from deepspeed_tpu.profiling.flops_profiler.profiler import cost_analysis
    from deepspeed_tpu.profiling.step_profiler import peak_tflops

    m = _build_model(model, seq, dtype, cand, model_overrides)
    rng = jax.random.PRNGKey(0)
    r = np.random.RandomState(0)
    vocab = m.config.vocab_size
    ids = jnp.asarray(r.randint(0, vocab, (cand.micro_batch, seq)),
                      jnp.int32)
    batch = {"input_ids": ids, "labels": ids}
    params = m.init(rng, ids)
    tx = _make_tx()
    opt_state = tx.init(params)
    step = _build_step(m, tx)
    rng2 = jax.random.PRNGKey(1)

    def timed(fn, *args, n=steps):
        out = fn(*args)  # compile + warm (donated args: use fresh copies)
        jax.block_until_ready(out)
        return out

    # fused single-program timing: donation consumes the state, so thread
    # it through the loop exactly as training would
    p, o = params, opt_state
    p, o, _ = timed(step, p, o, batch, rng2)
    for _ in range(max(0, warmup - 1)):
        p, o, _ = step(p, o, batch, rng2)
        jax.block_until_ready(p)
    t0 = time.perf_counter()
    for _ in range(steps):
        p, o, loss = step(p, o, batch, rng2)
    jax.block_until_ready(p)
    fused_s = (time.perf_counter() - t0) / steps

    cost = cost_analysis(step, jax.eval_shape(lambda: p),
                         jax.eval_shape(lambda: o), batch, rng2)
    peak, peak_src = peak_tflops()
    tflops = cost["flops"] / fused_s / 1e12 if fused_s > 0 else 0.0
    out: Dict[str, Any] = {
        "measured_step_s": fused_s,
        "flops_per_step": cost["flops"],
        "bytes_accessed_per_step": cost["bytes_accessed"],
        "analytic_tflops": round(tflops, 3),
        "analytic_mfu": round(tflops / peak, 5) if peak else 0.0,
        "peak_tflops": peak,
        "peak_source": peak_src,
        "loss": float(loss),
    }

    if measure_fused:
        # two-program split: grads program + optimizer-tail program, the
        # engine's forward()/step() shape (no donation reuse across them)
        def grads_fn(params, batch, rng):
            def loss_fn(pp):
                return m.apply(pp, batch["input_ids"],
                               labels=batch["labels"],
                               deterministic=False, rngs={"dropout": rng})

            return jax.value_and_grad(loss_fn)(params)

        def apply_fn(params, opt_state, grads):
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        jg = jax.jit(grads_fn)
        ja = jax.jit(apply_fn, donate_argnums=(0, 1))
        _, g = jg(p, batch, rng2)
        jax.block_until_ready(g)
        p2, o2 = ja(p, o, g)
        jax.block_until_ready(p2)
        t0 = time.perf_counter()
        for _ in range(steps):
            _, g = jg(p2, batch, rng2)
            p2, o2 = ja(p2, o2, g)
        jax.block_until_ready(p2)
        split_s = (time.perf_counter() - t0) / steps
        out["unfused_step_s"] = split_s
        out["fused_saving_s"] = split_s - fused_s
        out["fuse_optimizer"] = bool(fused_s < split_s)
    return out


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------

DEFAULT_POLICIES = ("full", "selective", "save_dots",
                    "save_nothing_but_flash")


def candidate_grid(micro_batches: Sequence[int],
                   policies: Sequence[str] = DEFAULT_POLICIES,
                   flash_options: Sequence[bool] = (True, False)
                   ) -> List[StepCandidate]:
    """The cross product, minus points that alias each other:
    ``save_nothing_but_flash`` without flash IS ``full`` (no tensor
    carries the saved names on the einsum path)."""
    out = []
    for pol in policies:
        for flash in flash_options:
            if pol == "save_nothing_but_flash" and not flash:
                continue
            for mb in micro_batches:
                out.append(StepCandidate(pol, int(mb), bool(flash)))
    return out


def search(model: str = "gpt2-1.3b", seq: int = 1024, dtype=None, *,
           micro_batches: Sequence[int] = (4, 6, 8),
           policies: Sequence[str] = DEFAULT_POLICIES,
           flash_options: Sequence[bool] = (True, False),
           device_kind: Optional[str] = None,
           hbm_override_gib: Optional[float] = None,
           live: Optional[bool] = None,
           live_steps: int = 3,
           measure_fused: bool = True,
           model_overrides: Optional[Dict[str, Any]] = None,
           baseline: Optional[StepCandidate] = None,
           _analyze=None, _bench=None) -> Dict[str, Any]:
    """Run the full HBM-bounded search and return the report.

    Per candidate: avals-only AOT analysis -> predicted peak bytes ->
    analytic prune against the device ceiling -> (surviving candidates
    only) live benchmark when ``live`` — default: live iff the target
    device is the one actually attached. ``_analyze``/``_bench`` inject
    fakes for tests. Nothing over the ceiling is ever executed.
    """
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    attached = ""
    try:
        attached = jax.devices()[0].device_kind
    except Exception:
        pass
    target = device_kind or attached or "cpu"
    ceiling, ceiling_src = device_ceiling_bytes(target, hbm_override_gib)
    if live is None:
        live = bool(attached) and (target.lower() in attached.lower()
                                   or attached.lower() in target.lower())
    analyze = _analyze or (
        lambda c: analyze_candidate(model, seq, dtype, c, model_overrides))
    bench = _bench or (
        lambda c: live_benchmark(model, seq, dtype, c, model_overrides,
                                 steps=live_steps,
                                 measure_fused=measure_fused))

    base = baseline or StepCandidate("full", micro_batches[0] if 6 not in
                                     micro_batches else 6, False)
    cands = candidate_grid(micro_batches, policies, flash_options)
    if base not in cands:
        cands.insert(0, base)

    # pass 1 — every candidate gets the avals-only AOT treatment (memory
    # breakdown + XLA cost metrics); nothing executes here
    rows: List[Dict[str, Any]] = []
    analyses: List[Optional[Dict[str, float]]] = []
    for cand in cands:
        row: Dict[str, Any] = {
            "remat_policy": cand.remat_policy,
            "micro_batch": cand.micro_batch,
            "flash": cand.flash,
            "is_baseline": cand == base,
            "executed_live": False,
        }
        try:
            an = analyze(cand)
        except Exception as e:  # a candidate that cannot even lower loses
            row.update(error=f"{type(e).__name__}: {e}", fits=False)
            an = None
        if an is not None:
            peak_b = an["peak_working_set_bytes"]
            row["predicted_peak_bytes"] = peak_b
            row["analysis"] = {
                k: an[k] for k in
                ("argument_bytes", "temp_bytes", "alias_bytes",
                 "flops", "bytes_accessed") if k in an}
            row["fits"] = bool(peak_b < ceiling) if ceiling else None
        rows.append(row)
        analyses.append(an)

    # calibrate the roofline on the anchor candidate (the measured r4
    # flash/full/micro-6 point) when this search covers it; else default
    a = CALIBRATION_ANCHOR
    anchor = StepCandidate(a["remat_policy"], a["micro_batch"], a["flash"])
    compute_eff, calib_src = _DEFAULT_COMPUTE_EFF, "default (no anchor run)"
    if model == a["model"] and seq == a["seq"] and anchor in cands:
        an = analyses[cands.index(anchor)]
        if an is not None:
            compute_eff, calib_src = calibrate_compute_efficiency(
                an.get("flops", 0.0), an.get("bytes_accessed", 0.0))

    # pass 2 — roofline predictions for everyone; live benchmark ONLY for
    # candidates whose predicted peak clears the ceiling
    for cand, row, an in zip(cands, rows, analyses):
        if an is None:
            continue
        row.update(predict_step(an.get("flops", 0.0),
                                an.get("bytes_accessed", 0.0), target,
                                compute_eff))
        if live and row["fits"] is not False:
            try:
                row.update(bench(cand))
                row["executed_live"] = True
            except Exception as e:
                row["live_error"] = f"{type(e).__name__}: {e}"

    def score(r):
        # measured MFU outranks predicted; candidates with neither sink
        if r.get("error") or r["fits"] is False:
            return -1.0
        return r.get("analytic_mfu") or r.get("predicted_analytic_mfu") \
            or 0.0

    base_row = next(r for r in rows if r["is_baseline"])
    winner = max(rows, key=score)
    report = {
        "model": model, "seq": seq,
        "dtype": jnp.dtype(dtype).name,
        "device_kind": target,
        "backend_device": attached or "none",
        "hbm_ceiling_bytes": ceiling,
        "hbm_ceiling_source": ceiling_src,
        "compute_efficiency": compute_eff,
        "calibration": calib_src,
        "live": bool(live),
        "candidates": rows,
        "baseline": {k: base_row.get(k) for k in
                     ("remat_policy", "micro_batch", "flash",
                      "predicted_peak_bytes", "predicted_analytic_mfu",
                      "analytic_mfu")},
        "winner": winner,
        "winner_beats_baseline": score(winner) > score(base_row),
    }
    return report


def winner_entry(report: Dict[str, Any]) -> Dict[str, Any]:
    """Compress a search report's winner into a cacheable entry."""
    w = report["winner"]
    entry = {k: w[k] for k in ("remat_policy", "micro_batch", "flash")}
    for k in ("predicted_peak_bytes", "predicted_analytic_mfu",
              "analytic_mfu", "measured_step_s", "fuse_optimizer"):
        if w.get(k) is not None:
            entry[k] = w[k]
    entry["device_kind"] = report["device_kind"]
    return entry


# ---------------------------------------------------------------------------
# resolution (mem -> disk -> PRETUNED -> live)
# ---------------------------------------------------------------------------

def get_step_config(model: str, seq: int, dtype=None, *,
                    device_kind: Optional[str] = None,
                    num_devices: Optional[int] = None,
                    autotune: Optional[bool] = None,
                    search_kwargs: Optional[Dict[str, Any]] = None
                    ) -> Optional[Dict[str, Any]]:
    """Resolve the tuned (remat_policy, micro_batch, flash) for a model
    config on a device, or None (caller keeps its configured settings).

    ``autotune=None`` defers to the ``DS_TPU_STEP_AUTOTUNE`` env flag;
    ``search_kwargs`` feeds the live :func:`search` on a miss.
    ``num_devices`` keys the cache (default: the visible device count) —
    a topology change misses the old entry and re-resolves. PRETUNED
    entries stay per-chip (micro_batch is per device), so they remain the
    fallback at any count.
    """
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    if num_devices is None:
        num_devices = jax.device_count()
    key = cache_key(device_kind, model, seq, dtype, num_devices)

    with _lock:
        hit = _mem_cache.get(key)
        if hit is not None:
            return dict(hit)
        entry = _valid(_load_disk_cache().get(key))
        if entry is not None:
            entry.setdefault("source", "disk")
            _mem_cache[key] = entry
            return dict(entry)
        pre = _valid(PRETUNED.get(
            (device_kind, model, int(seq), jnp.dtype(dtype).name)))
        if pre is not None:
            pre.setdefault("source", "pretuned")
            _mem_cache[key] = pre
            return dict(pre)

    if autotune is None:
        autotune = os.environ.get(_AUTOTUNE_ENV, "0") not in ("", "0")
    if not autotune:
        return None

    report = search(model, seq, dtype, device_kind=device_kind,
                    **(search_kwargs or {}))
    tuned = winner_entry(report)
    tuned["source"] = "live"
    # Persist WITHOUT "source" — a later process loading this entry saw a
    # disk hit, not a live search, and reports it as such.
    persisted = {k: v for k, v in tuned.items() if k != "source"}
    with _lock:
        _mem_cache[key] = tuned
        try:
            _store_disk_cache(key, persisted)
        except OSError as e:
            warnings.warn(
                f"step autotune: could not persist winner to "
                f"{cache_path()!r} ({e}); it stays in-memory for this "
                "process", RuntimeWarning)
    return dict(tuned)
