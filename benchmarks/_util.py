"""Shared benchmark helpers."""

import json
import time

from deepspeed_tpu.utils.timer import fence  # noqa: F401  (re-export)


def gpt_flops_per_token(cfg, seq: int) -> float:
    """Model (algorithmic) training FLOPs per token for a causal GPT:
    6N for the non-embedding params + the causal attention term."""
    from deepspeed_tpu.models.transformer_lm import num_params

    embed = cfg.vocab_size * cfg.n_embd
    attn = 6 * cfg.n_layer * cfg.n_embd * seq
    return 6.0 * (num_params(cfg) - embed) + attn


def time_train_steps(engine, batch, steps: int = 5,
                     warmup: int = 2) -> float:
    """Seconds per train_batch, warmed and fenced (see ``fence``)."""
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader

    it = iter(RepeatingLoader([batch]))
    for _ in range(warmup):
        engine.train_batch(it)
    fence(engine.params)
    t0 = time.time()
    for _ in range(steps):
        engine.train_batch(it)
    fence(engine.params)
    return (time.time() - t0) / steps


def analytic_step_metrics(engine, dt: float, peak: float = None) -> dict:
    """Compiled-step cost-analysis metrics for one optimizer step.

    Complements the hand-derived ``model_tflops`` (algorithmic 6N count)
    with what XLA actually scheduled: ``analytic_tflops`` from the
    compiled program's HLO flops (per device, post-partitioning) over the
    measured step time, and MFU against the hardware-peak table
    (``profiling/step_profiler.py``). On TPU a missing cost or memory
    analysis is an error; elsewhere (backends without a cost model)
    the fields it would have filled are left out."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    cost = engine.compiled_step_cost()
    if not cost or not cost.get("flops"):
        if on_tpu:
            raise RuntimeError(
                "engine.compiled_step_cost() returned no flops on TPU "
                f"(got {cost!r})")
        return {}
    from deepspeed_tpu.profiling.step_profiler import peak_tflops

    src = "caller"
    if peak is None:
        peak, src = peak_tflops()
    tflops = cost["flops"] / dt / 1e12
    out = {
        "analytic_flops_per_step": cost["flops"],
        "analytic_tflops": round(tflops, 2),
        "analytic_mfu": round(tflops / peak, 4) if peak else 0.0,
        "analytic_peak_tflops": peak,
        "analytic_peak_source": src,
        "hbm_gb_per_s": round(cost.get("bytes_accessed", 0.0) / dt / 1e9, 1),
    }
    # Compiled-step memory_analysis() (telemetry/memory.py): the static
    # HBM budget XLA committed to — argument/output/temp/alias breakdown
    # plus the peak working set.
    mem = engine.compiled_step_memory()
    if mem:
        out.update({f"analytic_mem_{k}": v for k, v in mem.items()})
    elif on_tpu:
        raise RuntimeError(
            "engine.compiled_step_memory() returned nothing on TPU")
    return out


def backend_preflight(emit=None, _runner=None) -> dict:
    """Look at the backend once, in THIS process, before a benchmark
    commits to it: ok only when it is a TPU. A timing taken on the CPU
    backend is not a device number, so anything else is refused —
    ``{"ok": False, "error": ...}`` plus one JSON evidence line through
    ``emit`` — and the caller exits nonzero.

    No subprocess and no retry: the process that probes is the process
    that benchmarks (one process per chip), and a backend that fails to
    come up is a failed run. ``_runner`` injects a fake probe for tests;
    it returns ``(ok, "<platform> <device count>")`` like the real one."""
    emit = emit or (lambda obj: print(json.dumps(obj), flush=True))
    try:
        ok, detail = (_runner or _backend_probe)()
    except Exception as e:  # a backend that fails to initialise
        ok, detail = False, f"{type(e).__name__}: {e}"
    if ok and not str(detail).startswith("tpu "):
        ok, detail = False, (f"backend is {detail!r}, not a TPU: refusing "
                             "to time it")
    if ok:
        return {"ok": True, "backend": detail}
    emit({"event": "backend_preflight_failure", "error": str(detail)[-2000:]})
    return {"ok": False, "error": str(detail)[-2000:]}


def _backend_probe():
    import jax

    return True, f"{jax.default_backend()} {len(jax.devices())}"


def run_with_retry(fn, name: str, retries: int = 1, backoff_s: float = 5.0,
                   emit=None):
    """Run ``fn()``; on failure emit an evidence JSON line, back off, and
    retry up to ``retries`` more times. Returns ``(result, None)`` or
    ``(None, error_str)`` — never raises, so one flaky workload cannot
    turn the whole bench into an evidence-free rc=1."""
    emit = emit or (lambda obj: print(json.dumps(obj), flush=True))
    err = ""
    for attempt in range(1, retries + 2):
        try:
            return fn(), None
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
            emit({"event": "workload_failure", "workload": name,
                  "attempt": attempt, "max_attempts": retries + 1,
                  "error": err[-2000:]})
            if attempt <= retries:
                import gc

                gc.collect()
                time.sleep(backoff_s)
    return None, err


