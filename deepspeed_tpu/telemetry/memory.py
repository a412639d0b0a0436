"""HBM memory accounting.

Three sources, in decreasing order of authority:

1. **Compiled-step ``memory_analysis()``** — XLA's own accounting of the
   already-compiled step executable (``CompiledMemoryStats``): argument /
   output / temp bytes, donation-aliased bytes, generated-code size.
   Read from the executable the engine already dispatched
   (``DeepSpeedEngine.compiled_step_programs``), so it never recompiles.
2. **Live ``device.memory_stats()`` watermarks** — the PJRT allocator's
   ``bytes_in_use`` / ``peak_bytes_in_use``. A host-local runtime query,
   NOT a device sync, but still sampled only where the step profiler has
   already paid a fence (zero added syncs on the healthy path). Returns
   None on backends without an allocator report (CPU) — every consumer
   gates on that.
3. **The ``device_kind`` HBM table** — the denominator: how much HBM the
   detected chip actually has, same keying as the peak-FLOPs table in
   ``profiling/step_profiler.py``.

jax is imported inside functions only: the telemetry package must stay
importable by supervisors that never initialize a backend.
"""

from typing import Any, Dict, Optional, Tuple

# HBM capacity per jax device in GiB, keyed by device_kind substrings
# (first match wins — newest/most-specific first; same convention as
# HW_PEAK_BF16_TFLOPS). v2/v3 are per-core because a jax device is one
# core there (half the chip's HBM); v4+ are per-chip. Sources: Google TPU
# system-architecture pages. No CPU entry: host RAM is not HBM and
# ``hbm_bytes`` reports None so callers can say so explicitly.
DEVICE_HBM_GIB = (
    ("v6e", 32.0),
    ("v6 lite", 32.0),
    ("v5p", 95.0),
    ("v5e", 16.0),
    ("v5 lite", 16.0),
    ("v5", 95.0),
    ("v4", 32.0),
    ("v3", 16.0),
    ("v2", 8.0),
)

_GIB = 1024 ** 3


def hbm_bytes(device=None, override_gib: Optional[float] = None
              ) -> Tuple[Optional[int], str]:
    """``(hbm_bytes_or_None, source)`` for ``device`` (default:
    ``jax.devices()[0]``). None means "no HBM figure for this backend"
    (CPU, unknown kinds) — the honest answer, not a guess."""
    if override_gib:
        return int(override_gib * _GIB), "config override"
    kind = ""
    try:
        if device is None:
            import jax

            device = jax.devices()[0]
        kind = str(getattr(device, "device_kind", device)).lower()
    except Exception:  # pragma: no cover - backend-less host
        return None, "no backend"
    for sub, gib in DEVICE_HBM_GIB:
        if sub in kind:
            return int(gib * _GIB), f"device_kind={kind!r}"
    return None, f"no HBM table entry for device_kind={kind!r}"


def live_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """Current allocator watermarks for ``device``, or None when the
    backend exposes none (``memory_stats()`` is None on CPU). Host-local
    query; no device sync."""
    try:
        if device is None:
            import jax

            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:  # pragma: no cover - backend-less host
        return None
    if not stats:
        return None
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size", "bytes_reserved", "num_allocs")
    out = {k: int(v) for k, v in stats.items()
           if k in keep and isinstance(v, (int, float))}
    return out or None


def memory_analysis_of(compiled) -> Dict[str, float]:
    """``CompiledMemoryStats`` of an already-compiled program
    (``jax.stages.Compiled``), as bytes::

        {"argument_bytes", "output_bytes", "temp_bytes", "alias_bytes",
         "generated_code_bytes", "peak_working_set_bytes"}

    ``peak_working_set_bytes`` = arguments + outputs + temps − aliased
    (donated inputs reuse their buffers for outputs): the analytic
    per-device HBM ceiling of running this program, excluding whatever
    else the process keeps resident.
    """
    ma = compiled.memory_analysis()
    if ma is None:  # pragma: no cover - backend without the API
        raise RuntimeError("backend returned no memory_analysis()")
    arg = float(getattr(ma, "argument_size_in_bytes", 0) or 0)
    out = float(getattr(ma, "output_size_in_bytes", 0) or 0)
    tmp = float(getattr(ma, "temp_size_in_bytes", 0) or 0)
    alias = float(getattr(ma, "alias_size_in_bytes", 0) or 0)
    code = float(getattr(ma, "generated_code_size_in_bytes", 0) or 0)
    return {
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": tmp,
        "alias_bytes": alias,
        "generated_code_bytes": code,
        "peak_working_set_bytes": max(0.0, arg + out + tmp - alias),
    }


def compiled_memory_analysis(fn, *args) -> Dict[str, float]:
    """:func:`memory_analysis_of` ``fn(*args)`` (args may be avals): jit
    (no-op when ``fn`` is already jitted), lower, compile. A hit in jit's
    own cache only when the avals carry the shardings of the arrays the
    step was dispatched with; otherwise a fresh XLA compile."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return memory_analysis_of(jitted.lower(*args).compile())


def memory_analysis_of_call(jitted_fn, *concrete_args) -> Dict[str, float]:
    """``compiled_memory_analysis`` with avals derived from concrete
    arguments (the pipeline engine holds live stage inputs, not avals)."""
    import jax

    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if hasattr(x, "shape") else x, concrete_args)
    return compiled_memory_analysis(jitted_fn, *avals)


def summarize_program_memory(programs: Dict[str, Dict[str, float]]
                             ) -> Dict[str, float]:
    """Flatten per-program memory dicts into one counter dict.

    Programs run sequentially (fwd/bwd then apply; pipeline stages in
    schedule order), so the honest headline is the MAX working set over
    programs, not the sum — plus prefixed per-program detail and a summed
    generated-code size (all executables stay loaded).
    """
    out: Dict[str, float] = {}
    peak = 0.0
    code = 0.0
    for name, mem in programs.items():
        for k, v in mem.items():
            out[f"{name}_{k}"] = float(v)
        peak = max(peak, float(mem.get("peak_working_set_bytes", 0.0)))
        code += float(mem.get("generated_code_bytes", 0.0))
    out["peak_working_set_bytes"] = peak
    out["generated_code_bytes_total"] = code
    return out


def format_bytes(n: Optional[Any]) -> str:
    """Human GiB/MiB formatting for reports (None-safe)."""
    if n is None:
        return "n/a"
    n = float(n)
    if n >= _GIB:
        return f"{n / _GIB:.2f} GiB"
    if n >= 1024 ** 2:
        return f"{n / 1024 ** 2:.1f} MiB"
    return f"{int(n)} B"
