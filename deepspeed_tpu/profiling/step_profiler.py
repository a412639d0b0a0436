"""Step-level performance tracer.

Where the flops profiler answers "what does the model cost?" (per-module
HLO accounting), this module answers "where does a training step's time
and wire traffic actually go?" — the attribution layer ROADMAP items 3
(MFU plateau) and 4 (quantized collectives) both stall without.

Three jobs, all config-gated behind the ``step_profiler`` block:

1. **Analytic MFU** — FLOPs / bytes-accessed come from the compiled
   step's XLA cost analysis (``flops_profiler.cost_analysis``, i.e. the
   post-partition per-device module), not hand-derived ``6N`` counts.
   Achieved TFLOPS over the fenced mean step time is divided by a
   hardware-peak table keyed on ``jax.devices()[0].device_kind``.
2. **Phase attribution** — per-step wall time is split into named phases
   (``dataloader``, ``h2d``, ``compiled_step``, ``sentinel``,
   ``checkpoint``, ...) via the existing ``SynchronizedWallClockTimer``.
   Each phase stop drains the device queue (``utils.timer.fence``) so
   device work is charged to the phase that dispatched it; the residual
   between the phase sum and the fenced step envelope is reported as
   ``other``, so phases always sum to the step wall time. Every fence
   is gated on the profiling window: with the profiler disabled (or
   outside ``[start_step, start_step + num_steps)``) ``phase()`` returns
   a shared no-op context manager and the healthy path gains **zero**
   device syncs — the invariant the sentinel work established and the
   r3 regression taught us to guard.
3. **Trace export** — the same phase spans are emitted as Chrome
   trace-event JSON (``ph: "X"`` complete events, microsecond ts/dur)
   loadable in perfetto / ``chrome://tracing``, with optional
   ``jax.profiler`` trace capture over the same window for op-level
   drill-down.

Cumulative ``Perf/*`` (and the comm logger's ``Comm/*``) counters are
pushed through ``MonitorMaster`` when the window closes.
"""

import contextlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, fence

# ---------------------------------------------------------------------------
# Hardware peak table
# ---------------------------------------------------------------------------

# Dense (non-sparse) bf16 peak TFLOPS per jax device, keyed by substrings of
# ``device_kind`` (first match wins — order newest/most-specific first).
# Sources: Google TPU system architecture pages; v2/v3 are per-core because a
# jax device is one core there, v4+ are per-chip. The CPU entry is a nominal
# documented figure for the 8-virtual-device test mesh: MFU numbers on CPU
# are for plumbing tests, not performance claims.
HW_PEAK_BF16_TFLOPS = (
    ("v6e", 918.0),
    ("v6 lite", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),
    ("v5", 459.0),
    ("v4", 275.0),
    ("v3", 61.5),
    ("v2", 22.5),
    ("cpu", 0.5),
)


def peak_tflops(device=None, override: Optional[float] = None):
    """``(peak_bf16_tflops, source)`` for ``device`` (default: devices()[0]).

    ``override`` (the config's ``peak_tflops``) wins over the table. A
    ``device_kind`` that is not in the table raises: a utilization figure
    against a guessed denominator is worse than no figure.
    """
    if override:
        return float(override), "config override"
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(getattr(device, "device_kind", device)).lower()
    for sub, peak in HW_PEAK_BF16_TFLOPS:
        if sub in kind:
            return peak, f"device_kind={kind!r}"
    raise ValueError(
        f"no peak-TFLOPS entry for device_kind={kind!r}: add it to "
        "HW_PEAK_BF16_TFLOPS with its source, or set "
        "step_profiler.peak_tflops in the config")


# Reusable no-op context manager returned on every non-profiled step:
# nullcontext carries no per-enter state, so one shared instance keeps the
# disabled path at a single attribute check + dict-free ``with``.
_NULL_CTX = contextlib.nullcontext()

_TIMER_PREFIX = "step_profiler/"


class StepProfiler:
    """Config-gated step tracer (see module docstring).

    Engine protocol::

        prof.begin_step(global_step)     # fenced anchor, idempotent
        with prof.phase("h2d"): ...      # fenced stop charges device work
        with prof.phase("compiled_step"): ...
        prof.record_cost("train_step", jitted_fn, args)   # once per key
        prof.end_step(global_step)       # fenced envelope; residual→other

    ``end_step`` on the window's last step (or an explicit ``finalize()``)
    writes the trace artifact and pushes ``Perf/*`` / ``Comm/*`` counters
    through the monitor.
    """

    def __init__(self, config, timers: Optional[SynchronizedWallClockTimer] = None,
                 monitor=None):
        self.cfg = config
        self.enabled = bool(config.enabled)
        self.timers = timers if timers is not None else SynchronizedWallClockTimer()
        self.monitor = monitor
        self.window = range(config.start_step,
                            config.start_step + config.num_steps)
        self.records: List[Dict[str, Any]] = []
        self._costs: Dict[str, Dict[str, float]] = {}
        self._events: List[Dict[str, Any]] = []
        self._window_active = False
        self._in_step = False
        self._finalized = False
        self._t_base = 0.0
        self._step_t0 = 0.0
        self._step_idx = -1
        self._phase_acc: Dict[str, float] = {}
        self._jax_trace_on = False
        self._pid = 0
        # subsystem gauges merged into perf_counters (the engine feeds
        # the data-pipeline prefetch queue-depth/starvation stats here)
        self.aux_counters: Dict[str, float] = {}
        # HBM accounting (docs/observability.md "Memory accounting"):
        # compiled-step memory_analysis captured once per window, plus
        # live allocator watermarks maxed over the windowed steps
        self._memory: Optional[Dict[str, float]] = None
        self._live_mem_peak: Dict[str, float] = {}

    def set_memory(self, mem: Optional[Dict[str, float]]) -> None:
        """Record the compiled-step memory breakdown (once; later calls
        with None or after a first set are ignored)."""
        if mem and self._memory is None:
            self._memory = {str(k): float(v) for k, v in mem.items()}

    def has_memory(self) -> bool:
        return self._memory is not None

    def set_aux_counters(self, counters: Dict[str, float]) -> None:
        """Attach external gauges to the ``Perf/*`` export. Last write
        wins per key; cheap enough to call every step."""
        self.aux_counters.update(
            {str(k): float(v) for k, v in counters.items()})

    # -- gating ------------------------------------------------------------
    def active_for(self, step: int) -> bool:
        return (self.enabled and not self._finalized and step in self.window)

    def _fence(self):
        try:
            fence()
        except Exception:  # pragma: no cover - device-less host
            pass

    # -- step envelope -----------------------------------------------------
    def begin_step(self, step: int) -> None:
        if self._in_step or not self.active_for(step):
            return
        if not self._window_active:
            self._window_active = True
            try:
                import jax

                self._pid = jax.process_index()
            except Exception:  # pragma: no cover
                self._pid = 0
            # first fence compiles the drain program — pay that before the
            # first timed anchor, never inside a measured span
            self._fence()
            self._t_base = time.perf_counter()
            self._maybe_start_jax_trace()
        self._fence()
        self._step_t0 = time.perf_counter()
        self._step_idx = step
        self._phase_acc = {}
        self._in_step = True

    def phase(self, name: str):
        """Context manager attributing its span (host + device work it
        dispatched) to ``name``. A strict no-op outside the window."""
        if not self.in_window:
            return _NULL_CTX
        return self._phase_ctx(name)

    @property
    def in_window(self) -> bool:
        """Whether phases are being measured right now."""
        return self._window_active and not self._finalized

    @contextlib.contextmanager
    def _phase_ctx(self, name: str):
        timer = self.timers(_TIMER_PREFIX + name)
        t0 = time.perf_counter()
        if not timer.started_:
            timer.start(sync=False)  # previous fenced stop already drained
            own = True
        else:  # pragma: no cover - re-entrant phase; count outer span only
            own = False
        try:
            yield
        finally:
            self._fence()  # charge dispatched device work to this phase
            t1 = time.perf_counter()
            if own:
                timer.stop(sync=False)
            if self._in_step:
                self._phase_acc[name] = self._phase_acc.get(name, 0.0) + (t1 - t0)
            self._emit_event(name, t0, t1, cat="phase")

    def end_step(self, step: Optional[int] = None, comm_counters=None,
                 cost_cb: Optional[Callable[[], Optional[Dict]]] = None,
                 mem_cb: Optional[Callable[[], Optional[Dict]]] = None,
                 live_mem_cb: Optional[Callable[[], Optional[Dict]]] = None
                 ) -> None:
        if not self._in_step:
            return
        self._fence()
        t1 = time.perf_counter()
        total = t1 - self._step_t0
        measured = sum(self._phase_acc.values())
        other = max(0.0, total - measured)
        rec = {
            "step": self._step_idx,
            "total_s": total,
            "phases_s": dict(self._phase_acc),
            "other_s": other,
        }
        self.records.append(rec)
        self._emit_event(f"step {self._step_idx}", self._step_t0, t1,
                         cat="step", args={"phases_ms": {
                             k: round(v * 1e3, 3)
                             for k, v in self._phase_acc.items()}})
        self._in_step = False
        # compiled-step cost, once per window — AFTER the envelope closed:
        # cost extraction re-lowers the step (a compile) and must never be
        # charged to a measured span
        if cost_cb is not None and "optimizer_step" not in self._costs:
            try:
                cost = cost_cb()
            except Exception as e:  # pragma: no cover
                logger.warning(f"step_profiler: cost callback failed: {e}")
                cost = None
            if cost:
                self.set_cost("optimizer_step", cost)
        # compiled-step memory, once per window — same placement as the
        # cost callback: the lowering is a compile-cache hit but still
        # host work that must not land inside a measured span
        if mem_cb is not None and self._memory is None:
            try:
                self.set_memory(mem_cb())
            except Exception as e:  # pragma: no cover
                logger.warning(
                    f"step_profiler: memory callback failed: {e}")
        # live allocator watermarks: a host-local PJRT query (no sync),
        # sampled inside the already-fenced window and maxed over steps
        if live_mem_cb is not None:
            try:
                stats = live_mem_cb()
            except Exception:  # pragma: no cover
                stats = None
            if stats:
                for k, v in stats.items():
                    self._live_mem_peak[k] = max(
                        self._live_mem_peak.get(k, 0.0), float(v))
        if self._step_idx >= self.window.stop - 1:
            self.finalize(comm_counters=comm_counters)

    # -- compiled-step cost -------------------------------------------------
    def record_cost(self, key: str, fn: Callable, args, mult: int = 1) -> None:
        """Record XLA cost analysis of ``fn(*args)`` once per ``key``.

        ``mult`` scales the contribution into the per-step total (e.g. the
        fwd/bwd program runs ``gradient_accumulation_steps`` times per
        optimizer step). Cheap after the first call: a dict lookup.
        """
        if key in self._costs or not self._window_active or self._finalized:
            return
        try:
            from deepspeed_tpu.profiling.flops_profiler.profiler import (
                cost_analysis)

            cost = cost_analysis(fn, *args)
        except Exception as e:  # pragma: no cover - backend w/o cost model
            logger.warning(f"step_profiler: cost analysis for {key!r} "
                           f"unavailable: {e}")
            cost = {"flops": 0.0, "bytes_accessed": 0.0, "optimal_seconds": 0.0}
        cost["mult"] = mult
        self._costs[key] = cost

    def set_cost(self, key: str, cost: Dict[str, float], mult: int = 1) -> None:
        """Record a pre-computed cost dict (``{"flops", "bytes_accessed"}``)."""
        c = dict(cost)
        c.setdefault("flops", 0.0)
        c.setdefault("bytes_accessed", 0.0)
        c["mult"] = mult
        self._costs[key] = c

    def has_cost(self, key: str) -> bool:
        return key in self._costs

    @property
    def flops_per_step(self) -> float:
        """Per-device FLOPs per optimizer step (post-partition module)."""
        return sum(c["flops"] * c["mult"] for c in self._costs.values())

    @property
    def bytes_per_step(self) -> float:
        return sum(c["bytes_accessed"] * c["mult"] for c in self._costs.values())

    # -- results -----------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        n = len(self.records)
        if not n:
            return {"steps_profiled": 0}
        totals = [r["total_s"] for r in self.records]
        mean_s = sum(totals) / n
        phases: Dict[str, float] = {}
        for r in self.records:
            for k, v in r["phases_s"].items():
                phases[k] = phases.get(k, 0.0) + v
            phases["other"] = phases.get("other", 0.0) + r["other_s"]
        phases_ms = {k: v / n * 1e3 for k, v in phases.items()}
        covered = sum(v for k, v in phases.items() if k != "other")
        peak, peak_src = peak_tflops(override=self.cfg.peak_tflops)
        tflops = (self.flops_per_step / mean_s / 1e12) if mean_s > 0 else 0.0
        out = {
            "steps_profiled": n,
            "window": [self.window.start, self.window.stop],
            "step_time_ms": {"mean": mean_s * 1e3,
                             "min": min(totals) * 1e3,
                             "max": max(totals) * 1e3},
            "phases_ms": phases_ms,
            # fraction of the fenced step envelope explained by named
            # phases (the acceptance bar: >= 0.95 i.e. within 5%)
            "phase_coverage": covered / sum(totals) if sum(totals) else 0.0,
            "flops_per_step": self.flops_per_step,
            "bytes_accessed_per_step": self.bytes_per_step,
            "analytic_tflops": tflops,
            "peak_tflops": peak,
            "peak_source": peak_src,
            "analytic_mfu": tflops / peak if peak else 0.0,
            "hbm_gb_per_s": (self.bytes_per_step / mean_s / 1e9)
            if mean_s > 0 else 0.0,
            "costs": {k: dict(v) for k, v in self._costs.items()},
        }
        if self._memory is not None:
            out["memory"] = dict(self._memory)
        if self._live_mem_peak:
            out["live_memory_peak"] = dict(self._live_mem_peak)
        return out

    def mem_counters(self) -> Dict[str, float]:
        """Flat ``Mem/*`` counters: the compiled-step breakdown plus
        ``live_``-prefixed allocator watermarks (empty on backends
        without either source — CPU with no mem_cb set)."""
        out = {k: float(v) for k, v in (self._memory or {}).items()}
        for k, v in self._live_mem_peak.items():
            out[f"live_{k}"] = float(v)
        return out

    def perf_counters(self) -> Dict[str, float]:
        """Flat numeric counters for ``Monitor`` export (``Perf/<name>``)."""
        s = self.summary()
        if not s.get("steps_profiled"):
            return {}
        out = {
            "steps_profiled": float(s["steps_profiled"]),
            "step_ms_mean": s["step_time_ms"]["mean"],
            "phase_coverage": s["phase_coverage"],
            "flops_per_step": s["flops_per_step"],
            "bytes_accessed_per_step": s["bytes_accessed_per_step"],
            "analytic_tflops": s["analytic_tflops"],
            "analytic_mfu": s["analytic_mfu"],
            "hbm_gb_per_s": s["hbm_gb_per_s"],
        }
        for k, v in s["phases_ms"].items():
            out[f"phase_{k}_ms"] = v
        out.update(self.aux_counters)
        return out

    # -- trace export ------------------------------------------------------
    def _emit_event(self, name: str, t0: float, t1: float, cat: str = "phase",
                    args: Optional[Dict] = None) -> None:
        ev = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": (t0 - self._t_base) * 1e6,
            "dur": (t1 - t0) * 1e6,
            "pid": self._pid,
            "tid": 1 if cat == "step" else 0,
        }
        if args:
            ev["args"] = args
        self._events.append(ev)

    def trace_events(self) -> Dict[str, Any]:
        meta = [
            {"name": "process_name", "ph": "M", "pid": self._pid, "tid": 0,
             "args": {"name": "deepspeed_tpu step profiler"}},
            {"name": "thread_name", "ph": "M", "pid": self._pid, "tid": 0,
             "args": {"name": "phases"}},
            {"name": "thread_name", "ph": "M", "pid": self._pid, "tid": 1,
             "args": {"name": "steps"}},
        ]
        return {"traceEvents": meta + list(self._events),
                "displayTimeUnit": "ms"}

    def export_trace(self, path: Optional[str] = None) -> Optional[str]:
        path = path or self.cfg.trace_path
        if not path:
            return None
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.trace_events(), f)
        os.replace(tmp, path)
        return path

    # -- jax.profiler passthrough -----------------------------------------
    def _maybe_start_jax_trace(self) -> None:
        if not (self.cfg.jax_trace and self.cfg.jax_trace_dir):
            return
        try:
            import jax

            jax.profiler.start_trace(self.cfg.jax_trace_dir)
            self._jax_trace_on = True
        except Exception as e:  # pragma: no cover
            logger.warning(f"step_profiler: jax trace unavailable: {e}")

    def _stop_jax_trace(self) -> None:
        if not self._jax_trace_on:
            return
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:  # pragma: no cover
            pass
        self._jax_trace_on = False

    # -- window close ------------------------------------------------------
    def finalize(self, comm_counters=None) -> Optional[Dict[str, Any]]:
        """Close the window: stop traces, write artifacts, export counters.

        Idempotent; safe to call even if the run ended mid-window."""
        if self._finalized:
            return None
        if self._in_step:  # run ended inside a step — close the envelope
            self.end_step()
            if self._finalized:  # end_step on last window step recursed here
                return None
        if callable(comm_counters):
            try:
                comm_counters = comm_counters()
            except Exception:  # pragma: no cover
                comm_counters = None
        self._finalized = True
        self._stop_jax_trace()
        summary = self.summary()
        path = None
        try:
            import jax

            rank0 = jax.process_index() == 0
        except Exception:  # pragma: no cover
            rank0 = True
        if rank0:
            path = self.export_trace()
        if self.monitor is not None and getattr(self.monitor, "enabled", False) \
                and self.cfg.emit_counters:
            from deepspeed_tpu.monitor.monitor import counter_events

            step = self.records[-1]["step"] if self.records else 0
            events = counter_events("Perf", self.perf_counters(), step)
            if comm_counters:
                events += counter_events("Comm", comm_counters, step)
            mem = self.mem_counters()
            if mem:
                events += counter_events("Mem", mem, step)
            if events:
                self.monitor.write_events(events)
        if summary.get("steps_profiled"):
            log_dist(
                "step_profiler: {n} steps, mean {ms:.1f} ms, coverage "
                "{cov:.1%}, analytic {tf:.2f} TFLOPS ({mfu:.1%} MFU vs "
                "{peak:g} peak, {src})".format(
                    n=summary["steps_profiled"],
                    ms=summary["step_time_ms"]["mean"],
                    cov=summary["phase_coverage"],
                    tf=summary["analytic_tflops"],
                    mfu=summary["analytic_mfu"],
                    peak=summary["peak_tflops"],
                    src=summary["peak_source"]) +
                (f", trace → {path}" if path else ""),
                ranks=[0])
        return summary
