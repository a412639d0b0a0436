"""Crash-forensics flight recorder.

A bounded in-memory ring of the last N optimizer steps — phase timings,
loss / grad-norm (only when the engine already materialized them on
host), ``Comm/*`` wire bytes, feed-health counters, live memory
watermarks — plus the last M bus events. On a fatal path it dumps one
atomic, crc32-stamped ``blackbox-rank{k}.json`` that survives the
process: the per-rank evidence the elastic agent and launcher sweep into
a run-level crash report (``crash_report.py``).

Zero-added-syncs discipline (the step-profiler bar): per-step phase
spans here are **host dispatch times** (``perf_counter`` around the same
``with`` blocks the profiler fences) — no fence is ever issued by this
module. Inside the profiler's fenced window those spans coincide with
true device time; outside it they are the honest host-side view. Loss
and grad-norm are recorded only when some already-paid-for host
materialization (monitor export, sentinel verdict) produced them — the
recorder itself never pulls a device value.

Dump triggers (docs/observability.md "Flight recorder" trigger matrix):

* ``DivergenceError`` (exit 13) — explicit dump in the engine before the
  raise (the usual worker exit is a *caught* DivergenceError +
  ``sys.exit(13)``, which never reaches ``sys.excepthook``);
* ``HangWatchdog`` abort (exit 14) — dump inside the ``on_fire``
  callback, because the abort is ``os._exit`` which skips ``atexit``;
* SIGTERM (or any configured signal) — chained handler, previous handler
  (e.g. the graceful-shutdown flag-setter) still runs after the dump;
* unhandled exceptions — ``sys.excepthook`` chain;
* ``atexit`` backstop — dumps only when a fatal reason was armed but the
  corresponding dump never happened (e.g. an exit path we don't hook).

stdlib-only, like ``runtime/sentinel.py``: supervisors import this
module to read dumps without dragging in jax.
"""

import atexit
import json
import os
import signal as signal_module
import socket
import sys
import threading
import time
import traceback
import zlib
from collections import deque
from typing import Any, Callable, Dict, List, Optional

BLACKBOX_SCHEMA = "ds-tpu-blackbox/1"


def _canonical_bytes(payload: Dict[str, Any]) -> bytes:
    """Deterministic serialization the crc is computed over. ``default=
    str`` so an odd payload value degrades to its repr instead of killing
    the dump on the crash path."""
    return json.dumps(payload, sort_keys=True, separators=(",", ":"),
                      default=str).encode("utf-8")


def blackbox_crc(payload: Dict[str, Any]) -> int:
    """crc32 over the canonical payload *without* its ``crc32`` field."""
    body = {k: v for k, v in payload.items() if k != "crc32"}
    return zlib.crc32(_canonical_bytes(body)) & 0xFFFFFFFF


class FlightRecorder:
    """Bounded step/event ring with an atomic crash dump.

    All mutating methods are thread-safe: the hang watchdog dumps from
    its daemon thread while the training loop records steps.
    """

    def __init__(self, ring_steps: int = 64, ring_events: int = 256,
                 dump_dir: Optional[str] = None, rank: int = 0,
                 bus=None, clock: Callable[[], float] = time.time):
        if ring_steps < 1:
            raise ValueError(f"ring_steps must be >= 1, got {ring_steps}")
        if ring_events < 1:
            raise ValueError(f"ring_events must be >= 1, got {ring_events}")
        self.rank = int(rank)
        self.dump_dir = dump_dir
        self._clock = clock
        self._lock = threading.Lock()
        self._steps: deque = deque(maxlen=ring_steps)
        self._events: deque = deque(maxlen=ring_events)
        self._static: Dict[str, Any] = {}
        self._flush_hooks: List[Callable[[], None]] = []
        self._bus = bus
        self._dumped_path: Optional[str] = None
        self._pending_fatal: Optional[Dict[str, Any]] = None
        # current-step accumulator (begin_step/phase/record_step)
        self._cur_step: Optional[int] = None
        self._step_t0 = 0.0
        self._phase_acc: Dict[str, float] = {}
        if bus is not None:
            bus.subscribe(self.on_event)

    # -- static context ----------------------------------------------------
    def set_static(self, **info) -> None:
        """Attach run-constant context (world size, batch triad, model
        id, config digests) reproduced verbatim in every dump."""
        with self._lock:
            self._static.update(info)

    def add_flush_hook(self, fn: Callable[[], None]) -> None:
        """Run ``fn()`` right before a dump (the CsvMonitor durability
        hook: flush counter CSVs so the crash doesn't truncate them)."""
        with self._lock:
            self._flush_hooks.append(fn)

    # -- per-step recording ------------------------------------------------
    def begin_step(self, step: int) -> None:
        """Anchor the current step's host clock; idempotent per step."""
        if self._cur_step == step:
            return
        self._cur_step = step
        self._step_t0 = time.perf_counter()
        self._phase_acc = {}

    def add_phase_time(self, name: str, seconds: float) -> None:
        """Add host time of phase ``name`` to the current step's record
        (the engine's phase context measures it; perf_counter only)."""
        self._phase_acc[name] = self._phase_acc.get(name, 0.0) + seconds

    def record_step(self, step: int, loss: Optional[float] = None,
                    grad_norm: Optional[float] = None,
                    comm: Optional[Dict[str, float]] = None,
                    feed: Optional[Dict[str, float]] = None,
                    mem: Optional[Dict[str, int]] = None,
                    **extra) -> Dict[str, Any]:
        """Append one step record to the ring and close the accumulator.

        Callers pass only values that are ALREADY host-side (see module
        docstring); ``None`` fields are omitted from the record.
        """
        rec: Dict[str, Any] = {"step": int(step), "ts": self._clock()}
        # any open accumulator belongs to this record: the engine bumps
        # global_steps inside the optimizer step, so the step id at
        # record time is begin time's id + 1 — match on "open", not "=="
        if self._cur_step is not None:
            rec["total_s"] = time.perf_counter() - self._step_t0
            if self._phase_acc:
                rec["phases_s"] = dict(self._phase_acc)
        if loss is not None:
            rec["loss"] = float(loss)
        if grad_norm is not None:
            rec["grad_norm"] = float(grad_norm)
        if comm:
            rec["comm"] = {str(k): v for k, v in comm.items()}
        if feed:
            rec["feed"] = {str(k): float(v) for k, v in feed.items()}
        if mem:
            rec["mem"] = {str(k): v for k, v in mem.items()}
        if extra:
            rec.update(extra)
        with self._lock:
            self._steps.append(rec)
        self._cur_step = None
        self._phase_acc = {}
        return rec

    # -- bus fan-in --------------------------------------------------------
    def on_event(self, event: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(dict(event))

    # -- introspection (tests, crash report) -------------------------------
    def steps(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._steps)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    @property
    def dumped_path(self) -> Optional[str]:
        return self._dumped_path

    # -- fatal-path dump ---------------------------------------------------
    def arm(self, reason: str, exit_code: Optional[int] = None) -> None:
        """Mark a fatal reason so the ``atexit`` backstop dumps if no
        explicit dump happens before the interpreter exits."""
        with self._lock:
            self._pending_fatal = {"reason": reason, "exit_code": exit_code}

    def payload(self, reason: str, exit_code: Optional[int] = None,
                exc: Optional[BaseException] = None) -> Dict[str, Any]:
        """The dump body, crc-stamped. Pure (no I/O) so tests can check
        the schema without touching disk."""
        with self._lock:
            body: Dict[str, Any] = {
                "schema": BLACKBOX_SCHEMA,
                "rank": self.rank,
                "reason": reason,
                "exit_code": exit_code,
                "ts": self._clock(),
                "host": socket.gethostname(),
                "pid": os.getpid(),
                "static": dict(self._static),
                "steps": list(self._steps),
                "events": list(self._events),
            }
        if self._bus is not None:
            body["event_counts"] = self._bus.counts()
        if exc is not None:
            body["exception"] = {
                "type": type(exc).__name__,
                "message": str(exc),
                "traceback": traceback.format_exception(
                    type(exc), exc, exc.__traceback__),
            }
        body["crc32"] = blackbox_crc(body)
        return body

    def dump(self, reason: str, exit_code: Optional[int] = None,
             exc: Optional[BaseException] = None,
             force: bool = False) -> Optional[str]:
        """Write ``blackbox-rank{k}.json`` atomically (tmp + rename).

        Idempotent: the FIRST fatal reason wins (a SIGTERM arriving while
        the divergence dump is on disk must not overwrite the evidence)
        unless ``force``. Returns the path, or None when ``dump_dir`` is
        unset or the write failed — a dump failure must never mask the
        original crash.
        """
        if self.dump_dir is None:
            return None
        if self._dumped_path is not None and not force:
            return self._dumped_path
        for hook in list(self._flush_hooks):
            try:
                hook()
            except Exception:
                pass  # a broken flush hook must not block the dump
        try:
            os.makedirs(self.dump_dir, exist_ok=True)
            path = os.path.join(self.dump_dir,
                                f"blackbox-rank{self.rank}.json")
            tmp = f"{path}.tmp.{os.getpid()}"
            body = self.payload(reason, exit_code=exit_code, exc=exc)
            with open(tmp, "w") as f:
                json.dump(body, f, indent=1, default=str)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            self._dumped_path = path
            with self._lock:
                self._pending_fatal = None
            return path
        except Exception as e:
            try:
                from deepspeed_tpu.utils.logging import logger

                logger.warning("flight recorder dump failed: %s", e)
            except Exception:
                pass
            return None

    def retract_dump(self) -> None:
        """Remove a dump that turned out not to be a crash.

        The SIGTERM handler dumps immediately (at signal time nobody
        knows whether the grace save will succeed); when the graceful
        shutdown then commits its checkpoint and exits cleanly, that
        blackbox is stale evidence — left behind it would pollute the
        next crash sweep of the same telemetry dir. Best-effort: a
        failure to unlink must not break the clean exit."""
        path, self._dumped_path = self._dumped_path, None
        with self._lock:
            self._pending_fatal = None
        if path is not None:
            try:
                os.remove(path)
            except OSError:
                pass

    def _atexit_dump(self) -> None:
        pending = self._pending_fatal
        if pending is not None and self._dumped_path is None:
            self.dump(pending["reason"], exit_code=pending.get("exit_code"))

    def close(self) -> None:
        """Unsubscribe from the bus (engine teardown / tests)."""
        if self._bus is not None:
            self._bus.unsubscribe(self.on_event)
            self._bus = None


def install_crash_handlers(recorder: FlightRecorder,
                           signals=("SIGTERM",),
                           excepthook: bool = True,
                           use_atexit: bool = True) -> Callable[[], None]:
    """Hook ``recorder.dump`` into the process's fatal paths.

    Chains, never replaces: the previous ``sys.excepthook`` and any
    previous signal handler (e.g. the engine's graceful-shutdown
    flag-setter) run *after* the dump. Signal handlers install only on
    the main thread (the ``signal`` module's requirement — same guard as
    the engine's graceful shutdown). Returns an ``uninstall()`` callable
    restoring what was replaced; ``atexit`` registrations stay (they are
    no-ops once nothing fatal is armed).
    """
    restorers: List[Callable[[], None]] = []

    if excepthook:
        prev_hook = sys.excepthook

        def _hook(exc_type, exc, tb):
            code = getattr(exc, "exit_code", 1)
            try:
                recorder.dump("unhandled_exception", exit_code=code, exc=exc)
            except Exception:
                pass
            prev_hook(exc_type, exc, tb)

        sys.excepthook = _hook

        def _restore_hook(h=_hook, p=prev_hook):
            if sys.excepthook is h:
                sys.excepthook = p

        restorers.append(_restore_hook)

    on_main = threading.current_thread() is threading.main_thread()
    if on_main:
        for name in signals:
            signum = getattr(signal_module, str(name), None)
            if signum is None:
                continue

            prev = signal_module.getsignal(signum)

            def _handler(sig, frame, _name=str(name), _prev=prev):
                try:
                    recorder.dump(f"signal:{_name}", exit_code=128 + sig)
                except Exception:
                    pass
                if callable(_prev):
                    _prev(sig, frame)
                elif _prev == signal_module.SIG_DFL:
                    # preserve default semantics: re-deliver with the
                    # default handler restored so the process still dies
                    signal_module.signal(sig, signal_module.SIG_DFL)
                    os.kill(os.getpid(), sig)

            signal_module.signal(signum, _handler)

            def _restore_sig(snum=signum, h=_handler, p=prev):
                if signal_module.getsignal(snum) is h:
                    try:
                        signal_module.signal(snum, p)
                    except (ValueError, TypeError):
                        pass

            restorers.append(_restore_sig)

    if use_atexit:
        atexit.register(recorder._atexit_dump)

        def _restore_atexit():
            try:
                atexit.unregister(recorder._atexit_dump)
            except Exception:
                pass

        restorers.append(_restore_atexit)

    def uninstall():
        for r in restorers:
            r()

    return uninstall
