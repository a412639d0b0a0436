"""Wall-clock and throughput timers.

Parity with reference ``deepspeed/utils/timer.py`` (SynchronizedWallClockTimer
:20-133, ThroughputTimer :135). CUDA-event synchronisation is replaced by
``jax.block_until_ready`` on live arrays (the honest TPU analogue: XLA is
async-dispatched exactly like CUDA streams).
"""

import time
from collections import OrderedDict

from deepspeed_tpu.utils.logging import log_dist


_fence_fn = None


def _sync():
    """Timer-internal fence; never raises (timers must work device-less)."""
    try:
        fence()
    except Exception:  # pragma: no cover
        pass


def fence(tree=None):
    """Wait for the device before reading the wall clock (JAX dispatch is
    asynchronous: without this a timing measures the enqueue).

    With ``tree`` (e.g. ``engine.params``): ``jax.block_until_ready`` on
    it. Without: a tiny program enqueued behind everything pending — the
    device runs programs in order — and waited on. On the v5e the plain
    wait and a scalar host read of a device-side reduction agree (29.0 vs
    29.5 ms around a 40-matmul chain, chip run PR 21), so the plain wait
    is the one form kept.

    Call ``prewarm_fence()`` once outside any timed window first: the
    no-tree program's lazy first compile inside a measured region reads as
    a throughput regression.
    """
    import jax
    import jax.numpy as jnp

    if tree is not None and jax.tree.leaves(tree):
        jax.block_until_ready(tree)
        return
    global _fence_fn
    if _fence_fn is None:
        _fence_fn = jax.jit(lambda: jnp.zeros(()))
    jax.block_until_ready(_fence_fn())


def prewarm_fence():
    """Compile + run the no-tree fence program once (outside timed regions)."""
    _sync()


class _Timer:
    def __init__(self, name: str):
        self.name_ = name
        self.started_ = False
        self.elapsed_ = 0.0
        self.start_time = 0.0
        self.count = 0

    def start(self, sync: bool = True):
        assert not self.started_, f"timer {self.name_} has already been started"
        if sync:
            _sync()
        self.start_time = time.time()
        self.started_ = True

    def stop(self, reset: bool = False, sync: bool = True):
        assert self.started_, f"timer {self.name_} is not started"
        if sync:
            _sync()
        elapsed = time.time() - self.start_time
        if reset:
            self.elapsed_ = elapsed
        else:
            self.elapsed_ += elapsed
        self.started_ = False
        self.count += 1

    def reset(self):
        self.started_ = False
        self.elapsed_ = 0.0
        self.count = 0

    def elapsed(self, reset: bool = True):
        started = self.started_
        if started:
            self.stop()
        elapsed = self.elapsed_
        if reset:
            self.reset()
        if started:
            self.start()
        return elapsed

    def mean(self):
        return (self.elapsed_ / self.count) if self.count else 0.0


class SynchronizedWallClockTimer:
    """Named-timer group; `log()` prints a one-line breakdown like the
    reference's wall_clock_breakdown output (engine.py:2063-2078)."""

    def __init__(self):
        self.timers = OrderedDict()

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    def has(self, name: str) -> bool:
        return name in self.timers

    def log(self, names=None, normalizer: float = 1.0, reset: bool = True, ranks=None):
        assert normalizer > 0.0
        names = names if names is not None else list(self.timers)
        parts = []
        for name in names:
            if name in self.timers:
                elapsed = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {elapsed:.2f}")
        if parts:
            log_dist("time (ms) | " + " | ".join(parts), ranks=ranks)

    def get_mean(self, names, normalizer: float = 1.0):
        assert normalizer > 0.0
        return {
            name: self.timers[name].mean() * 1000.0 / normalizer
            for name in names
            if name in self.timers
        }


class ThroughputTimer:
    """samples/sec + optional TFLOPS reporting (reference utils/timer.py:135)."""

    def __init__(
        self,
        batch_size: int,
        start_step: int = 2,
        steps_per_output: int = 50,
        monitor_memory: bool = False,
        logging_fn=None,
    ):
        self.start_time = 0.0
        self.end_time = 0.0
        self.started = False
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self.step_elapsed_time = 0.0
        self._fence_epoch_time = None  # wall clock at last fenced report
        self._fence_epoch_step = 0
        self._fenced_total_time = 0.0
        self._fenced_total_steps = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or (lambda msg: log_dist(msg, ranks=[0]))
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        if self.initialized:
            return
        # compile the queue-drain fence now, while the caller is still in
        # its own compile/warmup phase — the lazy first compile must not
        # land inside a measured region
        prewarm_fence()
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step:
            # NO device fence here: syncing every micro step would serialize
            # the dispatch pipeline (one fence costs a full in-flight step).
            # Throughput is fenced only at reporting boundaries (and the
            # baseline is seeded in stop() at the warmup crossing), so the
            # running average is exact and intermediate steps overlap.
            self.start_time = time.time()

    def _reseed_fence_epoch(self):
        """Drain the device queue and (re)anchor the fenced wall-clock
        baseline at the current step count."""
        _sync()
        self._fence_epoch_time = time.time()
        self._fence_epoch_step = self.global_step_count

    def stop(self, global_step: bool = False, report_speed: bool = True):
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        if global_step:
            self.global_step_count += 1
            if (self.global_step_count >= self.start_step
                    and self._fence_epoch_time is None):
                # crossing from warmup into the measured region: drain the
                # queue and seed the fenced baseline HERE, at the tail of
                # the last warmup step, so the drain (which waits out every
                # in-flight compile/step) is never charged to the first
                # measured interval
                self._reseed_fence_epoch()
        if self.start_time > 0:
            self.end_time = time.time()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            self.step_elapsed_time += duration
            self.start_time = 0.0
            if global_step and report_speed and (
                self.global_step_count % self.steps_per_output == 0
            ):
                # steps in between are dispatch-only (no fence); honest
                # throughput = samples between fenced boundaries / the
                # fenced wall time between them
                prev_time, prev_step = (self._fence_epoch_time,
                                        self._fence_epoch_step)
                self._reseed_fence_epoch()
                curr = 0.0
                if prev_time is not None:
                    span = self._fence_epoch_time - prev_time
                    steps = self.global_step_count - prev_step
                    if span > 0:
                        curr = self.batch_size * steps / span
                    self._fenced_total_time += span
                    self._fenced_total_steps += steps
                self.logging(
                    "epoch={}/micro_step={}/global_step={}, "
                    "RunningAvgSamplesPerSec={:.3f}, CurrSamplesPerSec={:.3f}".format(
                        self.epoch_count,
                        self.micro_step_count,
                        self.global_step_count,
                        self.avg_samples_per_sec(),
                        curr,
                    )
                )
        if global_step:
            self.step_elapsed_time = 0.0

    def avg_samples_per_sec(self):
        # fenced boundary-to-boundary accounting only: before the first
        # fenced interval the host-side durations are dispatch-only and
        # would overreport by orders of magnitude — return 0 ("no honest
        # measurement yet") instead
        if self._fenced_total_time > 0:
            return (self.batch_size * self._fenced_total_steps
                    / self._fenced_total_time)
        return 0.0
