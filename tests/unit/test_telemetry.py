"""Telemetry bus, flight recorder, crash handlers, crash-report sweep,
monitor fan-out isolation, and the engine wiring (docs/observability.md
"Telemetry events" / "Flight recorder" / "Memory accounting").

The zero-added-syncs bar (same as test_step_profiler): the recorder must
never materialize a device value itself — loss/grad-norm appear in step
records ONLY when the monitor or sentinel already paid for the host
transfer, and live memory sampling self-disables on backends (CPU) whose
``memory_stats()`` is None.
"""

import gc
import json
import os
import signal
import sys

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.config import (
    DeepSpeedConfig,
    DeepSpeedConfigError,
    TelemetryConfig,
)
from deepspeed_tpu.runtime.dataloader import RepeatingLoader
from deepspeed_tpu.runtime.sentinel import DivergenceError
from deepspeed_tpu.telemetry import (
    BLACKBOX_SCHEMA,
    FlightRecorder,
    TelemetryBus,
    install_crash_handlers,
    load_blackbox,
    sweep_blackbox_dumps,
    telemetry_bus,
    verify_blackbox,
)
from deepspeed_tpu.telemetry.flight_recorder import blackbox_crc
from deepspeed_tpu.utils import fault_injection as fi

from unit.simple_model import SimpleModel, random_dataset


@pytest.fixture(autouse=True)
def _fresh_global_bus():
    """Engines subscribe their recorders to the process-global bus; give
    every test a clean slate so counts/subscribers don't leak across."""
    telemetry_bus.reset()
    yield
    telemetry_bus.reset()


# ---------------------------------------------------------------------------
# bus
# ---------------------------------------------------------------------------
class TestTelemetryBus:
    def test_publish_order_and_envelope(self):
        bus = TelemetryBus(rank=3)
        seen = []
        bus.subscribe(seen.append)
        bus.publish("a.one", step=5, foo=1)
        bus.publish("a.two", severity="warning")
        assert [e["kind"] for e in seen] == ["a.one", "a.two"]
        ev = seen[0]
        assert ev["rank"] == 3 and ev["step"] == 5 and ev["foo"] == 1
        assert ev["severity"] == "info" and ev["ts"] > 0
        assert "step" not in seen[1] and seen[1]["severity"] == "warning"

    def test_counts_and_unsubscribe(self):
        bus = TelemetryBus(rank=0)
        seen = []
        bus.subscribe(seen.append)
        bus.publish("k")
        bus.publish("k")
        bus.unsubscribe(seen.append)
        bus.publish("k")
        assert bus.counts() == {"k": 3}
        assert len(seen) == 2

    def test_raising_subscriber_isolated(self):
        bus = TelemetryBus(rank=0)
        seen = []

        def bad(ev):
            raise RuntimeError("boom")

        bus.subscribe(bad)
        bus.subscribe(seen.append)
        bus.publish("k")  # must not raise
        bus.publish("k")
        assert len(seen) == 2

    def test_bound_method_subscriber_weakly_held(self):
        bus = TelemetryBus(rank=0)

        class Sub:
            def __init__(self):
                self.seen = []

            def on_event(self, ev):
                self.seen.append(ev)

        s = Sub()
        bus.subscribe(s.on_event)
        bus.publish("k")
        assert len(s.seen) == 1
        del s
        gc.collect()
        bus.publish("k")  # dead ref pruned, no error
        with bus._lock:
            assert not bus._subscribers


# ---------------------------------------------------------------------------
# config block
# ---------------------------------------------------------------------------
class TestTelemetryConfig:
    def test_defaults(self):
        cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1})
        t = cfg.telemetry
        assert t.enabled and t.dump_dir is None
        assert t.ring_steps == 64 and t.ring_events == 256
        assert t.dump_signals == ["SIGTERM"]

    def test_validation(self):
        with pytest.raises(DeepSpeedConfigError):
            TelemetryConfig.from_dict({"ring_steps": 0})
        with pytest.raises(DeepSpeedConfigError):
            TelemetryConfig.from_dict({"dump_signals": ["SIGNOPE"]})


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_step_ring_bounded(self):
        rec = FlightRecorder(ring_steps=4, ring_events=4)
        for i in range(10):
            rec.record_step(i, loss=float(i))
        steps = rec.steps()
        assert [s["step"] for s in steps] == [6, 7, 8, 9]

    def test_none_fields_omitted(self):
        rec = FlightRecorder()
        r = rec.record_step(1, loss=None, grad_norm=None, comm=None,
                            feed=None, mem=None)
        assert set(r) == {"step", "ts"}
        r2 = rec.record_step(2, loss=1.5, mem={"bytes_in_use": 7},
                             skipped=True)
        assert r2["loss"] == 1.5 and r2["mem"] == {"bytes_in_use": 7}
        assert r2["skipped"] is True

    def test_phase_accumulation(self):
        rec = FlightRecorder()
        rec.begin_step(3)
        rec.add_phase_time("compiled_step", 0.25)
        rec.add_phase_time("compiled_step", 0.5)
        rec.add_phase_time("h2d", 0.125)
        r = rec.record_step(3)
        assert r["total_s"] >= 0
        assert r["phases_s"] == {"compiled_step": 0.75, "h2d": 0.125}
        # accumulator closed: next record has no stale phases
        assert "phases_s" not in rec.record_step(4)

    def test_engine_phase_wraps_step_profiler_phase(self):
        """The engine's one phase context enters the step profiler's
        phase inside its window only, and times the phase (profiler's
        fence included) for the recorder."""
        from deepspeed_tpu.runtime.engine import _PhaseSpan

        entered = []

        class Inner:
            def __enter__(self):
                entered.append("in")

            def __exit__(self, *a):
                entered.append("out")

        class Prof:
            in_window = True

            def phase(self, name):
                entered.append(name)
                return Inner()

        class Eng:
            global_steps = 1
            step_profiler = Prof()
            flight_recorder = FlightRecorder()

        Eng.flight_recorder.begin_step(1)
        with _PhaseSpan(Eng, "p"):
            entered.append("body")
        assert entered == ["p", "in", "body", "out"]
        assert set(Eng.flight_recorder.record_step(1)["phases_s"]) == {"p"}
        Prof.in_window = False
        with _PhaseSpan(Eng, "q"):
            entered.append("body")
        assert entered[4:] == ["body"]

    def test_bus_events_ring(self):
        bus = TelemetryBus(rank=1)
        rec = FlightRecorder(ring_events=3, bus=bus)
        for i in range(5):
            bus.publish("k", i=i)
        assert [e["i"] for e in rec.events()] == [2, 3, 4]
        rec.close()
        bus.publish("k", i=99)
        assert len(rec.events()) == 3  # unsubscribed

    def test_payload_schema_and_crc(self):
        rec = FlightRecorder(rank=2)
        rec.set_static(world=8)
        rec.record_step(1, loss=2.0)
        p = rec.payload("divergence", exit_code=13,
                        exc=ValueError("nan loss"))
        assert p["schema"] == BLACKBOX_SCHEMA
        assert p["rank"] == 2 and p["exit_code"] == 13
        assert p["static"] == {"world": 8}
        assert p["exception"]["type"] == "ValueError"
        assert verify_blackbox(p)
        p["steps"][0]["loss"] = 999.0  # tamper
        assert not verify_blackbox(p)
        assert blackbox_crc(p) != p["crc32"]

    def test_dump_atomic_and_first_reason_wins(self, tmp_path):
        rec = FlightRecorder(dump_dir=str(tmp_path), rank=0)
        rec.record_step(1, loss=1.0)
        path = rec.dump("divergence", exit_code=13)
        assert path and os.path.basename(path) == "blackbox-rank0.json"
        # second fatal (e.g. SIGTERM during teardown) must not overwrite
        assert rec.dump("signal:SIGTERM", exit_code=143) == path
        payload, status = load_blackbox(path)
        assert status == "ok" and payload["reason"] == "divergence"
        # no stray tmp files: the write was atomic
        assert [f.name for f in tmp_path.iterdir()] == ["blackbox-rank0.json"]
        forced = rec.dump("second", exit_code=1, force=True)
        assert load_blackbox(forced)[0]["reason"] == "second"

    def test_dump_without_dir_is_noop(self):
        rec = FlightRecorder()
        assert rec.dump("divergence", exit_code=13) is None

    def test_dump_runs_flush_hooks_and_survives_broken_hook(self, tmp_path):
        rec = FlightRecorder(dump_dir=str(tmp_path))
        ran = []
        rec.add_flush_hook(lambda: ran.append(1))
        rec.add_flush_hook(lambda: 1 / 0)
        assert rec.dump("r") is not None
        assert ran == [1]

    def test_atexit_backstop_only_when_armed(self, tmp_path):
        rec = FlightRecorder(dump_dir=str(tmp_path))
        rec._atexit_dump()  # nothing armed -> no dump
        assert not list(tmp_path.iterdir())
        rec.arm("hang_watchdog", exit_code=14)
        rec._atexit_dump()
        payload, status = load_blackbox(rec.dumped_path)
        assert status == "ok"
        assert payload["reason"] == "hang_watchdog"
        assert payload["exit_code"] == 14


# ---------------------------------------------------------------------------
# crash handlers
# ---------------------------------------------------------------------------
class TestCrashHandlers:
    def test_excepthook_chains_and_uninstalls(self, tmp_path):
        rec = FlightRecorder(dump_dir=str(tmp_path))
        prev_calls = []
        orig_hook = sys.excepthook
        sys.excepthook = lambda *a: prev_calls.append(a)
        try:
            uninstall = install_crash_handlers(rec, signals=(),
                                               use_atexit=False)

            class Crash(RuntimeError):
                exit_code = 7

            err = Crash("die")
            sys.excepthook(Crash, err, None)
            payload, status = load_blackbox(rec.dumped_path)
            assert status == "ok"
            assert payload["reason"] == "unhandled_exception"
            assert payload["exit_code"] == 7  # exc.exit_code honored
            assert len(prev_calls) == 1  # previous hook still ran
            uninstall()
            assert sys.excepthook is not None
            sys.excepthook(Crash, err, None)
            assert len(prev_calls) == 2  # restored to the prev hook
        finally:
            sys.excepthook = orig_hook

    def test_signal_handler_dumps_then_chains(self, tmp_path):
        rec = FlightRecorder(dump_dir=str(tmp_path))
        chained = []
        prev = signal.signal(signal.SIGUSR1,
                             lambda s, f: chained.append(s))
        try:
            uninstall = install_crash_handlers(
                rec, signals=("SIGUSR1",), excepthook=False,
                use_atexit=False)
            os.kill(os.getpid(), signal.SIGUSR1)
            payload, status = load_blackbox(rec.dumped_path)
            assert status == "ok"
            assert payload["reason"] == "signal:SIGUSR1"
            assert payload["exit_code"] == 128 + signal.SIGUSR1
            assert chained == [signal.SIGUSR1]  # previous handler ran
            uninstall()
            os.kill(os.getpid(), signal.SIGUSR1)
            assert len(chained) == 2  # restored handler still works
        finally:
            signal.signal(signal.SIGUSR1, prev)

    def test_unknown_signal_name_skipped(self, tmp_path):
        rec = FlightRecorder(dump_dir=str(tmp_path))
        uninstall = install_crash_handlers(rec, signals=("SIGNOPE",),
                                           excepthook=False,
                                           use_atexit=False)
        uninstall()


# ---------------------------------------------------------------------------
# run-level crash report sweep
# ---------------------------------------------------------------------------
class TestCrashReportSweep:
    def _dump(self, tmp_path, rank, reason, exit_code, ts, step):
        rec = FlightRecorder(dump_dir=str(tmp_path), rank=rank,
                             clock=lambda: ts)
        rec.record_step(step, loss=0.5)
        rec.on_event({"ts": ts, "kind": "sentinel.skip", "rank": rank})
        assert rec.dump(reason, exit_code=exit_code)

    def test_sweep_merges_ranks(self, tmp_path):
        # rank 1 dies first (earliest ts) -> holds the root cause
        self._dump(tmp_path, 0, "signal:SIGTERM", 143, ts=200.0, step=31)
        self._dump(tmp_path, 1, "divergence", 13, ts=100.0, step=30)
        report = sweep_blackbox_dumps(str(tmp_path))
        assert report["num_ranks"] == 2
        assert report["reasons"] == {"signal:SIGTERM": 1, "divergence": 1}
        assert report["exit_codes"] == {"143": 1, "13": 1}
        assert report["first_fatal_rank"] == "1"
        assert report["last_step_min"] == 30
        assert report["last_step_max"] == 31
        # merged event tail is wall-clock ordered across ranks
        tail = report["events_tail"]
        assert [e["rank"] for e in tail] == [1, 0]
        assert os.path.exists(report["path"])
        with open(report["path"]) as f:
            assert json.load(f)["schema"] == "ds-tpu-crash-report/1"

    def test_sweep_flags_torn_dump(self, tmp_path):
        self._dump(tmp_path, 0, "divergence", 13, ts=1.0, step=1)
        path = tmp_path / "blackbox-rank0.json"
        payload = json.loads(path.read_text())
        payload["steps"][0]["loss"] = 666.0  # corrupt after the stamp
        path.write_text(json.dumps(payload))
        report = sweep_blackbox_dumps(str(tmp_path))
        assert report["ranks"]["0"]["status"] == "crc_mismatch"

    def test_sweep_empty_dir_returns_none(self, tmp_path):
        assert sweep_blackbox_dumps(str(tmp_path)) is None
        assert not (tmp_path / "crash-report.json").exists()


# ---------------------------------------------------------------------------
# MonitorMaster fan-out with fake backends (satellite)
# ---------------------------------------------------------------------------
class FakeBackend:
    def __init__(self, fail=False):
        self.events = []
        self.flushes = 0
        self.closes = 0
        self.enabled = True
        self.fail = fail

    def write_events(self, evs):
        if self.fail:
            raise IOError("disk full")
        self.events.extend(evs)

    def flush(self):
        self.flushes += 1

    def close(self):
        self.closes += 1


def fanout_master():
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1})
    return MonitorMaster(cfg)


class TestMonitorMasterFanout:
    def test_event_ordering_preserved(self):
        master = fanout_master()
        fake = FakeBackend()
        master.add_backend(fake)
        assert master.enabled
        master.write_events([("Train/loss", 1.0, 1), ("Train/lr", 0.1, 1)])
        master.write_events([("Train/loss", 0.9, 2)])
        assert fake.events == [("Train/loss", 1.0, 1), ("Train/lr", 0.1, 1),
                               ("Train/loss", 0.9, 2)]

    def test_counter_batching_sorted_prefixed(self):
        master = fanout_master()
        fake = FakeBackend()
        master.add_backend(fake)
        master.write_counters("Mem", {"peak": 2.0, "in_use": 1.0}, 7)
        assert fake.events == [("Mem/in_use", 1.0, 7), ("Mem/peak", 2.0, 7)]

    def test_raising_backend_isolated(self):
        master = fanout_master()
        bad, good = FakeBackend(fail=True), FakeBackend()
        master.add_backend(bad)
        master.add_backend(good)
        master.write_events([("a", 1.0, 1)])
        master.write_events([("a", 2.0, 2)])
        assert len(good.events) == 2  # bad backend cost good nothing
        # warned once (the _warned once-guard), not once per batch
        assert master._warned == {id(bad)}

    def test_flush_and_close_idempotent(self):
        master = fanout_master()
        fake = FakeBackend()
        master.add_backend(fake)
        master.flush()
        assert fake.flushes == 1
        master.close()
        master.close()
        assert fake.closes >= 1
        assert not master.enabled


# ---------------------------------------------------------------------------
# engine wiring: recording, zero added syncs, divergence blackbox
# ---------------------------------------------------------------------------
def base_config(**overrides):
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "steps_per_print": 10 ** 9,
    }
    cfg.update(overrides)
    return cfg


def make_engine(config):
    engine, _, loader, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=8), config=config,
        training_data=random_dataset(64))
    return engine, iter(RepeatingLoader(loader))


class TestEngineTelemetry:
    def test_recorder_on_by_default_no_handlers_without_dir(self):
        engine, it = make_engine(base_config())
        assert engine.flight_recorder is not None
        assert engine._telemetry_uninstall is None  # no dump_dir -> no hooks
        for _ in range(3):
            engine.train_batch(it)
        steps = engine.flight_recorder.steps()
        assert [s["step"] for s in steps] == [1, 2, 3]
        # zero-added-syncs bar: nothing (monitor/sentinel) paid for a host
        # loss, so the recorder must not have materialized one
        assert all("loss" not in s for s in steps)
        assert all("grad_norm" not in s for s in steps)
        # phases are host dispatch times, recorded every step (no window)
        assert "compiled_step" in steps[-1]["phases_s"]
        assert steps[-1]["total_s"] > 0
        assert engine.flight_recorder.set_static  # static context attached
        static = engine.flight_recorder.payload("x")["static"]
        assert static["train_batch_size"] == engine.train_batch_size

    def test_disabled_telemetry_leaves_engine_bare(self):
        engine, it = make_engine(base_config(telemetry={"enabled": False}))
        assert engine.flight_recorder is None
        engine.train_batch(it)

    def test_loss_recorded_when_monitor_pays(self, tmp_path):
        engine, it = make_engine(base_config(
            csv_monitor={"enabled": True, "output_path": str(tmp_path),
                         "job_name": "t"}))
        for _ in range(2):
            engine.train_batch(it)
        steps = engine.flight_recorder.steps()
        assert all(np.isfinite(s["loss"]) for s in steps)

    def test_live_memory_sampling_self_disables_on_cpu(self):
        engine, it = make_engine(base_config())
        assert engine._live_mem_sampling  # config default on
        assert engine._live_memory_sample() is None  # CPU: no memory_stats
        assert not engine._live_mem_sampling  # one probe, then off

    def test_compiled_step_memory_breakdown(self):
        engine, it = make_engine(base_config())
        engine.train_batch(it)
        mem = engine.compiled_step_memory()
        assert mem["peak_working_set_bytes"] > 0
        assert any(k.endswith("argument_bytes") for k in mem)

    def test_divergence_writes_blackbox(self, tmp_path):
        tdir = tmp_path / "telemetry"
        engine, it = make_engine(base_config(
            sentinel={"enabled": True, "skip_budget": 1,
                      "rollback_budget": 0},
            telemetry={"dump_dir": str(tdir)}))
        try:
            for _ in range(4):
                engine.train_batch(it)
            with fi.nan_at_step(engine, step=4, times=None):
                with pytest.raises(DivergenceError):
                    for _ in range(10):
                        engine.train_batch(it)
            path = tdir / "blackbox-rank0.json"
            payload, status = load_blackbox(str(path))
            assert status == "ok"
            assert payload["reason"] == "divergence"
            assert payload["exit_code"] == 13
            assert payload["exception"]["type"] == "DivergenceError"
            # sentinel paid for the host loss -> records carry it; the
            # poisoned step's non-finite loss is in the evidence
            losses = [s.get("loss") for s in payload["steps"]]
            assert losses and not np.isfinite(losses[-1])
            kinds = [e["kind"] for e in payload["events"]]
            assert "sentinel.skip" in kinds
            assert "sentinel.diverged" in kinds
            assert payload["event_counts"]["sentinel.diverged"] == 1
        finally:
            if engine._telemetry_uninstall is not None:
                engine._telemetry_uninstall()

    def test_graceful_preemption_retracts_blackbox(self, tmp_path):
        """SIGTERM dumps immediately (nobody knows yet whether the grace
        save will land), then chains to the graceful-shutdown flag; when
        the save commits and the process exits cleanly, the stale
        blackbox is withdrawn so a later sweep sees no false crash."""
        tdir = tmp_path / "telemetry"
        ckpt = tmp_path / "ckpt"
        old_term = signal.getsignal(signal.SIGTERM)
        engine = None
        try:
            engine, it = make_engine(base_config(
                telemetry={"dump_dir": str(tdir)},
                graceful_shutdown={"enabled": True,
                                   "save_dir": str(ckpt)}))
            engine.train_batch(it)
            assert engine._telemetry_uninstall is not None
            os.kill(os.getpid(), signal.SIGTERM)
            # the chained handler dumped BEFORE the flag-setter ran
            assert (tdir / "blackbox-rank0.json").exists()
            with pytest.raises(SystemExit) as ei:
                engine.train_batch(it)
            assert ei.value.code == 0
            assert (ckpt / f"global_step{engine.global_steps}").exists()
            # clean exit: the preemption blackbox was retracted
            assert not (tdir / "blackbox-rank0.json").exists()
        finally:
            if engine is not None and engine._telemetry_uninstall:
                engine._telemetry_uninstall()
            signal.signal(signal.SIGTERM, old_term)
