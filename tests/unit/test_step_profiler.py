"""Step profiler unit tests (docs/observability.md).

Covers the tentpole surface host-side and cheap: the hardware-peak
table, XLA cost-analysis extraction on a tiny jitted step, phase
attribution summing to the step envelope, window gating (the
zero-added-syncs invariant), Chrome trace-event schema round-trip,
wire-dtype bytes accounting (compressed vs plain allreduce, traced via
eval_shape — no kernels), and the bench preflight/retry helpers."""

import json
import os
import pickle
import time

import numpy as np
import pytest

from deepspeed_tpu.comm.logging import CommsLogger, wire_factor
from deepspeed_tpu.profiling.step_profiler import (
    _NULL_CTX,
    StepProfiler,
    peak_tflops,
)
from deepspeed_tpu.runtime.config import StepProfilerConfig


def prof_config(**overrides):
    base = {"enabled": True, "start_step": 0, "num_steps": 2}
    base.update(overrides)
    return StepProfilerConfig.from_dict(base)


# ---------------------------------------------------------------------------
# hardware-peak table
# ---------------------------------------------------------------------------
class TestPeakTable:
    def test_override_wins(self):
        peak, src = peak_tflops(device="TPU v4", override=123.0)
        assert peak == 123.0 and src == "config override"

    @pytest.mark.parametrize("kind,expected", [
        ("TPU v5e", 197.0),
        ("TPU v5p chip", 459.0),
        ("TPU v5 lite", 197.0),   # must match before the bare "v5" row
        ("TPU v4", 275.0),
        ("TPU v3", 61.5),
        ("cpu", 0.5),
    ])
    def test_known_kinds(self, kind, expected):
        peak, src = peak_tflops(device=kind)
        assert peak == expected
        assert "device_kind" in src

    @pytest.mark.parametrize("kind", ["TPU v9", "quantum abacus"])
    def test_unknown_kind_raises(self, kind):
        with pytest.raises(ValueError, match="no peak-TFLOPS entry"):
            peak_tflops(device=kind)


# ---------------------------------------------------------------------------
# cost analysis on a tiny jitted step
# ---------------------------------------------------------------------------
class TestCostAnalysis:
    def test_matmul_flops(self):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.profiling.flops_profiler.profiler import (
            cost_analysis,
        )

        n = 64
        a = jax.ShapeDtypeStruct((n, n), jnp.float32)
        cost = cost_analysis(jax.jit(lambda x, y: x @ y), a, a)
        # one n^3 matmul = 2n^3 flops; allow backend fusion slack
        assert cost["flops"] >= 2 * n ** 3
        assert cost["bytes_accessed"] >= 3 * n * n * 4

    def test_profiler_folds_mult(self):
        prof = StepProfiler(prof_config())
        prof.set_cost("fwd_bwd", {"flops": 100.0, "bytes_accessed": 10.0},
                      mult=4)
        prof.set_cost("apply", {"flops": 7.0, "bytes_accessed": 1.0})
        assert prof.flops_per_step == 407.0
        assert prof.bytes_per_step == 41.0


# ---------------------------------------------------------------------------
# phase attribution
# ---------------------------------------------------------------------------
class TestPhaseAttribution:
    def run_steps(self, prof, n_steps, start=0):
        for s in range(start, start + n_steps):
            prof.begin_step(s)
            with prof.phase("work"):
                time.sleep(0.02)
            with prof.phase("io"):
                time.sleep(0.01)
            time.sleep(0.005)  # un-named -> "other"
            prof.end_step(s)

    def test_phases_plus_other_sum_to_envelope(self, tmp_path):
        prof = StepProfiler(prof_config(
            trace_path=str(tmp_path / "t.json")))
        self.run_steps(prof, 2)
        assert len(prof.records) == 2
        for rec in prof.records:
            parts = sum(rec["phases_s"].values()) + rec["other_s"]
            assert parts == pytest.approx(rec["total_s"], rel=1e-6)
            assert rec["phases_s"]["work"] >= 0.02
            assert rec["other_s"] >= 0.004
        s = prof.summary()
        assert s["steps_profiled"] == 2
        assert 0.0 < s["phase_coverage"] < 1.0
        assert set(s["phases_ms"]) == {"work", "io", "other"}

    def test_window_gating_zero_instrumentation(self):
        prof = StepProfiler(prof_config(start_step=5, num_steps=1))
        # outside the window: no step opens, phase() is the SHARED no-op
        prof.begin_step(0)
        assert prof._in_step is False
        assert prof.phase("work") is _NULL_CTX
        assert prof.active_for(4) is False
        assert prof.active_for(5) is True
        # after finalize the window never reopens
        prof.begin_step(5)
        prof.end_step(5)
        assert prof._finalized
        assert prof.phase("work") is _NULL_CTX
        assert prof.active_for(5) is False

    def test_begin_step_idempotent_within_step(self):
        prof = StepProfiler(prof_config())
        prof.begin_step(0)
        t0 = prof._step_t0
        prof.begin_step(0)  # engine calls from both train_batch and forward
        assert prof._step_t0 == t0
        prof.end_step(0)
        assert len(prof.records) == 1

    def test_cost_cb_runs_once_after_envelope(self):
        prof = StepProfiler(prof_config())
        calls = []

        def cb():
            calls.append(1)
            return {"flops": 5.0, "bytes_accessed": 2.0}

        prof.begin_step(0)
        prof.end_step(0, cost_cb=cb)
        prof.begin_step(1)
        prof.end_step(1, cost_cb=cb)
        assert len(calls) == 1
        assert prof.has_cost("optimizer_step")
        assert prof.flops_per_step == 5.0

    def test_analytic_mfu_with_override(self):
        prof = StepProfiler(prof_config(peak_tflops=100.0))
        self.run_steps(prof, 2)
        prof.set_cost("optimizer_step", {"flops": 1e12, "bytes_accessed": 1e9})
        s = prof.summary()
        assert s["peak_tflops"] == 100.0
        assert s["peak_source"] == "config override"
        assert s["analytic_tflops"] > 0
        assert s["analytic_mfu"] == pytest.approx(
            s["analytic_tflops"] / 100.0)


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------
class TestTraceExport:
    def test_schema_round_trip(self, tmp_path):
        path = str(tmp_path / "trace.json")
        prof = StepProfiler(prof_config(trace_path=path))
        TestPhaseAttribution().run_steps(prof, 2)
        assert prof._finalized
        assert os.path.exists(path)
        with open(path) as f:
            trace = json.load(f)
        events = trace["traceEvents"]
        meta = [e for e in events if e["ph"] == "M"]
        assert any(e["name"] == "process_name" for e in meta)
        assert any(e["name"] == "thread_name" for e in meta)
        complete = [e for e in events if e["ph"] == "X"]
        for e in complete:
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        steps = [e for e in complete if e["name"].startswith("step ")]
        phases = [e for e in complete if not e["name"].startswith("step ")]
        assert len(steps) == 2
        assert {e["name"] for e in phases} == {"work", "io"}
        # phase spans nest inside their step envelope on the other track
        for ph in phases:
            assert any(st["ts"] <= ph["ts"] and
                       ph["ts"] + ph["dur"] <= st["ts"] + st["dur"] + 1e3
                       for st in steps)
        # round-trip: the in-memory event list IS what landed on disk
        assert events == prof.trace_events()["traceEvents"]
        assert trace["displayTimeUnit"] == "ms"

    def test_perf_counters_flat(self):
        prof = StepProfiler(prof_config(peak_tflops=1.0))
        TestPhaseAttribution().run_steps(prof, 2)
        prof.set_cost("optimizer_step", {"flops": 1e9, "bytes_accessed": 1e6})
        c = prof.perf_counters()
        for key in ("steps_profiled", "step_ms_mean", "phase_coverage",
                    "phase_work_ms", "phase_io_ms", "phase_other_ms",
                    "analytic_mfu", "flops_per_step"):
            assert key in c, key
            assert isinstance(c[key], float)

    def test_counters_reach_monitor(self, tmp_path):
        class FakeMonitor:
            enabled = True

            def __init__(self):
                self.events = []

            def write_events(self, evs):
                self.events.extend(evs)

        mon = FakeMonitor()
        prof = StepProfiler(prof_config(), monitor=mon)
        prof.begin_step(0)
        prof.end_step(0)
        prof.finalize(comm_counters={"all_reduce_wire_bytes": 17.0})
        tags = {t for t, _, _ in mon.events}
        assert any(t.startswith("Perf/") for t in tags)
        assert "Comm/all_reduce_wire_bytes" in tags


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------
class TestConfig:
    def test_defaults_off(self):
        cfg = StepProfilerConfig.from_dict({})
        assert cfg.enabled is False
        assert cfg.num_steps >= 1

    def test_validation(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError

        with pytest.raises(DeepSpeedConfigError):
            StepProfilerConfig.from_dict({"start_step": -1})
        with pytest.raises(DeepSpeedConfigError):
            StepProfilerConfig.from_dict({"num_steps": 0})
        with pytest.raises(DeepSpeedConfigError):
            StepProfilerConfig.from_dict({"jax_trace": True})

    def test_engine_config_parses_block(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        cfg = DeepSpeedConfig({
            "train_micro_batch_size_per_gpu": 1,
            "step_profiler": {"enabled": True, "start_step": 3,
                              "num_steps": 5, "peak_tflops": 9.0},
        })
        assert cfg.step_profiler.enabled is True
        assert cfg.step_profiler.start_step == 3
        assert cfg.step_profiler.num_steps == 5
        assert cfg.step_profiler.peak_tflops == 9.0


# ---------------------------------------------------------------------------
# bytes-on-wire accounting
# ---------------------------------------------------------------------------
class TestWireBytes:
    def test_wire_factors(self):
        assert wire_factor("all_reduce", 8) == pytest.approx(1.75)
        assert wire_factor("broadcast", 8) == pytest.approx(1.75)
        assert wire_factor("reduce_scatter", 8) == pytest.approx(0.875)
        assert wire_factor("all_to_all", 8) == pytest.approx(0.875)
        assert wire_factor("all_gather", 8) == 7.0
        assert wire_factor("ppermute", 8) == 1.0
        assert wire_factor("all_reduce", None) == 1.0  # unknown axis size
        assert wire_factor("all_reduce", 1) == 0.0     # nothing crosses

    def test_wire_dtype_reexpresses_payload(self):
        log = CommsLogger(enabled=True)
        x = np.zeros((1024,), np.float32)
        log.append("all_reduce", x, "dp", world=8)
        log.append("all_reduce", x, "dp", wire_dtype=np.int8, world=8,
                   log_name="quantized")
        c = log.counters()
        assert c["all_reduce_bytes"] == 4096
        assert c["all_reduce_wire_bytes"] == pytest.approx(4096 * 1.75)
        assert c["quantized_bytes"] == 4096  # logical payload unchanged
        assert c["quantized_wire_bytes"] == pytest.approx(1024 * 1.75)
        assert c["total_wire_bytes"] == (c["all_reduce_wire_bytes"]
                                         + c["quantized_wire_bytes"])

    def test_compressed_vs_plain_allreduce(self, eight_devices):
        """The acceptance-criterion ratio, measured the same way the
        grad-exchange benchmark does: trace both exchange flavours under
        eval_shape and compare ring-accounted wire bytes."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from deepspeed_tpu.comm import comm as dist
        from deepspeed_tpu.comm.compressed import quantized_all_reduce
        from deepspeed_tpu.comm.logging import comms_logger

        mesh = Mesh(np.array(eight_devices), ("dp",))
        g = jax.ShapeDtypeStruct((8192,), jnp.float32)

        def traced_bytes(fn):
            mapped = shard_map(fn, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False)
            comms_logger.reset()
            comms_logger.enabled = True
            comms_logger.prof_all = True
            try:
                jax.eval_shape(mapped, g)
                return comms_logger.total_wire_bytes(), \
                    comms_logger.counters()
            finally:
                comms_logger.enabled = False
                comms_logger.reset()

        bf16_bytes, _ = traced_bytes(
            lambda x: dist.all_reduce(x.astype(jnp.bfloat16), "dp"))
        int8_bytes, c = traced_bytes(
            lambda x: quantized_all_reduce(x, "dp"))
        assert bf16_bytes > 0 and int8_bytes > 0
        # per-exchange: int8 payload+sideband is ~half of bf16 (never
        # below 0.5 exactly — the fp32 scale sideband is the floor)
        assert 0.5 < int8_bytes / bf16_bytes < 0.55
        assert c["quantized_all_reduce.scales_wire_bytes"] > 0
        assert c["quantized_all_reduce_wire_bytes"] > \
            c["quantized_all_reduce.scales_wire_bytes"]
        # per-optimizer-step at gas=2: the plain path exchanges every
        # micro step, the compressed path once at the boundary
        gas = 2
        assert int8_bytes / (bf16_bytes * gas) < 0.5


# ---------------------------------------------------------------------------
# bench preflight / retry helpers
# ---------------------------------------------------------------------------
class TestBenchHelpers:
    def test_preflight_accepts_only_tpu(self):
        from benchmarks._util import backend_preflight

        events = []
        r = backend_preflight(emit=events.append,
                              _runner=lambda: (True, "tpu 1"))
        assert r == {"ok": True, "backend": "tpu 1"} and not events

    def test_preflight_rejects_cpu_probe(self):
        """BENCH_r06: the old preflight accepted "cpu 1" and the bench
        timed the CPU until its timeout."""
        from benchmarks._util import backend_preflight

        events = []
        r = backend_preflight(emit=events.append,
                              _runner=lambda: (True, "cpu 1"))
        assert r["ok"] is False and "cpu 1" in r["error"]
        assert [e["event"] for e in events] == ["backend_preflight_failure"]
        # the real in-process probe on this (CPU) backend is refused too
        assert backend_preflight(emit=lambda e: None)["ok"] is False

    def test_preflight_failed_backend_is_one_failed_attempt(self):
        from benchmarks._util import backend_preflight

        calls, events = [], []

        def probe():
            calls.append(1)
            raise RuntimeError("backend failed to initialise")

        r = backend_preflight(emit=events.append, _runner=probe)
        assert r["ok"] is False and "failed to initialise" in r["error"]
        assert len(calls) == 1 and len(events) == 1  # no retry

    def test_run_with_retry(self):
        from benchmarks._util import run_with_retry

        n, events = [], []

        def flaky():
            n.append(1)
            if len(n) == 1:
                raise RuntimeError("boom")
            return 42

        out, err = run_with_retry(flaky, "w", retries=1, backoff_s=0.0,
                                  emit=events.append)
        assert (out, err) == (42, None)
        out, err = run_with_retry(lambda: 1 / 0, "w2", retries=1,
                                  backoff_s=0.0, emit=events.append)
        assert out is None and "ZeroDivisionError" in err
        assert [e["workload"] for e in events] == ["w", "w2", "w2"]


# ---------------------------------------------------------------------------
# legacy checkpoint fallback rides along this PR (see test plan in ISSUE)
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestLegacyEngineStates:
    def test_load_checkpoint_reads_bare_pickle_meta(self, tmp_path):
        import deepspeed_tpu
        from deepspeed_tpu.runtime.dataloader import RepeatingLoader
        from tests.unit.simple_model import SimpleModel, random_dataset

        config = {
            "train_micro_batch_size_per_gpu": 4,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "steps_per_print": 10 ** 9,
        }

        def make_engine():
            eng, _, loader, _ = deepspeed_tpu.initialize(
                model=SimpleModel(hidden_dim=16), config=config,
                training_data=random_dataset(32))
            return eng, iter(RepeatingLoader(loader))

        engine, it = make_engine()
        for _ in range(3):
            engine.train_batch(it)
        ckpt = str(tmp_path / "ckpt")
        assert engine.save_checkpoint(ckpt, tag="legacy")

        tag_dir = os.path.join(ckpt, "legacy")
        msgpack_path = os.path.join(tag_dir, "engine_states.msgpack")
        meta = pickle.loads(np.asarray(
            engine.checkpoint_engine.load(msgpack_path)["meta"]).tobytes())
        # rewrite the meta the way pre-msgpack checkpoints stored it:
        # a bare pickle, no manifest
        with open(os.path.join(tag_dir, "engine_states.pkl"), "wb") as f:
            pickle.dump(meta, f)
        os.remove(msgpack_path)
        manifest = os.path.join(tag_dir, "manifest.json")
        if os.path.exists(manifest):
            os.remove(manifest)

        fresh, it2 = make_engine()
        fresh.train_batch(it2)  # materialize state templates
        fresh.load_checkpoint(ckpt, tag="legacy",
                              load_optimizer_states=True)
        assert fresh.global_steps == engine.global_steps
        assert fresh.global_samples == engine.global_samples
        assert fresh.micro_steps == engine.micro_steps
