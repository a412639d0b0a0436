# Self-running gates (reference wires the same split into
# .github/workflows/: formatting + unit suites + op pre-compile; here the
# TPU-facing perf gate is the extra axis).
#
#   make quick   fast confidence: imports + the fast unit subset
#                (<5 min, virtual CPU mesh, `-m "not slow"`) — what the
#                pre-push hook runs
#   make test    full unit suite on the 8-device virtual CPU mesh
#   make smoke   bring-up check ON THE CHIP: `python chip_smoke.py`
#                (kernels, 8 steps of GPT-2 1.3B, a 4-request server).
#                Refuses anything but a TPU — run it where the chip is,
#                e.g. through the chip tool; never part of a CPU gate
#   make chaos   fault-injection suite: torn/failed checkpoint writes,
#                preemption grace saves, crash-loop detection, elastic
#                topology resume (8->4 / 4->8 kill-and-reshard), the
#                training health sentinel: NaN/spike anomalies, auto-
#                rollback, hang watchdog (docs/recovery.md), the
#                serving-fleet failover units, and the cluster health
#                plane units (silence schedule, coordinated abort, SDC
#                digest cross-check) — runs chaos-cluster first
#   make chaos-serve  kill-a-replica-mid-decode scenario: one of N
#                serving replicas is SIGKILLed while decoding; asserts
#                zero lost requests, token-identical failover replays,
#                and one serve.failover per migrated request (commits
#                benchmarks/inference/failover_bench_results.json)
#   make chaos-cluster  cluster-health scenarios on a REAL 2-process
#                world: SIGSTOP one rank of a pp=2 run (survivor exits
#                15 within the silence budget, ONE world relaunch,
#                resume on-trajectory) and a silent bit flip in a
#                replicated weight (digest probe catches it within K
#                steps, crc-valid blackbox, rollback on-trajectory) —
#                docs/recovery.md "Cluster health & SDC defense"
#                (commits benchmarks/chaos_cluster_results.json)
#   make profile step-profiler gate on a tiny CPU config: asserts phase
#                breakdown sums to step wall time, analytic MFU from the
#                compiled step, and a perfetto-loadable trace
#                (docs/observability.md)
#   make blackbox crash-forensics gate: injected NaN divergence must
#                leave a crc-valid flight-recorder blackbox (>=32 step
#                records with phases/loss/comm + compiled memory) and a
#                sweepable run-level crash report (docs/observability.md)
#   make memreport  analytic HBM report for the 1.3B seq-1024 train step
#                from avals-only AOT compile (docs/performance.md
#                "The 1.3B memory ceiling")
#   make serve-bench  serving front door under the bursty prefix-skewed
#                trace: CB+prefix-cache vs cold CB vs sequential (TTFT /
#                tok/s / hit rate, CPU backend, commits benchmarks/
#                inference/serving_bench_prefix_results.json)
#   make serve-bench-uniform  the original uniform-trace CB-vs-sequential
#                comparison (serving_bench_results.json)
#   make serve-bench-disagg  disaggregated topology on the bursty trace:
#                prefill/decode split vs front door, int8-KV + spec-
#                decode tier, lanes-per-replica capacity table (commits
#                benchmarks/inference/serving_bench_disagg_results.json)
#   make data-bench  packed input pipeline: dataloader+h2d phase share
#                with background prefetch off vs on (commits
#                benchmarks/data/input_pipeline_bench_results.json)
#   make dryrun  the multi-axis mesh gate (__graft_entry__.dryrun_
#                multichip(8)) with per-phase wall clock; commits
#                benchmarks/dryrun_phase_times.json and fails if the
#                total breaches the 5-minute budget
#   make overlap-measured  wall-clock bucketed-vs-monolithic exchange
#                deltas (benchmarks/communication/
#                overlap_measured_results.json); nonzero exit when
#                bucketed-on regresses beyond the measured noise band
#   make hierarchical-exchange  ICI/DCN two-level exchange gate: per-
#                level wire bytes (int8 DCN leg <= 0.3x flat bf16) and
#                wall clock within the monolithic int8 baseline's
#                3-sigma band (benchmarks/communication/
#                hierarchical_exchange_results.json); nonzero exit past
#                either bound
#   make check   test, plus a reminder to run the chip smoke when hot
#                paths changed (no chip on a developer machine)
#   make hooks   install the committed .githooks (pre-push runs
#                `make quick`)

PY ?= python
# hot paths whose changes call for a run of chip_smoke.py on the chip
HOT_PATHS := deepspeed_tpu/runtime/engine.py deepspeed_tpu/models \
             deepspeed_tpu/ops deepspeed_tpu/utils/timer.py \
             deepspeed_tpu/inference/engine.py

.PHONY: quick test smoke chaos chaos-serve chaos-cluster profile \
        blackbox memreport \
        check hooks hot-changed serve-bench serve-bench-uniform \
        serve-bench-disagg data-bench \
        dryrun overlap-measured \
        hierarchical-exchange

# the <5-min smoke tier: config/mesh/kernels plus the comm + flash table +
# process-group units, with tests marked `slow` (pyproject marker) opted
# out — mark compile-heavy tests slow rather than dropping whole files
quick:
	$(PY) -c "import deepspeed_tpu; import __graft_entry__; print('imports ok')"
	$(PY) -m pytest tests/unit/test_config.py tests/unit/test_mesh.py \
	  tests/unit/test_ops.py tests/unit/test_comm.py \
	  tests/unit/test_compressed_comm.py tests/unit/test_bucketed_comm.py \
	  tests/unit/test_grad_exchange_modes.py \
	  tests/unit/test_pipe_transport.py \
	  tests/unit/test_flash_table.py tests/unit/test_procgroup.py \
	  tests/unit/test_launcher.py tests/unit/test_serving.py \
	  tests/unit/test_serving_frontdoor.py \
	  tests/unit/test_serving_fleet.py \
	  tests/unit/test_serving_disagg.py \
	  tests/unit/test_data_pipeline.py tests/unit/test_telemetry.py \
	  tests/unit/test_elastic_reshard.py \
	  tests/unit/test_health_state.py tests/unit/test_cluster_health.py \
	  -q -x -m "not slow"

test:
	$(PY) -m pytest tests/ -q

smoke:
	$(PY) chip_smoke.py

# includes the elastic 8->4 / 4->8 topology-resume scenarios (train on N
# virtual devices, kill mid-epoch, resume on N' — docs/recovery.md
# "Elastic topology resume"); the slow marker is NOT excluded here
chaos: chaos-cluster
	$(PY) -m pytest tests/unit/test_fault_tolerance.py tests/unit/test_sentinel.py \
	  tests/unit/test_elastic_reshard.py tests/unit/test_serving_fleet.py \
	  tests/unit/test_health_state.py tests/unit/test_cluster_health.py -q

# wedge-one-rank / flip-one-bit scenarios on a real two-process world
# under the world agent (docs/recovery.md "Cluster health & SDC
# defense"); exits nonzero if any survivor hangs instead of aborting 15,
# the world relaunches more than once, the digest probe misses the
# corruption, or the resumed losses leave the reference trajectory
chaos-cluster:
	JAX_PLATFORMS=cpu $(PY) benchmarks/chaos_cluster.py

# serving-fleet kill scenario: three runs over one trace (in-process
# reference, fleet baseline, fleet with a mid-decode SIGKILL) proving
# the exact-failover contract end to end (docs/recovery.md "Serving
# failover"); exits nonzero on any lost request or token divergence
chaos-serve:
	JAX_PLATFORMS=cpu $(PY) benchmarks/inference/chaos_serve.py

profile:
	$(PY) benchmarks/profile_step.py

blackbox:
	JAX_PLATFORMS=cpu $(PY) benchmarks/blackbox_check.py

memreport:
	JAX_PLATFORMS=cpu $(PY) benchmarks/memory_report.py \
	  --out benchmarks/memory_report_1p3b.json

# multi-axis mesh gate with committed per-phase wall clock; the child
# writes the artifact, and dryrun_multichip itself fails the run when
# total exceeds DS_TPU_DRYRUN_TOTAL_BUDGET_S (default 300s)
dryrun:
	DS_TPU_DRYRUN_TIMES_OUT=benchmarks/dryrun_phase_times.json \
	  $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

overlap-measured:
	JAX_PLATFORMS=cpu $(PY) benchmarks/communication/overlap_measured.py

hierarchical-exchange:
	JAX_PLATFORMS=cpu $(PY) benchmarks/communication/hierarchical_exchange.py

# the serving front-door headline: bursty prefix-skewed trace through
# CB+prefix-cache vs cold CB vs sequential generate (docs/performance.md
# "Serving"). Runs on the virtual CPU backend; writes benchmarks/
# inference/serving_bench_prefix_results.json and exits nonzero unless
# prefix p95 TTFT strictly beats cold CB with a positive hit rate.
serve-bench:
	JAX_PLATFORMS=cpu $(PY) benchmarks/inference/serving_prefix_bench.py

# the original uniform-trace comparison (CB vs sequential, no prefix
# reuse); writes benchmarks/inference/serving_bench_results.json.
serve-bench-uniform:
	JAX_PLATFORMS=cpu $(PY) benchmarks/inference/serving_bench.py

# disaggregated serving on the same bursty trace: prefill/decode split
# (DisaggServer + KV hand-off) vs the front door, plus int8-KV + spec-
# decode decode tier and the lanes-per-replica capacity table
# (docs/performance.md "Disaggregated serving"). Writes benchmarks/
# inference/serving_bench_disagg_results.json; exits nonzero unless
# disagg tokens are identical to the front door's, int8 capacity beats
# bf16 >= 1.7x / fp32 >= 3.0x, and spec acceptance >= 0.5.
serve-bench-disagg:
	JAX_PLATFORMS=cpu $(PY) benchmarks/inference/serving_disagg_bench.py

# packed input pipeline: dataloader+h2d share of step time with
# data_pipeline.prefetch off vs on (docs/data.md). Writes
# benchmarks/data/input_pipeline_bench_results.json; exits nonzero when
# prefetch fails to reduce the input share.
data-bench:
	JAX_PLATFORMS=cpu $(PY) benchmarks/data/input_pipeline_bench.py

# exits 0 when any hot-path file differs from BASE (override: `make
# hot-changed BASE=<sha>` — the pre-push hook passes the remote sha so a
# multi-commit push is diffed as a RANGE, not just the last commit).
# Fallback order: origin/main, then HEAD~1; if neither resolves, report
# changed — running the gate needlessly is the safe failure mode.
BASE ?=
hot-changed:
	@base="$(BASE)"; \
	if [ -z "$$base" ]; then \
	  base=$$(git rev-parse --verify -q origin/main \
	          || git rev-parse --verify -q 'HEAD~1') || true; \
	fi; \
	if [ -z "$$base" ]; then \
	  echo "no base to diff against; treating hot paths as changed"; \
	  exit 0; \
	fi; \
	if git diff --name-only "$$base" -- $(HOT_PATHS) | grep -q .; then \
	  echo "hot paths changed since $$base"; exit 0; \
	else \
	  echo "no hot-path changes"; exit 1; \
	fi

check: test
	@if $(MAKE) -s hot-changed; then \
	  echo "hot paths changed: run 'python chip_smoke.py' on the chip"; fi

hooks:
	git config core.hooksPath .githooks
	@echo "hooks installed: pre-push runs 'make quick'"
