"""DeepSpeed-style JSON config system for the TPU framework.

Capability parity with reference ``deepspeed/runtime/config.py`` (DeepSpeedConfig
:712, batch-triad resolution, per-feature config blocks). Differences are
TPU-motivated and documented per block:

* GPU-only knobs (cuda streams, NCCL tuning) parse but are inert.
* A new ``"tpu"`` block configures the device mesh (dp/fsdp/tp/pp/ep/sp axis
  sizes), remat policy, and buffer donation — concepts with no reference
  analogue because XLA owns scheduling.
"""

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.config_utils import (
    ConfigModel,
    dict_raise_error_on_duplicate_keys,
    get_scalar_param,
    pretty_json,
)
from deepspeed_tpu.utils.logging import logger


class DeepSpeedConfigError(Exception):
    pass


# ---------------------------------------------------------------------------
# Precision blocks (reference runtime/config.py fp16/bf16/amp parsing)
# ---------------------------------------------------------------------------
@dataclass
class Fp16Config(ConfigModel):
    enabled: bool = C.FP16_ENABLED_DEFAULT
    loss_scale: float = C.FP16_LOSS_SCALE_DEFAULT
    initial_scale_power: int = C.FP16_INITIAL_SCALE_POWER_DEFAULT
    loss_scale_window: int = C.FP16_LOSS_SCALE_WINDOW_DEFAULT
    hysteresis: int = C.FP16_HYSTERESIS_DEFAULT
    min_loss_scale: float = C.FP16_MIN_LOSS_SCALE_DEFAULT
    fp16_master_weights_and_grads: bool = C.FP16_MASTER_WEIGHTS_AND_GRADS_DEFAULT
    auto_cast: bool = False  # inert on TPU: XLA handles dtype propagation

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.loss_scale == 0


@dataclass
class Bf16Config(ConfigModel):
    enabled: bool = C.BFLOAT16_ENABLED_DEFAULT


@dataclass
class AmpConfig(ConfigModel):
    enabled: bool = C.AMP_ENABLED_DEFAULT
    opt_level: str = "O1"  # accepted for config compatibility; bf16 is the TPU path


# ---------------------------------------------------------------------------
# ZeRO block (reference deepspeed/runtime/zero/config.py:145)
# ---------------------------------------------------------------------------
@dataclass
class ZeroOffloadParamConfig(ConfigModel):
    device: str = "none"  # none | cpu | nvme
    nvme_path: str = "/local_nvme"
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    max_in_cpu: int = 1_000_000_000
    pin_memory: bool = False


@dataclass
class ZeroOffloadOptimizerConfig(ConfigModel):
    device: str = "none"  # none | cpu | nvme
    nvme_path: str = "/local_nvme"
    buffer_count: int = 4
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False


@dataclass
class ZeroConfig(ConfigModel):
    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = False  # inert: XLA overlaps collectives automatically
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False
    offload_param: Optional[Dict[str, Any]] = None
    offload_optimizer: Optional[Dict[str, Any]] = None
    sub_group_size: int = 1_000_000_000
    cpu_offload: bool = False  # deprecated alias handled in __post_init__validate__
    cpu_offload_param: bool = False  # deprecated alias (reference zero/config.py)
    # elements that may be gathered ahead of use. Read by
    # runtime/zero/gather.py ``turn_length`` (through ZeroShardingRules):
    # where a layer's first matrix and the stack's vectors fit, a layer
    # loop's turn gathers the next layer's first weight; 0 gathers nothing
    # ahead and gives the one-layer turn of before PR 60.
    prefetch_bucket_size: int = 50_000_000
    param_persistence_threshold: int = 100_000
    model_persistence_threshold: int = 2 ** 62
    # the cap on gathered elements whole at once. Read with the key above:
    # below two layers' gathered elements nothing is gathered ahead.
    max_live_parameters: int = 1_000_000_000
    max_reuse_distance: int = 1_000_000_000
    gather_16bit_weights_on_model_save: bool = False
    stage3_gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    round_robin_gradients: bool = False
    zero_hpz_partition_size: int = 1
    # Aliases used by stage3-prefixed keys in real-world configs
    _aliases = {
        "stage3_prefetch_bucket_size": "prefetch_bucket_size",
        "stage3_param_persistence_threshold": "param_persistence_threshold",
        "stage3_model_persistence_threshold": "model_persistence_threshold",
        "stage3_max_live_parameters": "max_live_parameters",
        "stage3_max_reuse_distance": "max_reuse_distance",
    }

    def __post_init__validate__(self):
        if self.stage not in (0, 1, 2, 3):
            raise DeepSpeedConfigError(f"ZeRO stage must be 0..3, got {self.stage}")
        if self.cpu_offload and self.offload_optimizer is None:
            self.offload_optimizer = {"device": "cpu"}
        if self.cpu_offload_param and self.offload_param is None:
            self.offload_param = {"device": "cpu"}
        if self.stage3_gather_16bit_weights_on_model_save:
            self.gather_16bit_weights_on_model_save = True

    @property
    def offload_param_config(self) -> ZeroOffloadParamConfig:
        return ZeroOffloadParamConfig.from_dict(self.offload_param or {})

    @property
    def offload_optimizer_config(self) -> ZeroOffloadOptimizerConfig:
        return ZeroOffloadOptimizerConfig.from_dict(self.offload_optimizer or {})


# ---------------------------------------------------------------------------
# Optimizer / scheduler blocks
# ---------------------------------------------------------------------------
@dataclass
class OptimizerConfig(ConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)
    legacy_fusion: bool = False


@dataclass
class SchedulerConfig(ConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# Aux feature blocks
# ---------------------------------------------------------------------------
@dataclass
class ActivationCheckpointingConfig(ConfigModel):
    """Reference activation_checkpointing block. On TPU, ``partition_activations``
    maps to sharded remat residuals, ``cpu_checkpointing`` to host offload of
    remat residuals; ``contiguous_memory_optimization``/``synchronize`` are inert
    (XLA owns memory layout)."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


@dataclass
class FlopsProfilerConfig(ConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclass
class TensorboardConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


@dataclass
class WandbConfig(ConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed"


@dataclass
class CsvConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


@dataclass
class CommsLoggerConfig(ConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = field(default_factory=list)


@dataclass
class StepProfilerConfig(ConfigModel):
    """Step-level performance tracer (docs/observability.md). Profiles the
    half-open optimizer-step window ``[start_step, start_step+num_steps)``:
    fenced per-step phase attribution, compiled-step cost analysis →
    analytic MFU, Chrome trace-event export, optional ``jax.profiler``
    capture. Disabled (the default) it adds zero device syncs."""

    enabled: bool = False
    start_step: int = 2          # skip compile + warmup steps
    num_steps: int = 8           # window length in optimizer steps
    trace_path: Optional[str] = None   # Chrome trace JSON ("" / None: off)
    jax_trace: bool = False            # jax.profiler capture over the window
    jax_trace_dir: Optional[str] = None
    peak_tflops: Optional[float] = None  # override the hardware-peak table
    emit_counters: bool = True           # Perf/* + Comm/* via the monitor

    def __post_init__validate__(self):
        if self.start_step < 0:
            raise DeepSpeedConfigError("step_profiler.start_step must be >= 0")
        if self.num_steps < 1:
            raise DeepSpeedConfigError("step_profiler.num_steps must be >= 1")
        if self.jax_trace and not self.jax_trace_dir:
            raise DeepSpeedConfigError(
                "step_profiler.jax_trace requires step_profiler.jax_trace_dir")


@dataclass
class DataPipelineConfig(ConfigModel):
    """Input data pipeline (deepspeed_tpu/data/, docs/data.md): swaps the
    engine's synchronous ``DeepSpeedDataLoader`` path for deterministic
    sharded streaming + sequence packing + background device prefetch.
    Disabled (the default) the input path is byte-identical to the
    historical loop — ``deepspeed_io`` builds the same loader as ever."""

    enabled: bool = False
    # bin-pack variable-length documents into [B, seq_length] with
    # segment_ids/positions; False collates one sample per row instead
    pack_sequences: bool = True
    seq_length: int = 1024
    pad_token_id: int = 0
    shuffle: bool = True
    seed: int = 0
    # "process": shard the sample stream by jax process (DP rank);
    # "none": every process sees the full stream
    shard: str = "process"
    # background worker that runs the engine's sharded device_put so h2d
    # of batch N+1 overlaps compute of batch N
    prefetch: bool = True
    prefetch_depth: int = 2
    # pack to the curriculum scheduler's quantized difficulty seq-len
    # (bounded compiled-shape count; see docs/data.md)
    curriculum_pack: bool = True

    def __post_init__validate__(self):
        if self.seq_length < 2:
            raise DeepSpeedConfigError(
                "data_pipeline.seq_length must be >= 2")
        if self.prefetch_depth < 1:
            raise DeepSpeedConfigError(
                "data_pipeline.prefetch_depth must be >= 1")
        if self.shard not in ("process", "none"):
            raise DeepSpeedConfigError(
                f"data_pipeline.shard must be 'process' or 'none', got "
                f"{self.shard!r}")


@dataclass
class CurriculumConfig(ConfigModel):
    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 1
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = field(default_factory=dict)


@dataclass
class ProgressiveLayerDropConfig(ConfigModel):
    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


@dataclass
class EigenvalueConfig(ConfigModel):
    enabled: bool = False
    verbose: bool = False
    max_iter: int = 100
    tol: float = 1e-2
    stability: float = 1e-6
    gas_boundary_resolution: int = 1
    layer_name: str = "bert.encoder.layer"
    layer_num: int = 0


@dataclass
class AioConfig(ConfigModel):
    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True


@dataclass
class PipelineConfig(ConfigModel):
    stages: Any = "auto"
    partition: str = "best"
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    pipe_partitioned: bool = True
    grad_partitioned: bool = True


@dataclass
class GracefulShutdownConfig(ConfigModel):
    """Preemption grace handler (no reference analogue; docs/recovery.md).
    When enabled, the engine traps ``signals`` and, at the next step
    boundary, saves + commits a final checkpoint to ``save_dir`` before
    exiting — turning a slice preemption into a clean resume point."""

    enabled: bool = False
    save_dir: Optional[str] = None
    tag: Optional[str] = None  # None -> the default global_step<N> tag
    signals: List[str] = field(default_factory=lambda: ["SIGTERM", "SIGINT"])
    exit_after_save: bool = True
    exit_code: int = 0

    def __post_init__validate__(self):
        if self.enabled and not self.save_dir:
            raise DeepSpeedConfigError(
                "graceful_shutdown.enabled requires graceful_shutdown."
                "save_dir (where the final checkpoint goes)")
        import signal as _signal

        for name in self.signals:
            if not hasattr(_signal, str(name)):
                raise DeepSpeedConfigError(
                    f"graceful_shutdown.signals: unknown signal {name!r}")


@dataclass
class SentinelConfig(ConfigModel):
    """Training health sentinel (no reference analogue; docs/recovery.md
    "Divergence and hang recovery"). When enabled, the engine judges every
    optimizer step host-side — non-finite loss/grads (any dtype, not just
    the fp16 loss-scale path) plus rolling-window loss/grad-norm spike
    detection — and responds in graduated stages: cond-skip the bad batch
    (``skip_budget`` consecutive), roll back to the newest manifest-valid
    checkpoint (``rollback_budget`` times, reseeding the data order), then
    raise ``DivergenceError`` with ``divergence_exit_code``. A daemon
    hang watchdog arms around each step when ``hang_timeout_s > 0``."""

    enabled: bool = False
    check_nonfinite: bool = True
    window: int = 50            # rolling-window length (healthy steps)
    min_window: int = 10        # samples required before spike checks arm
    loss_spike_zscore: float = 6.0   # <=0 disables the z-score check
    loss_spike_ratio: float = 3.0    # <=0 disables the ratio check
    grad_spike_zscore: float = 6.0
    grad_spike_ratio: float = 10.0
    skip_budget: int = 3        # consecutive anomalies before rollback
    rollback_budget: int = 2    # rollbacks before DivergenceError
    rollback_dir: Optional[str] = None  # checkpoint root to roll back to
    reseed_on_rollback: bool = True
    divergence_exit_code: int = C.DIVERGENCE_EXIT_CODE_DEFAULT
    hang_timeout_s: float = 0.0  # 0 disables the watchdog
    hang_action: str = "warn"    # warn | abort
    hang_exit_code: int = C.SENTINEL_HANG_EXIT_CODE_DEFAULT

    def __post_init__validate__(self):
        if self.window < 2:
            raise DeepSpeedConfigError(
                f"sentinel.window must be >= 2, got {self.window}")
        if not (2 <= self.min_window <= self.window):
            raise DeepSpeedConfigError(
                f"sentinel.min_window must be in [2, window="
                f"{self.window}], got {self.min_window}")
        if self.skip_budget < 0 or self.rollback_budget < 0:
            raise DeepSpeedConfigError(
                "sentinel.skip_budget and sentinel.rollback_budget must "
                "be >= 0")
        if self.hang_timeout_s < 0:
            raise DeepSpeedConfigError(
                f"sentinel.hang_timeout_s must be >= 0 (0 disables), got "
                f"{self.hang_timeout_s}")
        if self.hang_action not in ("warn", "abort"):
            raise DeepSpeedConfigError(
                f"sentinel.hang_action must be 'warn' or 'abort', got "
                f"{self.hang_action!r}")
        for name in ("divergence_exit_code", "hang_exit_code"):
            code = getattr(self, name)
            if not (1 <= int(code) <= 255):
                raise DeepSpeedConfigError(
                    f"sentinel.{name} must be in [1, 255] (0 means "
                    f"success to the elastic agent), got {code}")


@dataclass
class TelemetryConfig(ConfigModel):
    """Telemetry bus + crash-forensics flight recorder
    (docs/observability.md "Telemetry events" / "Flight recorder").

    Enabled by default: the recorder is an in-memory ring (bounded, host
    timers only — no fences, no device pulls), so the healthy path pays
    microseconds per step and gains zero syncs. Blackbox dumps are
    written only when ``dump_dir`` resolves (the config field, else the
    ``DS_TPU_TELEMETRY_DIR`` env the elastic agent / launcher export);
    crash handlers (SIGTERM / excepthook / atexit) install only then."""

    enabled: bool = True
    ring_steps: int = 64          # step records kept (>= 32 for forensics)
    ring_events: int = 256        # bus events kept
    dump_dir: Optional[str] = None  # None -> DS_TPU_TELEMETRY_DIR env
    # live device.memory_stats() watermarks in each step record (host
    # query, no sync; auto-disabled after the first None on CPU)
    sample_memory: bool = True
    # fatal signals that trigger a dump (chained before any previous
    # handler, e.g. graceful_shutdown's flag-setter)
    dump_signals: List[str] = field(default_factory=lambda: ["SIGTERM"])

    def __post_init__validate__(self):
        if self.ring_steps < 1:
            raise DeepSpeedConfigError(
                f"telemetry.ring_steps must be >= 1, got {self.ring_steps}")
        if self.ring_events < 1:
            raise DeepSpeedConfigError(
                f"telemetry.ring_events must be >= 1, got "
                f"{self.ring_events}")
        import signal as _signal

        for name in self.dump_signals:
            if not hasattr(_signal, str(name)):
                raise DeepSpeedConfigError(
                    f"telemetry.dump_signals: unknown signal {name!r}")


@dataclass
class MeshConfig(ConfigModel):
    """TPU device-mesh axis sizes. -1 on ``dp`` means "use all remaining
    devices". No reference analogue: replaces mpu/process-group plumbing
    (reference utils/groups.py, pipe/topology.py) with named mesh axes."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1


@dataclass
class GradExchangeConfig(ConfigModel):
    """Explicit bucketed gradient exchange (``comm/bucketed.py``).

    ``deferred=True`` replaces XLA's implicit per-micro-step gradient psum
    with the compressed-path machinery at a bf16/fp32 wire format: grads
    stay per-worker through the accumulation window and are exchanged ONCE
    per optimizer step in size-bounded buckets at the GAS boundary (T3-style
    — cuts gradient wire bytes by the accumulation factor and frees XLA to
    overlap per-bucket collectives). ``bucket_mb`` also buckets the int8
    ``communication_data_type`` exchange (error-feedback residuals become
    per-bucket). 0 keeps the legacy per-leaf exchange. Defaults are
    off/safe: nothing changes unless explicitly enabled.
    """

    bucket_mb: float = 0.0
    deferred: bool = False
    wire_dtype: str = "bf16"  # bf16 | fp32 (deferred exchange payload)
    # two-level ICI/DCN exchange (comm/bucketed.py hierarchical_all_reduce):
    # intra-slice wire_dtype psum over ICI, inter-slice bucketed int8
    # EQuARX exchange over DCN. "auto" activates when the mesh detects a
    # multi-slice dp axis (MeshTopology.dcn_size("dp") > 1) and falls back
    # to the flat exchange otherwise; "on" demands slice structure (loud
    # failure without it). Requires deferred=true.
    hierarchical: str = "off"  # off | auto | on
    # >0 forces the inter-slice group count (slice-major over the dp axis)
    # instead of detecting it from device.slice_index — how the virtual
    # CPU mesh exercises the DCN leg; 0 = detect
    dcn_slices: int = 0
    dcn_block: int = 512  # int8 quantization block for the DCN leg

    def __post_init__(self):
        if self.wire_dtype not in ("bf16", "bfloat16", "fp32", "float32"):
            raise DeepSpeedConfigError(
                "tpu.grad_exchange.wire_dtype must be one of bf16/bfloat16/"
                f"fp32/float32, got {self.wire_dtype!r}")
        if self.bucket_mb < 0:
            raise DeepSpeedConfigError(
                f"tpu.grad_exchange.bucket_mb must be >= 0, got "
                f"{self.bucket_mb}")
        if self.hierarchical not in ("off", "auto", "on"):
            raise DeepSpeedConfigError(
                "tpu.grad_exchange.hierarchical must be one of off/auto/on,"
                f" got {self.hierarchical!r}")
        if self.dcn_slices < 0:
            raise DeepSpeedConfigError(
                f"tpu.grad_exchange.dcn_slices must be >= 0, got "
                f"{self.dcn_slices}")
        if self.dcn_block < 1:
            raise DeepSpeedConfigError(
                f"tpu.grad_exchange.dcn_block must be >= 1, got "
                f"{self.dcn_block}")


@dataclass
class TpuPipelineConfig(ConfigModel):
    """Pipeline stage-to-stage transport (``runtime/pipe/transport.py``).

    ``transport`` picks how activations/cotangents hop between stage
    sub-meshes:

    - ``device_put`` — host-level cross-mesh transfer (the original
      single-process fast path; on a multi-process CPU mesh this path
      cannot be emulated and hangs — see tests/unit/test_multihost.py).
    - ``ppermute`` — one jitted ``lax.ppermute`` over the JOINT (pp, dp)
      mesh: works across process boundaries and lets XLA overlap the
      transfer with compute.
    - ``auto`` — ppermute when ``jax.process_count() > 1``, device_put
      otherwise. The transport never leaks into checkpoint layout.
    """

    transport: str = "auto"  # auto | ppermute | device_put

    def __post_init__(self):
        if self.transport not in ("auto", "ppermute", "device_put"):
            raise DeepSpeedConfigError(
                "tpu.pipeline.transport must be one of auto/ppermute/"
                f"device_put, got {self.transport!r}")


@dataclass
class ClusterHealthConfig(ConfigModel):
    """Cluster health plane (``runtime/health.py``; docs/recovery.md
    "Cluster health & SDC defense"). An out-of-band TCP heartbeat mesh
    between training processes — daemon threads, never through XLA
    collectives, so it stays live while the main thread is wedged inside
    one. Peers are tracked with the healthy→suspect→down silence
    schedule shared with the serving fleet (utils/health_state.py); a
    peer declared down mid-step makes every survivor abort with
    ``exit_code`` (one world-level failure for the elastic agent instead
    of N staggered hang timeouts); per-host step-time skew emits
    ``health.straggler``; and every ``digest_every_k`` steps an SDC probe
    digests the fully-replicated param leaves and cross-checks the
    digests over the mesh."""

    # "auto" = off single-process, on when jax.process_count() > 1; also
    # accepts plain booleans from JSON
    enabled: Any = "auto"
    host: str = "127.0.0.1"      # address this rank's beat server binds
    port_base: int = 29700       # rank r listens on port_base + r
    peers: List[str] = field(default_factory=list)  # ["host:port", ...]
    beat_interval_s: float = 0.5
    suspect_after_s: float = 2.0
    down_after_s: float = 6.0
    recover_probes: int = 2
    abort_on_peer_loss: bool = True
    exit_code: int = C.PEER_LOSS_EXIT_CODE_DEFAULT
    # SDC parameter-digest probe cadence in optimizer steps (0 disables)
    digest_every_k: int = 0
    # "abort": coordinated exit_code abort (the agent relaunches the
    # world from the newest manifest-valid tag); "rollback": flag the
    # mismatch for the engine, which routes through the sentinel's
    # in-process rollback at the next step boundary
    sdc_action: str = "abort"
    # straggler detection: own step-time EWMA vs the fleet median
    straggler_ratio: float = 1.5       # <=0 disables
    straggler_min_peers: int = 2       # ewma samples needed before judging
    ewma_alpha: float = 0.2
    # peer step counters further apart than this emit health.desync
    step_skew_threshold: int = 10      # <=0 disables

    def __post_init__validate__(self):
        if self.enabled not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                "tpu.cluster_health.enabled must be true/false/'auto', "
                f"got {self.enabled!r}")
        if self.beat_interval_s <= 0:
            raise DeepSpeedConfigError(
                "tpu.cluster_health.beat_interval_s must be > 0, got "
                f"{self.beat_interval_s}")
        if not 0 < self.suspect_after_s < self.down_after_s:
            raise DeepSpeedConfigError(
                "tpu.cluster_health needs 0 < suspect_after_s < "
                f"down_after_s, got {self.suspect_after_s} / "
                f"{self.down_after_s}")
        if self.beat_interval_s >= self.suspect_after_s:
            raise DeepSpeedConfigError(
                "tpu.cluster_health.beat_interval_s must be < "
                "suspect_after_s (a healthy peer must beat faster than "
                f"the schedule suspects it), got {self.beat_interval_s} "
                f">= {self.suspect_after_s}")
        if self.recover_probes < 1:
            raise DeepSpeedConfigError(
                "tpu.cluster_health.recover_probes must be >= 1, got "
                f"{self.recover_probes}")
        if not (1 <= int(self.exit_code) <= 255):
            raise DeepSpeedConfigError(
                "tpu.cluster_health.exit_code must be in [1, 255], got "
                f"{self.exit_code}")
        if self.digest_every_k < 0:
            raise DeepSpeedConfigError(
                "tpu.cluster_health.digest_every_k must be >= 0 "
                f"(0 disables), got {self.digest_every_k}")
        if self.sdc_action not in ("abort", "rollback"):
            raise DeepSpeedConfigError(
                "tpu.cluster_health.sdc_action must be 'abort' or "
                f"'rollback', got {self.sdc_action!r}")
        if not 0 < self.ewma_alpha <= 1:
            raise DeepSpeedConfigError(
                "tpu.cluster_health.ewma_alpha must be in (0, 1], got "
                f"{self.ewma_alpha}")
        if not (1 <= self.port_base <= 65535):
            raise DeepSpeedConfigError(
                "tpu.cluster_health.port_base must be a valid port, got "
                f"{self.port_base}")

    def resolve_enabled(self, process_count: int) -> bool:
        """Auto-on exactly when there is a peer to watch."""
        if self.enabled == "auto":
            return int(process_count) > 1
        return bool(self.enabled)


@dataclass
class TpuConfig(ConfigModel):
    mesh: Dict[str, Any] = field(default_factory=dict)
    remat: str = "none"  # none | full | selective (dots_saveable)
    donate_params: bool = True
    matmul_precision: str = "default"
    # route FusedAdam to the Pallas kernel (ops/pallas/fused_adam.py) instead
    # of optax's XLA-fused chain
    use_pallas_optimizer: bool = False
    # debug observability for the 1-bit optimizers: materialize the exact
    # averaged-gradient norm each step via an UNCOMPRESSED pmean (costs a
    # full fp32 allreduce — defeats the compression, debug only) so
    # get_global_grad_norm() and monitors keep working. The int8 path
    # materializes its post-exchange norm for free and ignores this flag.
    compressed_grad_norm: bool = False
    # explicit bucketed gradient exchange — see GradExchangeConfig
    grad_exchange: Dict[str, Any] = field(default_factory=dict)
    # pipeline stage-to-stage transport — see TpuPipelineConfig
    pipeline: Dict[str, Any] = field(default_factory=dict)
    # out-of-band heartbeat mesh + SDC probes — see ClusterHealthConfig
    cluster_health: Dict[str, Any] = field(default_factory=dict)

    @property
    def mesh_config(self) -> MeshConfig:
        return MeshConfig.from_dict(self.mesh)

    @property
    def cluster_health_config(self) -> ClusterHealthConfig:
        return ClusterHealthConfig.from_dict(self.cluster_health)

    @property
    def pipeline_config(self) -> "TpuPipelineConfig":
        return TpuPipelineConfig.from_dict(self.pipeline)

    @property
    def grad_exchange_config(self) -> GradExchangeConfig:
        return GradExchangeConfig.from_dict(self.grad_exchange)


# ---------------------------------------------------------------------------
# Main config
# ---------------------------------------------------------------------------
class DeepSpeedConfig:
    """Parses a DeepSpeed-style JSON config (path or dict) and resolves the
    batch triad ``train_batch_size = micro_batch * grad_accum * dp_world``
    exactly like reference ``runtime/config.py:712-1058``."""

    def __init__(self, config, dp_world_size: Optional[int] = None):
        if isinstance(config, str):
            if not os.path.exists(config):
                raise DeepSpeedConfigError(f"config path does not exist: {config}")
            with open(config, "r") as f:
                self._param_dict = json.load(
                    f, object_pairs_hook=dict_raise_error_on_duplicate_keys
                )
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        else:
            raise DeepSpeedConfigError(
                f"config must be a path or dict, got {type(config)}"
            )

        self.dp_world_size = dp_world_size
        self._initialize(self._param_dict)

    # -- feature blocks ----------------------------------------------------
    def _initialize(self, pd: Dict[str, Any]):
        self.train_batch_size = get_scalar_param(pd, C.TRAIN_BATCH_SIZE, None)
        self.train_micro_batch_size_per_gpu = get_scalar_param(
            pd, C.TRAIN_MICRO_BATCH_SIZE_PER_GPU, None
        )
        self.gradient_accumulation_steps = get_scalar_param(
            pd, C.GRADIENT_ACCUMULATION_STEPS, None
        )
        self.steps_per_print = get_scalar_param(
            pd, C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT
        )
        self.gradient_clipping = get_scalar_param(
            pd, C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT
        )
        self.prescale_gradients = get_scalar_param(
            pd, C.PRESCALE_GRADIENTS, C.PRESCALE_GRADIENTS_DEFAULT
        )
        self.gradient_predivide_factor = get_scalar_param(
            pd, C.GRADIENT_PREDIVIDE_FACTOR, C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT
        )
        self.sparse_gradients_enabled = get_scalar_param(
            pd, C.SPARSE_GRADIENTS, C.SPARSE_GRADIENTS_DEFAULT
        )
        self.wall_clock_breakdown = get_scalar_param(
            pd, C.WALL_CLOCK_BREAKDOWN, C.WALL_CLOCK_BREAKDOWN_DEFAULT
        )
        self.memory_breakdown = get_scalar_param(
            pd, C.MEMORY_BREAKDOWN, C.MEMORY_BREAKDOWN_DEFAULT
        )
        self.dump_state = get_scalar_param(pd, C.DUMP_STATE, C.DUMP_STATE_DEFAULT)
        self.dataloader_drop_last = get_scalar_param(
            pd, C.DATALOADER_DROP_LAST, C.DATALOADER_DROP_LAST_DEFAULT
        )
        self.zero_allow_untested_optimizer = get_scalar_param(
            pd, C.ZERO_ALLOW_UNTESTED_OPTIMIZER, C.ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT
        )
        self.communication_data_type = get_scalar_param(
            pd, C.COMMUNICATION_DATA_TYPE, C.COMMUNICATION_DATA_TYPE_DEFAULT
        )
        if self.communication_data_type is not None and (
                self.communication_data_type not in C.COMMUNICATION_DATA_TYPES):
            raise DeepSpeedConfigError(
                f"Invalid {C.COMMUNICATION_DATA_TYPE}. Supported: "
                f"{C.COMMUNICATION_DATA_TYPES}. "
                f"Got: {self.communication_data_type}"
            )

        self.fp16 = Fp16Config.from_dict(pd.get(C.FP16, {}))
        bf16_block = pd.get(C.BFLOAT16, pd.get(C.BFLOAT16_OLD, {}))
        self.bf16 = Bf16Config.from_dict(bf16_block)
        self.amp = AmpConfig.from_dict(pd.get(C.AMP, {}))
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")

        self.zero_config = ZeroConfig.from_dict(pd.get(C.ZERO_OPTIMIZATION, {}))
        self.optimizer = OptimizerConfig.from_dict(pd.get(C.OPTIMIZER, {}))
        self.scheduler = SchedulerConfig.from_dict(pd.get(C.SCHEDULER, {}))
        self.activation_checkpointing = ActivationCheckpointingConfig.from_dict(
            pd.get(C.ACTIVATION_CHECKPOINTING, {})
        )
        self.flops_profiler = FlopsProfilerConfig.from_dict(
            pd.get(C.FLOPS_PROFILER, {})
        )
        self.tensorboard = TensorboardConfig.from_dict(pd.get(C.MONITOR_TENSORBOARD, {}))
        self.wandb = WandbConfig.from_dict(pd.get(C.MONITOR_WANDB, {}))
        self.csv_monitor = CsvConfig.from_dict(pd.get(C.MONITOR_CSV, {}))
        self.comms_logger = CommsLoggerConfig.from_dict(pd.get(C.COMMS_LOGGER, {}))
        self.step_profiler = StepProfilerConfig.from_dict(
            pd.get(C.STEP_PROFILER, {}))
        self.data_pipeline = DataPipelineConfig.from_dict(
            pd.get(C.DATA_PIPELINE, {}))
        self.curriculum_learning = CurriculumConfig.from_dict(
            pd.get(C.CURRICULUM_LEARNING, {})
        )
        self.progressive_layer_drop = ProgressiveLayerDropConfig.from_dict(
            pd.get(C.PROGRESSIVE_LAYER_DROP, {})
        )
        self.eigenvalue = EigenvalueConfig.from_dict(pd.get(C.EIGENVALUE, {}))
        self.aio = AioConfig.from_dict(pd.get(C.AIO, {}))
        self.pipeline = PipelineConfig.from_dict(pd.get(C.PIPELINE, {}))
        self.tpu = TpuConfig.from_dict(pd.get(C.TPU, {}))
        # Dict-shaped blocks consumed by their own subsystems
        self.sparse_attention = pd.get(C.SPARSE_ATTENTION, None)
        self.elasticity = pd.get(C.ELASTICITY, {})
        self.autotuning = pd.get(C.AUTOTUNING, {})
        self.compression_training = pd.get(C.COMPRESSION_TRAINING, {})
        self.data_efficiency = pd.get(C.DATA_EFFICIENCY, {})
        self.quantize_training = pd.get(C.QUANTIZE_TRAINING, {})
        from deepspeed_tpu.nebula import NebulaConfig

        self.nebula = NebulaConfig.from_dict(pd.get(C.NEBULA, {}))
        ckpt = pd.get(C.CHECKPOINT, {}) or {}
        self.checkpoint_tag_validation = str(
            ckpt.get(C.CHECKPOINT_TAG_VALIDATION, C.CHECKPOINT_TAG_VALIDATION_DEFAULT)
        ).title()
        if self.checkpoint_tag_validation not in C.CHECKPOINT_TAG_VALIDATION_MODES:
            raise DeepSpeedConfigError(
                f"checkpoint.tag_validation must be one of "
                f"{C.CHECKPOINT_TAG_VALIDATION_MODES}"
            )
        self.load_universal_checkpoint = ckpt.get(
            C.LOAD_UNIVERSAL_CHECKPOINT, C.LOAD_UNIVERSAL_CHECKPOINT_DEFAULT
        )
        self.checkpoint_keep_n = int(ckpt.get(
            C.CHECKPOINT_KEEP_N, C.CHECKPOINT_KEEP_N_DEFAULT))
        if self.checkpoint_keep_n < 0:
            raise DeepSpeedConfigError(
                f"checkpoint.keep_n must be >= 0 (0 = keep all), got "
                f"{self.checkpoint_keep_n}")
        self.checkpoint_verify = bool(ckpt.get(
            C.CHECKPOINT_VERIFY, C.CHECKPOINT_VERIFY_DEFAULT))
        self.graceful_shutdown = GracefulShutdownConfig.from_dict(
            pd.get(C.GRACEFUL_SHUTDOWN, {}))
        self.sentinel = SentinelConfig.from_dict(pd.get(C.SENTINEL, {}))
        self.telemetry = TelemetryConfig.from_dict(pd.get(C.TELEMETRY, {}))

        if self.dp_world_size is not None:
            self._resolve_batch_triad(self.dp_world_size)

    # -- batch triad (reference runtime/config.py _batch_assertion etc.) ---
    def _resolve_batch_triad(self, dp_world_size: int):
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps

        for name, v in ((C.TRAIN_BATCH_SIZE, train),
                        (C.TRAIN_MICRO_BATCH_SIZE_PER_GPU, micro),
                        (C.GRADIENT_ACCUMULATION_STEPS, gas)):
            if v is not None and v <= 0:
                raise DeepSpeedConfigError(f"{name} must be positive, got {v}")

        if train is not None and micro is not None and gas is not None:
            pass
        elif train is not None and micro is not None:
            gas = train // (micro * dp_world_size)
        elif train is not None and gas is not None:
            micro = train // (gas * dp_world_size)
        elif micro is not None and gas is not None:
            train = micro * gas * dp_world_size
        elif train is not None:
            gas = 1
            micro = train // dp_world_size
        elif micro is not None:
            gas = 1
            train = micro * dp_world_size
        else:
            raise DeepSpeedConfigError(
                "At least one of train_batch_size or "
                "train_micro_batch_size_per_gpu must be set"
            )

        if micro is None or micro <= 0 or gas is None or gas <= 0:
            raise DeepSpeedConfigError(
                f"Could not resolve a positive batch triad from "
                f"train={self.train_batch_size} micro="
                f"{self.train_micro_batch_size_per_gpu} "
                f"gas={self.gradient_accumulation_steps} dp={dp_world_size}"
            )
        if train != micro * gas * dp_world_size:
            raise DeepSpeedConfigError(
                f"Batch triad inconsistent: train_batch_size {train} != "
                f"micro_batch {micro} * grad_accum {gas} * dp {dp_world_size}"
            )
        self.train_batch_size = train
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = gas

    # -- convenience -------------------------------------------------------
    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def precision_dtype(self) -> str:
        if self.bf16.enabled:
            return "bfloat16"
        if self.fp16.enabled:
            return "float16"
        return "float32"

    def print_config(self):
        logger.info("DeepSpeedConfig:\n%s", pretty_json(self._param_dict))
