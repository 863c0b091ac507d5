"""Master config (reference: deepspeed/runtime/config.py DeepSpeedConfig).

Accepts the reference's JSON schema (train_batch_size /
train_micro_batch_size_per_gpu / gradient_accumulation_steps, optimizer,
scheduler, fp16/bf16, zero_optimization, gradient_clipping, ...) plus
TPU-specific blocks (``mesh``). ``train_micro_batch_size_per_gpu`` is kept
under its reference name; "gpu" reads as "chip".

Batch-size resolution follows ``runtime/config.py:_batch_assertion``:
train_batch == micro_batch * grad_accum * data_parallel_size, with any one
of the three derivable from the other two.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Literal, Optional

from pydantic import Field

from .config_utils import DeepSpeedConfigModel

TRAIN_BATCH_SIZE_DEFAULT = None
GRADIENT_CLIPPING_DEFAULT = 0.0


class FP16Config(DeepSpeedConfigModel):
    enabled: bool = False
    loss_scale: float = 0.0  # 0 -> dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0
    auto_cast: bool = False


class BF16Config(DeepSpeedConfigModel):
    enabled: bool = False


class OffloadOptimizerConfig(DeepSpeedConfigModel):
    """reference: runtime/zero/offload_config.py DeepSpeedZeroOffloadOptimizerConfig"""
    device: Literal["cpu", "nvme", "none"] = "none"
    nvme_path: Optional[str] = None
    pin_memory: bool = False
    ratio: float = 1.0
    # TPU extension (streamed/Infinity tier): storage dtype of the Adam
    # moments in host memory. bfloat16 halves the host-memory footprint
    # and the per-step device<->host traffic of m/v; the update math
    # still runs in fp32 on device (master stays fp32 regardless).
    moment_dtype: Literal["float32", "bfloat16"] = "float32"


class OffloadParamConfig(DeepSpeedConfigModel):
    device: Literal["cpu", "nvme", "none"] = "none"
    nvme_path: Optional[str] = None
    pin_memory: bool = False
    # TPU extension: layer-streamed params (runtime/infinity.py). None =
    # auto (stage 3 + device=cpu + single chip); True forces the
    # streamed engine (CPU tests), False forces the whole-tree-fetch
    # sharded path.
    stream: Optional[bool] = None
    # TPU extension (streamed cpu tier): what phase A streams per layer.
    # "master" (default) streams the fp32 master directly — minimum
    # host RAM. "compute" keeps a bf16 copy of the layer stacks in
    # pinned_host, halving fwd/bwd H2D bytes at +2 bytes/param of host
    # RAM — measured on a v5e host at 7B scale the extra pinned
    # footprint (~81 GiB total) cost MORE in host-memory pressure than
    # the halved bytes saved (98s/step master vs 107.5s compute), so
    # opt in only with RAM headroom. The nvme tier always keeps the
    # compute-dtype stack (master is on disk).
    stream_dtype: Literal["compute", "master"] = "master"


class ZeroConfig(DeepSpeedConfigModel):
    """reference: runtime/zero/config.py DeepSpeedZeroConfig"""
    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = int(5e8)
    allgather_bucket_size: int = int(5e8)
    overlap_comm: bool = True  # XLA overlaps collectives natively
    offload_optimizer: OffloadOptimizerConfig = Field(
        default_factory=OffloadOptimizerConfig)
    offload_param: OffloadParamConfig = Field(default_factory=OffloadParamConfig)
    sub_group_size: int = int(1e9)
    stage3_prefetch_bucket_size: int = int(5e7)
    stage3_param_persistence_threshold: int = int(1e5)
    stage3_max_live_parameters: int = int(1e9)
    stage3_gather_16bit_weights_on_model_save: bool = False
    zero_hpz_partition_size: int = 1  # ZeRO++ hierarchical partition
    zero_quantized_weights: bool = False  # ZeRO++ qwZ
    zero_quantized_gradients: bool = False  # ZeRO++ qgZ
    # wire format for qwZ/qgZ payloads: int8 (reference CUDAQuantizer) or
    # fp8 e4m3 (native float8 dtype; this build's extension)
    zero_quantized_dtype: Literal["int8", "fp8"] = "int8"
    # two-hop weight-gather / gradient-exchange over an fsdp×zps-split
    # mesh (set mesh.zps > 1): intra-zps hop on fast links first, then
    # the inter-fsdp hop (quantized when qwZ/qgZ are on) — slow-link
    # traffic drops by the zps factor (ZeRO++ hierarchy over the
    # MiCS-style full shard; docs/zeropp.md). Validated against the
    # mesh at engine init.
    zero_hierarchical_allgather: bool = False
    # gradient-wire rounding for qgZ: "stochastic" (default) is the
    # unbiased floor-plus-uniform mode keyed on the step counter —
    # quantization noise averages out across steps so the loss
    # trajectory tracks the fp32 wire; "nearest" is deterministic
    # round-to-nearest (int8 wire only; fp8 rounds via the dtype cast)
    zero_quantized_rounding: Literal["stochastic", "nearest"] = \
        "stochastic"
    mics_shard_size: int = -1  # MiCS sub-cluster size (ref zero/config.py)
    mics_hierarchical_params_gather: bool = False
    round_robin_gradients: bool = False
    ignore_unused_parameters: bool = True


class OptimizerConfig(DeepSpeedConfigModel):
    type: str = "adamw"
    params: dict[str, Any] = Field(default_factory=dict)


class SchedulerConfig(DeepSpeedConfigModel):
    type: Optional[str] = None
    params: dict[str, Any] = Field(default_factory=dict)


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """reference: runtime/activation_checkpointing/config.py. When
    ``policy`` is set EXPLICITLY the engine plumbs it into the model's
    ``remat_policy`` (``"none"`` disables remat entirely) — the knob
    the autotuning planner's chosen plan patches, so a plan ``apply()``
    reproduces the remat decision through config alone. Under every
    policy a rematted layer also keeps what a kernel's forward rule
    declares (the flash kernel's output and row log-sum-exp, one
    layer-boundary-sized tensor an attention layer): see
    ``models/transformer.py`` ``_remat_policy``."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: jax.checkpoint policy name ("none" = remat off;
    # "nothing_saveable" = whole-layer recompute but for kernels' declared
    # residuals)
    policy: str = "nothing_saveable"


class HybridEngineConfig(DeepSpeedConfigModel):
    """reference: deepspeed/runtime/config.py hybrid_engine block
    (DeepSpeedHybridEngineConfig: enabled, max_out_tokens,
    inference_tp_size, release_inference_cache, pin_parameters,
    tp_gather_partition_size)."""
    enabled: bool = False
    max_out_tokens: int = 512
    inference_tp_size: int = 1
    release_inference_cache: bool = False
    pin_parameters: bool = True
    tp_gather_partition_size: int = 8


class MeshConfig(DeepSpeedConfigModel):
    """TPU-specific: degrees for each mesh axis; fsdp=-1 absorbs the rest.
    ``zps`` (ZeRO++ hpZ / MiCS shard subgroup) is normally derived from
    zero_hpz_partition_size / mics_shard_size, not set directly.

    ``dcn`` maps axis names to the portion of their degree that spans
    data-center-network (multi-slice) boundaries, e.g.
    ``{"mesh": {"pp": 4, "dcn": {"pp": 2}}}`` runs pipeline stages 2-wide
    across slices and 2-deep within each slice; axes absent from ``dcn``
    stay entirely on intra-slice ICI (parallel/mesh.py
    build_device_array; reference: runtime/pipe/topology.py
    ProcessTopology)."""
    pp: int = 1
    dp: int = 1
    fsdp: int = -1
    zps: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    dcn: Dict[str, int] = {}


class SequenceParallelConfig(DeepSpeedConfigModel):
    """TPU-native SP config: Ulysses all-to-all (reference
    deepspeed/sequence) or ring attention (context parallelism, not in the
    reference). 'auto' = ulysses when mesh.sp > 1."""
    mode: Literal["auto", "ulysses", "ring"] = "auto"


class CommsLoggerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    prof_ops: list[str] = Field(default_factory=list)
    debug: bool = False


class TelemetryConfig(DeepSpeedConfigModel):
    """Unified observability (``deepspeed_tpu/telemetry/``): host-side
    span tracing with Chrome-trace (Perfetto) export plus a process-wide
    metrics registry with Prometheus text exposition. Activated by the
    engine when ``enabled`` is true; ``wall_clock_breakdown: true`` also
    activates the span tracer (the fwd/bwd/step breakdown events are
    sourced from span data). When disabled nothing is imported or
    allocated — hot-loop call sites are guarded. See
    docs/observability.md."""
    enabled: bool = False
    # span ring-buffer capacity (events; oldest dropped first).
    # Cumulative per-name totals survive eviction.
    span_buffer_size: int = 8192
    # mirror every span into a jax.profiler.TraceAnnotation so it also
    # lands in the XPlane trace captured by jax.profiler.trace()
    profiler_annotations: bool = True
    # capture jit compile count/time via jax.monitoring
    jax_compile_events: bool = True
    # registry -> MonitorMaster flush cadence in engine steps
    # (0 = follow steps_per_print)
    flush_interval_steps: int = 0
    # --- device-truth layer (ISSUE 5), opt-in on top of enabled ------
    # register every observed compiled executable's cost_analysis()/
    # memory_analysis() (FLOPs, HBM) keyed by jit name + shape
    # signature; feeds the ds_ledger_* / HBM-headroom gauges and the
    # <prefix>.op_scopes.json / .op_work.json artifacts (HLO instruction
    # -> device scope; -> kind of work and bytes at its boundary).
    # Costs ONE extra backend compile per new executable at warmup.
    executable_ledger: bool = False
    # walk each registered executable's HLO for collective ops and
    # attribute payload bytes to mesh axes (requires executable_ledger)
    hlo_collectives: bool = True
    # per-rank ring buffer of recent dispatch/progress events, dumped
    # on hangs (telemetry/flightrec.py)
    flight_recorder: bool = False
    flight_recorder_size: int = 2048
    # hang watchdog: if instrumented loops (train_batch, fused-decode
    # drain) report no progress for this many seconds, dump flight
    # recorder + open spans + ledger + thread stacks to
    # watchdog_artifact_dir (0 = watchdog off; needs flight_recorder)
    watchdog_deadline_s: float = 0.0
    watchdog_artifact_dir: str = "telemetry_hangdump"
    # SIGABRT the process after a hang dump so a supervisor restarts
    # it (instead of an external timeout SIGKILLing without forensics)
    watchdog_abort: bool = False
    # --- per-request serving traces (ISSUE 10) -----------------------
    # record one lifecycle trace per serving request (enqueue/admit/
    # prefill/dispatch/drain/park/finish) with an exact TTFT + ITL
    # latency decomposition; exported as per-request Perfetto tracks,
    # a JSONL access log and component/SLO registry metrics. Host-only
    # ring; nothing is recorded until requests flow.
    request_traces: bool = True
    # completed-trace ring capacity (requests; oldest dropped first)
    request_trace_size: int = 1024
    # --- per-step training traces (ISSUE 20) -------------------------
    # record one telescoped record per train_batch (step_wall =
    # data_wait + h2d + dispatch_overhead + device_compute +
    # exposed_comm + optimizer + checkpoint + recompile + residual,
    # exact by construction), the run goodput/badput ledger
    # (ds_train_goodput_fraction / ds_train_badput_seconds{bucket}),
    # a JSONL step log, per-step Perfetto tracks, and an online
    # mean-shift regression detector over the per-component series.
    # Host-only ring; nothing is recorded until train_batch runs.
    steptrace: bool = True
    # step-record ring capacity (steps; oldest dropped first)
    steptrace_size: int = 2048
    # regression detector: compare the mean of the last W steps
    # against the W before them, per component; fire when the recent
    # mean exceeds base * (1 + threshold)
    steptrace_regression_window: int = 32
    steptrace_regression_threshold: float = 0.5
    # minimum seconds between per-step straggler-skew samples (two
    # tiny host collectives each; multiprocess only)
    straggler_interval_s: float = 1.0
    # --- fleet health plane (ISSUE 17), opt-in on top of enabled -----
    # install the time-series ring (periodic registry snapshots ->
    # windowed rates / SLO burn), the phi-accrual health monitor, and
    # the FleetScope aggregator; export_artifacts then also writes the
    # versioned <prefix>.fleet.json rollup. The serving router also
    # installs this layer when its RouterConfig.health block is on.
    fleet: bool = False
    # this process's replica name inside the fleet rollup
    # ("" = proc<pid>)
    fleet_replica: str = ""
    # snapshot ring capacity (samples; oldest dropped first) and the
    # minimum seconds between accepted samples (the serving loop calls
    # maybe_sample() on its housekeeping path; the ring rate-limits)
    timeseries_capacity: int = 512
    timeseries_interval_s: float = 0.25
    # multi-window burn-rate lookbacks in seconds (fast burn -> slow
    # burn), à la SRE fast/slow-burn alerting; [] = the built-in
    # (60, 300, 3600)
    burn_windows_s: list[float] = Field(default_factory=list)


class SentinelsConfig(DeepSpeedConfigModel):
    """Runtime dispatch-discipline enforcement (ISSUE 3,
    ``deepspeed_tpu/analysis/sentinels.py``): a recompile sentinel that
    asserts the warmed-up compiled step never retraces (catching silent
    shape/dtype drift that would recompile every step), and a
    ``jax.transfer_guard("disallow")`` scope around the hot dispatch so
    implicit host<->device transfers raise instead of silently
    serializing the pipeline. Complements the static ``graftlint``
    checks (``tools/graftlint.py``) at runtime. Disabled by default —
    nothing is imported and the dispatch path is untouched. See
    docs/static-analysis.md."""
    enabled: bool = False
    # "raise" fails fast (tests/bench); "warn" logs and keeps going
    mode: Literal["raise", "warn"] = "raise"
    # arm the no-recompile assertion on train_batch
    recompile: bool = True
    # wrap the compiled-step dispatch in transfer_guard("disallow")
    transfer_guard: bool = True
    # dispatches allowed to compile before the assertion arms
    warmup_steps: int = 1


class MeshsanConfig(DeepSpeedConfigModel):
    """Runtime mesh-traffic sanitizer (ISSUE 15,
    ``deepspeed_tpu/analysis/meshsan.py`` — the runtime half of the
    shardlint GL060-GL063 static SPMD pass). Cross-checks every
    compiled executable's ACTUAL collective traffic (the telemetry
    ledger's optimized-HLO walk; requires
    ``telemetry.executable_ledger``) against a declared per-executable
    traffic contract seeded from the mesh topology and the ZeRO++ wire
    flags: traffic on an undeclared axis, an unexpected
    all-to-all/collective-permute (the GSPMD silent-reshard signature),
    or full-precision bytes on an axis configured for an int8 wire
    become named findings carrying executable, axis, op and bytes —
    counted in ``ds_meshsan_violations_total{kind}`` and embedded
    (with per-collective stall attribution) in hang-watchdog dumps.
    Off by default — nothing is imported and the dispatch path is
    untouched. Env ``DS_MESHSAN=1`` force-enables (the conftest/CI
    opt-in knob). See docs/static-analysis.md, "SPMD correctness"."""
    enabled: bool = False
    # "raise" fails fast (tests/bench); "warn" logs, counts, and keeps
    # training (violations still reach ds_meshsan_violations_total)
    mode: Literal["raise", "warn"] = "raise"
    # override the auto-seeded contract: axes the compiled step may
    # move bytes on / carry all-to-all traffic on (None = seed from
    # the mesh topology + ZeRO++ flags; see
    # analysis.meshsan.seed_training_contract)
    axes: Optional[list[str]] = None
    all_to_all_axes: Optional[list[str]] = None
    # collectives below this payload never trip the wire-width check
    # (tiny fp32 control reductions — loss means, found-inf flags —
    # are not wire traffic)
    wire_min_bytes: int = Field(65536, ge=0)


class NumsanConfig(DeepSpeedConfigModel):
    """Runtime numerics sanitizer (ISSUE 18,
    ``deepspeed_tpu/analysis/numsan.py`` — the runtime half of the
    GL070-GL073 numerics lint). When enabled the compiled train step
    folds per-leaf non-finite counts + max|g| into the fused reduction
    that already computes the overflow bit, so a blown-up step becomes
    a named finding carrying the executable's ledger name and the
    worst leaf's PyTree path (instead of one anonymous bit feeding the
    loss scaler); every quantize site (KV write, qgZ wire, MoE
    dispatch) additionally reports its saturating-code fraction to the
    ``ds_numsan_saturation_ratio{site}`` gauge via a trace-time-armed
    ``jax.debug.callback``, and a fraction above ``saturation_ceiling``
    is a finding. Violations bump ``ds_numsan_violations_total{kind}``
    and the sanitizer's state rides hang-watchdog dumps next to
    blocksan's/meshsan's sections. Off by default — nothing is
    imported and every executable stays byte-identical. Env
    ``DS_NUMSAN=1`` force-enables (the conftest/CI opt-in knob). See
    docs/static-analysis.md, "Numerics"."""
    enabled: bool = False
    # "raise" fails fast (tests/bench); "warn" logs, counts, and keeps
    # training (violations still reach ds_numsan_violations_total)
    mode: Literal["raise", "warn"] = "raise"
    # saturating-code fraction above which a quantize site is a
    # finding (the healthy baseline is ~1/block_size: the block max
    # lands exactly on the clip boundary by construction)
    saturation_ceiling: float = Field(0.05, ge=0.0, le=1.0)
    # arm the quantize-site jax.debug.callback probes (qgZ wire, MoE
    # dispatch; adds one small fused reduction per armed site)
    saturation_probe: bool = True


class MoEConfig(DeepSpeedConfigModel):
    """Expert-parallel MoE training (ISSUE 16, docs/moe.md). Routes the
    dispatch/combine token shuffle of an MoE model (``num_experts > 0``)
    through the explicit hierarchical exchange
    (``runtime/comm/moe_alltoall.py``): fast intra-hop over ``zps``
    first, slow inter-hop over ``dp``/``fsdp`` on 1/zps-sized partials,
    with an optional int8/fp8 stochastic-rounded wire for the
    dispatched activations (the ZeRO++ qgZ protocol applied to tokens).
    Routing semantics (top_k_gating capacity/drops) are unchanged —
    only the wire. Ignored for dense models."""
    # None = auto: engage the explicit dispatcher when mesh.ep > 1
    # (true forces it on any token-sharded mesh — e.g. to get the
    # quantized dispatch wire without expert sharding; false keeps
    # XLA's implicit dispatch collectives)
    enabled: Optional[bool] = None
    # dispatch-activation wire: fp32 = exact, bf16 = half-width,
    # int8/fp8 = block-quantized qgZ wire (forward only; gradients flow
    # straight-through at full width)
    wire_dtype: Literal["fp32", "bf16", "int8", "fp8"] = "fp32"
    # int8 wire rounding; "stochastic" keys unbiased noise on the
    # training step (recommended — wire error averages out over steps)
    rounding: Literal["nearest", "stochastic"] = "stochastic"
    # routing overrides (None = the model config's values); surfaced so
    # the autotuner can grid capacity_factor without rebuilding models
    capacity_factor: Optional[float] = None
    min_capacity: Optional[int] = None
    # publish router drop-fraction / expert-load gauges each step via
    # jax.debug.callback (requires active telemetry; small dispatch
    # overhead — off by default)
    router_telemetry: bool = False


class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class MonitorConfigBase(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class TensorBoardConfig(MonitorConfigBase):
    pass


class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


class CometConfig(DeepSpeedConfigModel):
    """reference: monitor/config.py CometConfig."""
    enabled: bool = False
    samples_log_interval: int = 100
    project: Optional[str] = None
    workspace: Optional[str] = None
    api_key: Optional[str] = None
    experiment_name: Optional[str] = None
    experiment_key: Optional[str] = None
    online: Optional[bool] = None
    mode: Optional[str] = None


class CSVConfig(MonitorConfigBase):
    pass


class PipelineConfig(DeepSpeedConfigModel):
    stages: Literal["auto"] | int = "auto"
    partition_method: str = "parameters"
    activation_checkpoint_interval: int = 0
    # "gpipe": differentiable scan, all-M schedule, per-device activation
    #   memory ~ flat/pp, no recompute. "1f1b": reference TrainSchedule
    #   parity (runtime/pipe/schedule.py:189) — in-flight <= pp
    #   microbatches, stage inputs ring-buffered, backward recomputes the
    #   stage forward per microbatch (Megatron-style checkpointing).
    schedule: Literal["gpipe", "1f1b"] = "gpipe"


class DataEfficiencyConfig(DeepSpeedConfigModel):
    enabled: bool = False
    seed: int = 1234
    data_sampling: dict[str, Any] = Field(default_factory=dict)
    data_routing: dict[str, Any] = Field(default_factory=dict)


class CurriculumLearningConfig(DeepSpeedConfigModel):
    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: dict[str, Any] = Field(default_factory=dict)


# Compression parsing lives with the subsystem (compression/config.py,
# get_compression_config); the engine passes this raw section through.


class AIOConfig(DeepSpeedConfigModel):
    """reference: runtime/swap_tensor/aio_config.py"""
    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True


class CheckpointConfig(DeepSpeedConfigModel):
    """reference: runtime/config.py checkpoint_config + nebula config.
    ``async_save`` selects the background-serialized engine (the Nebula
    analogue)."""
    tag_validation: Literal["Ignore", "Warn", "Fail"] = "Warn"
    load_universal: bool = False
    async_save: bool = False


# Single source of truth for the elasticity block lives with the
# subsystem; re-exported here so DeepSpeedConfig.elasticity parses it.
from ..elasticity.config import ElasticityConfig  # noqa: E402

# Autotuning block lives with its subsystem too (ISSUE 7: the
# ledger-driven planner's search-space knobs); re-exported so
# DeepSpeedConfig.autotuning parses it and the generated config doc
# includes it.
from ..autotuning.config import AutotuningConfig  # noqa: E402


class DeepSpeedConfig(DeepSpeedConfigModel):
    train_batch_size: Optional[int] = None
    train_micro_batch_size_per_gpu: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None
    steps_per_print: int = 10
    gradient_clipping: float = GRADIENT_CLIPPING_DEFAULT
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    dump_state: bool = False
    seed: int = 1234

    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None
    fp16: FP16Config = Field(default_factory=FP16Config)
    bf16: BF16Config = Field(default_factory=BF16Config)
    zero_optimization: ZeroConfig = Field(default_factory=ZeroConfig)
    activation_checkpointing: ActivationCheckpointingConfig = Field(
        default_factory=ActivationCheckpointingConfig)
    mesh: MeshConfig = Field(default_factory=MeshConfig)
    sequence_parallel: SequenceParallelConfig = Field(
        default_factory=SequenceParallelConfig)
    comms_logger: CommsLoggerConfig = Field(default_factory=CommsLoggerConfig)
    telemetry: TelemetryConfig = Field(default_factory=TelemetryConfig)
    sentinels: SentinelsConfig = Field(default_factory=SentinelsConfig)
    meshsan: MeshsanConfig = Field(default_factory=MeshsanConfig)
    numsan: NumsanConfig = Field(default_factory=NumsanConfig)
    moe: MoEConfig = Field(default_factory=MoEConfig)
    flops_profiler: FlopsProfilerConfig = Field(default_factory=FlopsProfilerConfig)
    tensorboard: TensorBoardConfig = Field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)
    comet: CometConfig = Field(default_factory=CometConfig)
    pipeline: PipelineConfig = Field(default_factory=PipelineConfig)
    data_efficiency: DataEfficiencyConfig = Field(default_factory=DataEfficiencyConfig)
    curriculum_learning: CurriculumLearningConfig = Field(
        default_factory=CurriculumLearningConfig)
    compression_training: dict[str, Any] = Field(default_factory=dict)
    aio: AIOConfig = Field(default_factory=AIOConfig)
    checkpoint: CheckpointConfig = Field(default_factory=CheckpointConfig)
    elasticity: ElasticityConfig = Field(default_factory=ElasticityConfig)
    hybrid_engine: HybridEngineConfig = Field(
        default_factory=HybridEngineConfig)
    autotuning: AutotuningConfig = Field(default_factory=AutotuningConfig)

    @classmethod
    def from_any(cls, config: "str | dict | DeepSpeedConfig | None") -> "DeepSpeedConfig":
        if config is None:
            return cls()
        if isinstance(config, DeepSpeedConfig):
            return config
        if isinstance(config, str):
            with open(config) as f:
                config = json.load(f)
        return cls(**config)

    # -- batch-size arithmetic (reference: runtime/config.py:893-947) -----
    def resolve_batch_sizes(self, data_parallel_size: int) -> tuple[int, int, int]:
        """Returns (train_batch, micro_batch_per_chip, grad_accum)."""
        tb, mb, ga = (self.train_batch_size,
                      self.train_micro_batch_size_per_gpu,
                      self.gradient_accumulation_steps)
        dp = data_parallel_size
        have = lambda v: v is not None  # noqa: E731 — 0 must NOT read as unset
        if have(tb) and have(mb) and have(ga):
            pass
        elif have(tb) and have(mb):
            ga = tb // (mb * dp)
        elif have(tb) and have(ga):
            mb = tb // (ga * dp)
        elif have(mb) and have(ga):
            tb = mb * ga * dp
        elif have(tb):
            ga = 1
            mb = tb // dp
        elif have(mb):
            ga = 1
            tb = mb * dp
        else:
            tb, mb, ga = dp, 1, 1
        if tb != mb * ga * dp:
            raise ValueError(
                f"Check batch related parameters. train_batch_size is not equal "
                f"to micro_batch_per_gpu * gradient_acc_step * world_size "
                f"{tb} != {mb} * {ga} * {dp}")
        if min(tb, mb, ga) <= 0:
            raise ValueError(
                f"Batch sizes must be positive: train={tb} micro={mb} accum={ga} dp={dp}")
        self.train_batch_size = tb
        self.train_micro_batch_size_per_gpu = mb
        self.gradient_accumulation_steps = ga
        return tb, mb, ga

    @property
    def compute_dtype(self):
        import jax.numpy as jnp
        if self.fp16.enabled:
            return jnp.float16
        if self.bf16.enabled:
            return jnp.bfloat16
        return jnp.float32
