"""Autotuning config (reference: deepspeed/autotuning/config.py
DeepSpeedAutotuningConfig + constants.py), extended with the
ledger-driven planner's search-space knobs (ISSUE 7). The block is
parsed by ``DeepSpeedConfig.autotuning`` and consumed by
:class:`~.planner.Planner` / :class:`~.autotuner.Autotuner`."""

from __future__ import annotations

from typing import Any, Optional

from pydantic import Field

from ..runtime.config_utils import DeepSpeedConfigModel

# metrics (reference: constants.py AUTOTUNING_METRIC_*)
METRIC_THROUGHPUT = "throughput"
METRIC_LATENCY = "latency"
METRIC_FLOPS = "flops"

TUNER_GRIDSEARCH = "gridsearch"
TUNER_RANDOM = "random"
TUNER_MODELBASED = "model_based"


class AutotuningConfig(DeepSpeedConfigModel):
    """Search + trial-measurement knobs. The reference fields
    (metric/tuner/micro-batch bounds/zero_stages) drive both the legacy
    measured-trial :class:`Autotuner` and the planner's grid; the
    planner-specific fields below them widen the space to mesh shape,
    remat policy, optimizer-offload ratio, and the overlap ratio the
    cost model assumes (see docs/autotuning.md)."""

    enabled: bool = False
    fast: bool = True
    metric: str = METRIC_THROUGHPUT
    start_step: int = 1          # steps to skip before measuring (warmup)
    end_step: int = 4            # measured steps per trial
    tuner_type: str = TUNER_GRIDSEARCH
    tuner_early_stopping: int = 5
    tuner_num_trials: int = 50
    max_train_batch_size: Optional[int] = None
    min_train_batch_size: int = 1
    max_train_micro_batch_size_per_gpu: Optional[int] = None
    min_train_micro_batch_size_per_gpu: int = 1
    num_tuning_micro_batch_sizes: int = 3
    zero_stages: Optional[list[int]] = None  # None = try all feasible
    overwrite: bool = True
    results_dir: str = "autotuning_results"
    exps_dir: str = "autotuning_exps"
    arg_mappings: dict[str, Any] = Field(default_factory=dict)

    # --- planner search space (ISSUE 7) ------------------------------
    # mesh axes enumerated over the devices the base config leaves
    # free; every ordered factorization is a candidate. ["fsdp"] keeps
    # the classic ZeRO-style search; add "tp"/"sp" for models with
    # partition rules.
    mesh_axes: list[str] = Field(default_factory=lambda: ["fsdp"])
    # jax.checkpoint policy names to try ("none" disables remat); the
    # engine plumbs the winner into the model via
    # activation_checkpointing.policy
    remat_policies: list[str] = Field(
        default_factory=lambda: ["nothing_saveable"])
    # optimizer-state offload ratios (0 = all on device; >0 moves that
    # fraction to host via zero_optimization.offload_optimizer)
    offload_ratios: list[float] = Field(default_factory=lambda: [0.0])
    # overlap ratios the cost model assumes for collective hiding
    # (0.71 is the domino chunked-overlap ratio of a CPU run before the
    # chip: a proxy with no chip measurement behind it, ROADMAP.md queue
    # 3 item 5); extra
    # values re-score the same trial config under different overlap
    # assumptions, they do not change the emitted config
    overlap_ratios: list[float] = Field(default_factory=lambda: [0.71])
    # qwZ/qgZ wire formats to grid over for the sharded-DP collectives
    # (ISSUE 8): "fp32" = XLA's implicit full-precision wire,
    # "int8"/"fp8" = the ZeRO++ quantized protocol. Quantized entries
    # only pair with ZeRO stage >= 2 (the wire is a shard feature).
    wire_dtypes: list[str] = Field(default_factory=lambda: ["fp32"])
    # MoE routing grid (ISSUE 16), used only when the tuned model has
    # num_experts > 0: capacity factors to try (0.0 = keep the model
    # config's value) and dispatch all-to-all wire formats for the
    # ep-sharded token exchange (moe.wire_dtype — independent of the
    # ZeRO wire above). Candidates are costed by the same per-axis
    # collective-bytes ledger as every other grid point; add "ep" to
    # mesh_axes to search expert-parallel degree too (ep points that
    # don't divide num_experts are skipped).
    moe_capacity_factors: list[float] = Field(
        default_factory=lambda: [0.0])
    moe_wire_dtypes: list[str] = Field(default_factory=lambda: ["fp32"])
    # score quantized-wire variants analytically from the fp32
    # sibling's compiled facts (cost_model.quantized_wire_facts)
    # instead of compiling each variant config — one engine build per
    # mesh/batch/stage point instead of one per wire entry; turn off
    # for compiler-truth facts on the quantized configs themselves
    analytic_wire: bool = True
    # always add the base config itself as a grid point so a measured
    # plan can never choose something worse than the hand-tuned start
    include_base: bool = True
    # memory-model fragmentation safety factor for headroom pruning
    memory_safety_factor: float = 1.1
    # measured steps per calibration point (the short run that fits
    # effective FLOPs/s + per-step overhead)
    calibration_steps: int = 3
    # timing windows per measurement; the BEST (min seconds/step)
    # window is kept, which shields short CPU windows from scheduler
    # jitter
    measure_windows: int = 2
    # run the calibration measurement when no explicit Calibration is
    # passed (False falls back to the accelerator peak-FLOPs table)
    calibrate: bool = True
    # measure the top-K AOT-ranked candidates with hermetic in-process
    # trials (0 = prediction-only plan)
    measure_top_k: int = 0
    # write the plan artifact here ("" = don't write)
    plan_path: str = ""

    # --- serving planner search space (ISSUE 19) ---------------------
    # grids for the ServingPlanner's ServingCandidate product: fused
    # decode K, chain depth (max_inflight_dispatches), ring vs plain
    # chain admission, speculative draft lengths (0 = off), KV pool
    # dtype and block budget (0 = keep the base pool), admission bound
    # (shed_queue_depth, 0 = unbounded), replica count, and the
    # prefill/decode disaggregated split. The base engine/serving
    # config is always a grid point (include_base above), so a serving
    # plan can never rank below the hand-tuned start under its own
    # model.
    serving_k_steps: list[int] = Field(default_factory=lambda: [4, 8])
    serving_chain_depths: list[int] = Field(
        default_factory=lambda: [1, 2, 4])
    serving_ring_modes: list[bool] = Field(
        default_factory=lambda: [False, True])
    serving_draft_lens: list[int] = Field(
        default_factory=lambda: [0, 3])
    serving_kv_dtypes: list[str] = Field(
        default_factory=lambda: ["fp16"])
    serving_kv_blocks: list[int] = Field(default_factory=lambda: [0])
    serving_shed_depths: list[int] = Field(
        default_factory=lambda: [0, 16])
    serving_replicas: list[int] = Field(default_factory=lambda: [1])
    serving_disagg: list[bool] = Field(default_factory=lambda: [False])
    # write the serving plan artifact here ("" = don't write)
    serving_plan_path: str = ""
