"""Serving front-end configuration (ISSUE 6; the deepspeed_tpu
analogue of DeepSpeed-MII's serving deployment config)."""

from __future__ import annotations

from typing import Optional

from pydantic import Field

from ..runtime.config_utils import DeepSpeedConfigModel


class ControllerConfig(DeepSpeedConfigModel):
    """Online serving feedback controller (ISSUE 19,
    ``deepspeed_tpu/serving/controller.py``): a worker-thread state
    machine stepped at ``interval_s`` cadence from the server's beat
    that reads SLO burn rates (``telemetry/timeseries.py``) and
    reqtrace component p99s, and adapts three knobs the offline plan
    cannot set per-minute — the admission bound (shed depth), the
    dispatch-chain depth, and the speculative draft length. Policy:
    queue pressure throttles admission first (fast-fail beats silent
    aging: 11.2 s of queue wait in an open-loop CPU run before the
    chip); sustained ITL
    saturation then steps chain depth down, then drafts off (deep
    chains and long drafts win at low load and kill ITL at
    saturation). Recovery relaxes in reverse order and only after
    ``step_up_after`` consecutive healthy intervals (hysteresis — no
    flapping on jittered load). Every decision bumps
    ``ds_serving_controller_actions_total``. See docs/serving.md."""
    enabled: bool = False
    # controller decision cadence (seconds between update() steps)
    interval_s: float = Field(1.0, gt=0.0)
    # SLO burn-rate trip/clear thresholds (breaches per request over
    # the shortest telemetry burn window; 1.0 = every request burning).
    # Trip above burn_high; an interval only counts as healthy below
    # burn_low (the gap is the hysteresis band).
    burn_high: float = Field(0.1, ge=0.0)
    burn_low: float = Field(0.02, ge=0.0)
    # queue-wait p99 above this fraction of the TTFT SLO reads as
    # admission pressure (throttle the shed depth)
    queue_wait_frac: float = Field(0.5, gt=0.0)
    # ITL p99 above slo_itl_ms * this ratio reads as decode saturation
    # (step chain depth down, then drafts off)
    saturation_ratio: float = Field(1.5, gt=0.0)
    # consecutive healthy intervals required before relaxing one step
    step_up_after: int = Field(5, ge=1)
    # shed-depth bounds the throttle moves within; min_shed_depth also
    # arms shedding when ServingConfig.shed_queue_depth is 0
    min_shed_depth: int = Field(4, ge=1)
    max_shed_depth: int = Field(256, ge=1)
    # floors for the step-downs (chain depth never below this; draft
    # toggle is {0, configured})
    min_chain_depth: int = Field(1, ge=1)
    min_draft_len: int = Field(0, ge=0)


class ServingConfig(DeepSpeedConfigModel):
    """Async continuous-batching server over ``InferenceEngineV2``
    (``deepspeed_tpu.serving.AsyncInferenceServer``). Engine-level
    scheduling knobs — fused K, dispatch-chain depth
    (``max_inflight_dispatches``), in-graph admission
    (``fused_admission``), KV pool sizing, prefix caching — live on
    ``RaggedInferenceEngineConfig``; this block configures the request
    front end sitting above it. See docs/serving.md."""

    # per-request default when submit() does not specify one
    default_max_new_tokens: int = Field(128, ge=1)
    # default priority tier for submit(); LOWER values run first.
    # Tiers are relative — any ints work (0 = interactive, 1 = default,
    # 2 = batch is the documented convention).
    default_priority: int = 1
    # upper bound on requests open at once (queued + running);
    # submit() past it raises. 0 = unbounded.
    max_queue: int = Field(0, ge=0)
    # admission bound (ISSUE 19): a submit() arriving with this many
    # requests already open is SHED — it fails fast with a
    # RequestFailed("... shed ...") instead of aging in the mailbox
    # (a CPU run before the chip: unbounded admission put 11.2 s of
    # queue_wait in an 11.5 s TTFT p99). Shed requests are counted
    # (ds_serving_shed_total, reqtrace outcome=shed) — never silently
    # dropped. 0 = off (existing behavior, byte-identical); the
    # controller tightens/relaxes the live bound at runtime.
    shed_queue_depth: int = Field(0, ge=0)
    # preemption: a higher-priority prompt that cannot be admitted may
    # PARK strictly-lower-priority running requests — KV blocks swap
    # out (prefix-cached full blocks stay warm in the LRU), the token
    # history is retained host-side, and the victim resumes later
    # position-exactly.
    preemption: bool = True
    # fused decode steps per dispatch for the serving loop; None =
    # the engine config's fused_decode_steps
    k_steps: Optional[int] = None
    # sampling overrides for the whole server; None = engine defaults
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    # base PRNG seed for stochastic sampling (position-keyed, so
    # restarts/preemptions resume the same stream)
    seed: int = 0
    # replica label (ISSUE 13): stamped on every request trace this
    # server admits so the access log / bench report name the serving
    # replica. The router assigns replica0..N-1 when left empty.
    replica: str = ""
    # worker-thread sleep while idle or waiting on admission headroom
    idle_poll_s: float = Field(0.002, gt=0.0)
    # --- serving SLO targets (ISSUE 10) ------------------------------
    # with telemetry's request tracing active, every completed request
    # whose TTFT (submit -> first token) exceeds this target bumps
    # ds_serving_slo_ttft_breaches_total (SLO burn). 0 = no target.
    slo_ttft_ms: float = Field(0.0, ge=0.0)
    # same for the request's MEAN inter-token latency ->
    # ds_serving_slo_itl_breaches_total. 0 = no target.
    slo_itl_ms: float = Field(0.0, ge=0.0)
    # online feedback controller (ISSUE 19); off by default
    controller: ControllerConfig = Field(default_factory=ControllerConfig)


class DisaggregationConfig(DeepSpeedConfigModel):
    """Prefill/decode disaggregation (ISSUE 13): with a
    :class:`~deepspeed_tpu.serving.PrefillEngine` attached to the
    router, qualifying prompts run chunked prefill on the dedicated
    prefill engine/mesh and migrate to a decode replica as a
    serialized KV block set (``export_request``/``import_request``) —
    long-prompt admission stops stealing decode ticks. Quantized KV
    blocks travel in their storage format (no dequantize), and greedy
    continuation on the decode side is bit-identical to a co-located
    run."""
    enabled: bool = False
    # prompts with at least this many tokens take the disaggregated
    # path; shorter prompts prefill co-located on their decode replica
    # (a short prompt's hand-off costs more than its prefill steals).
    # 0 = every prompt migrates.
    prefill_threshold_tokens: int = Field(0, ge=0)


class HealthConfig(DeepSpeedConfigModel):
    """Replica health gating for the router (ISSUE 17,
    ``deepspeed_tpu/telemetry/health.py``): serving-loop heartbeats
    feed a phi-accrual failure detector; placement skips ``suspect`` /
    ``dead`` replicas (``health_skips`` router counter) and sends
    ``degraded`` replicas to the existing drain path. Only consulted
    when telemetry is active (the detector lives in the telemetry
    package; with telemetry off this block is inert and nothing is
    imported). See docs/observability.md "Fleet health & burn
    rates"."""
    enabled: bool = True
    # phi thresholds: suspicion is log10-scaled silence relative to the
    # replica's own heartbeat cadence. phi >= phi_suspect excludes the
    # replica from placement; phi >= phi_dead marks it dead (terminal
    # under silence; only a resumed heartbeat revives it).
    phi_suspect: float = Field(4.0, gt=0.0)
    phi_dead: float = Field(10.0, gt=0.0)
    # inter-heartbeat intervals kept per replica (the detector's
    # empirical cadence window)
    heartbeat_window: int = Field(64, ge=2)
    # intervals required before phi reports nonzero (cold detector
    # never suspects)
    min_heartbeats: int = Field(3, ge=1)
    # hysteresis: a suspect replica returns to service only once phi
    # falls below phi_suspect * recovery_ratio (not merely below the
    # trip point), so jittered heartbeats cannot flap the state
    recovery_ratio: float = Field(0.5, gt=0.0, le=1.0)
    # composite-score floor below which a live replica counts as
    # degraded (drains instead of taking new work)
    degraded_score: float = Field(0.35, ge=0.0, le=1.0)
    # floor on the detector's empirical mean heartbeat interval: a
    # burst of fast beats from a busy loop must not calibrate the
    # detector so tight that one long engine step reads as death
    min_interval_s: float = Field(0.05, gt=0.0)


class RouterConfig(DeepSpeedConfigModel):
    """Prefix-affinity multi-replica router
    (``deepspeed_tpu.serving.InferenceRouter``) fronting N decode
    ``AsyncInferenceServer`` replicas (ISSUE 13): requests place onto
    the replica whose prefix cache already holds the longest
    hash-chained match for the prompt (same-system-prompt traffic
    lands where the blocks are warm), with least-loaded fallback,
    per-replica admission backpressure, and drain-and-reroute when a
    replica's pool is exhausted. See docs/serving.md."""
    # a cached-prefix match shorter than this many full blocks does
    # not steer placement (least-loaded wins instead)
    min_affinity_blocks: int = Field(1, ge=1)
    # per-replica admission backpressure: a replica with this many
    # open requests is skipped at placement. 0 = only the replica's
    # own max_queue applies.
    max_open_per_replica: int = Field(0, ge=0)
    # drain watermark: a replica whose schedulable KV headroom falls
    # below this many blocks stops receiving NEW work (it drains its
    # residents) unless every replica is below it. 0 = disabled.
    drain_free_block_watermark: int = Field(0, ge=0)
    # a request that fails on its replica (pool exhausted, replica
    # died) is transparently resubmitted — prompt + tokens already
    # streamed, same uid, so greedy and position-keyed stochastic
    # streams continue exactly — to the next-best replica this many
    # times before the failure surfaces to the client
    reroute_retries: int = Field(2, ge=0)
    # asyncio backoff while every replica is backpressured
    retry_backoff_s: float = Field(0.005, gt=0.0)
    # prefill/decode disaggregation (requires a PrefillEngine on the
    # router)
    disaggregation: DisaggregationConfig = Field(
        default_factory=DisaggregationConfig)
    # replica health gating (ISSUE 17; effective only with telemetry
    # active)
    health: HealthConfig = Field(default_factory=HealthConfig)
