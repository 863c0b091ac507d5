"""FastGen-style inference engine (reference: inference/v2/engine_v2.py
InferenceEngineV2:30 — put(batch_uids, batch_tokens):107 runs one forward
over a ragged batch of mixed prefill/decode sequences against the blocked
KV cache; query:158/can_schedule:184 gate admission; flush:242 frees a
sequence's KV blocks. DeepSpeed-MII drives put() in a loop = continuous
batching with Dynamic SplitFuse prompt chunking).

TPU translation: ragged batches become bucketed batches (XLA needs static
shapes — batch and chunk sizes round up to powers of two, one compiled
program per bucket). Prefill chunks and the decode batch run through
paged_forward (paged.py) against the block pool; page tables/sequence
state stay host-side (ragged.py). The pool arrays are donated through the
compiled step so KV writes are in-place.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Literal, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import Field, model_validator

from ...runtime.config_utils import DeepSpeedConfigModel
from ...utils.logging import log_dist
# telemetry guard: sys.modules probe, NOT an import — a disabled
# serving loop allocates nothing and pays one dict lookup per
# *dispatch* (never per token)
from ...utils.telemetry_probe import (NULL_CM as _NULLCM,
                                      active_telemetry as _telemetry)
from ..config import DeepSpeedInferenceConfig
from .paged import (fused_decode_loop, fused_serve_loop,
                    fused_spec_decode_loop, fused_spec_serve_loop,
                    paged_forward)
from .ragged import (KVExportState, PrefixCache, DSStateManager,
                     SequenceDescriptor, kv_block_bytes,
                     quantized_block_budget)

PyTree = Any

# serving_metrics() schema: raw counters kept in serving_stats (reset
# zeroes exactly these); the prefix-cache counters ride alongside via
# ragged.PREFIX_STAT_KEYS, and derived ratio/occupancy gauges are
# appended at read time. telemetry.bridges consumes the same names. The spec_* counters (ISSUE 9) stay zero with speculative
# decoding off: spec_proposed_tokens/spec_accepted_tokens are the
# acceptance-rate numerator/denominator, spec_hit_slots counts
# (row, tick) slots where the prompt-lookup drafter fired at all.
# fused_live_slots counts scheduled (row, step) slots whose row was
# still ACTIVE — the occupancy numerator; spec-off it equals
# fused_slot_tokens (one token per live slot), spec-on the device
# loops report it (tokens per live slot is then 1..1+draft_len).
SERVING_COUNTER_KEYS = (
    "host_dispatches", "fused_dispatches", "fused_steps", "fused_slots",
    "fused_slot_tokens", "fused_live_slots", "decoded_tokens",
    "spec_proposed_tokens", "spec_accepted_tokens", "spec_hit_slots")


class _LatencyProbe:
    """Serving-latency telemetry for one generation drive: TTFT and
    inter-token-latency histograms plus the admission-queue-depth gauge.
    Constructed only when telemetry is active; all call sites are
    guarded, so the disabled path carries none of this."""

    __slots__ = ("_ttft", "_itl", "_queue", "_admit_t", "_last_t")

    def __init__(self, reg):
        self._ttft = reg.histogram(
            "ds_serving_ttft_seconds",
            "time from admission to a sequence's first generated token")
        self._itl = reg.histogram(
            "ds_serving_itl_seconds",
            "inter-token latency (observed once per generated token; "
            "tokens landing in one fused drain share the drain "
            "interval evenly)")
        self._queue = reg.gauge(
            "ds_serving_queue_depth",
            "prompts still waiting for admission to the decode batch")
        self._admit_t: dict[int, float] = {}
        self._last_t: dict[int, float] = {}

    def admitted(self, uids, waiting: int) -> None:
        now = time.perf_counter()
        for u in uids:
            self._admit_t[u] = now
        self._queue.set(waiting, engine="v2")

    def tokens(self, uid: int, n: int, first: bool = False) -> None:
        """``n`` new tokens landed for ``uid`` (``first``: the batch
        starts with the sequence's first generated token)."""
        now = time.perf_counter()
        last = self._last_t.get(uid)
        if first:
            self._ttft.observe(now - self._admit_t.pop(uid, now))
            n -= 1
            if last is None:
                last = now
        if n > 0 and last is not None:
            per = (now - last) / n
            for _ in range(n):
                self._itl.observe(per)
        self._last_t[uid] = now

    def finished(self, uid: int) -> None:
        """Drop per-uid state. A probe used to die with one
        generate call; the serving loop keeps one alive for the
        server's lifetime, so finished/preempted uids must not
        accumulate."""
        self._admit_t.pop(uid, None)
        self._last_t.pop(uid, None)


def _bucket(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _batch_bucket(n: int) -> int:
    """Decode-batch bucket: powers of two up to 8, then multiples of 8.

    Power-of-two-only batch buckets waste up to ~2x on everything
    (weights reads excepted) — e.g. 24 live sequences padded to 32 rows
    cost +33% per tick. Sublane granularity on TPU is 8, so multiples
    of 8 bucket tightly with a bounded executable count (r4 serving
    profiling: this alone closed most of the v2-vs-v1 decode gap at
    moderate batch)."""
    return _bucket(n) if n <= 8 else -(-n // 8) * 8


class PrefixCacheConfig(DeepSpeedConfigModel):
    """Automatic prefix caching (ISSUE 4): full KV blocks are indexed by
    a hash chain over their token content and SHARED across requests —
    a new prompt whose leading blocks match a cached chain skips their
    prefill entirely (refcount bump instead of compute). Off by
    default; the disabled path is byte-identical to an engine without
    the feature."""
    enabled: bool = False
    # a match shorter than this many full blocks is ignored (tiny
    # matches save little prefill but fragment the pool's LRU)
    min_match_blocks: int = 1
    # cap on indexed blocks; 0 = bounded only by the pool. Exceeding it
    # evicts the least-recently-used unreferenced cached block.
    max_cached_blocks: int = 0


class SpeculativeConfig(DeepSpeedConfigModel):
    """Self-drafting speculative decoding in the fused serving path
    (ISSUE 9): a device-side prompt-lookup (n-gram) drafter proposes up
    to ``draft_len`` tokens per row per tick from the row's own recent
    token history, and the fused loop verifies them in ONE forward over
    ``1 + draft_len`` positions — committing 1..1+draft_len tokens per
    tick. No draft model, no extra weights; greedy output is
    bit-identical to spec-off, stochastic output is bit-identical for
    the same seed (targets are position-key sampled, drafts only decide
    how many land per forward). Off by default; the disabled path
    builds none of the spec executables."""
    enabled: bool = False
    # draft tokens proposed (and verified) per decode tick; the verify
    # forward runs over 1 + draft_len positions
    draft_len: int = Field(3, ge=1)
    # shortest trailing n-gram that may match earlier history; longer
    # = fewer but better-targeted drafts
    min_ngram: int = Field(2, ge=1)
    # device-side recent-token window the drafter searches (per row,
    # int32) — seeded at admission from the sequence's committed
    # history (prefix-cache-shared prompt tokens included) and
    # maintained in-graph
    history_window: int = Field(64, ge=8)

    @model_validator(mode="after")
    def _window_covers_match(self):
        need = self.min_ngram + self.draft_len + 1
        if self.history_window < need:
            raise ValueError(
                f"speculative.history_window ({self.history_window}) "
                f"must be >= min_ngram + draft_len + 1 ({need}): the "
                "window must hold one n-gram, its full continuation "
                "and the trailing n-gram it matches against")
        return self


class KVCacheConfig(DeepSpeedConfigModel):
    """Quantized KV cache (ISSUE 12): the paged KV pools store int8 or
    fp8-e4m3 codes with symmetric per-vector f32 scales riding the
    block tables in their own scale slabs (``pools["ks"]/["vs"]``, one
    scale per written (token, kv-head) vector — or per token with
    ``granularity="token"``). Dequantization is fused into the
    consumers — in-register inside the Pallas paged-decode fold, a
    fused multiply on the jnp reference path — so quantized blocks are
    read straight from HBM with no materialized fp16 copy, and
    quantize-on-write happens once in the same graph as the pool
    scatter. With ``grow_pool`` the allocator is sized in QUANTIZED
    bytes: the HBM budget of ``num_kv_blocks`` full-precision blocks
    yields 2-4x more quantized blocks, i.e. 2-4x more resident
    requests per chip. Off by default; the disabled path is
    byte-identical to an engine without the feature (no scale slabs,
    same executables). Accuracy model, dtype-selection guidance and
    the metric guide live in docs/serving.md."""
    enabled: bool = False
    # storage format of the KV payload pools: "fp16" keeps the
    # engine's compute dtype (quantization off even when enabled —
    # the explicit no-op rung of the dtype ladder); int8 = symmetric
    # [-127, 127] codes; fp8 = native float8_e4m3fn
    dtype: Literal["fp16", "int8", "fp8"] = "int8"
    # scale granularity: "head" = one f32 scale per written
    # (token, kv-head) vector of head_dim elements (tightest, the
    # default); "token" = one scale across all kv heads of a token
    # (1/num_kv_heads of the scale memory, slightly coarser). Both are
    # write-once — no read-modify-requantize of earlier tokens, which
    # is what keeps cached quantized blocks bit-stable under sharing.
    granularity: Literal["head", "token"] = "head"
    # size the pool in quantized bytes: grow num_kv_blocks to fill the
    # HBM budget the configured full-precision pool would have used.
    # False = keep the configured block count (pool bytes shrink
    # instead — the parity/testing mode).
    grow_pool: bool = True


class GraftsanConfig(DeepSpeedConfigModel):
    """Runtime concurrency/KV-accounting sanitizers (ISSUE 11,
    ``analysis/blocksan.py`` — the runtime half of the graftsan
    GL050-GL053 static pass). ``blocksan`` journals every KV-block
    accounting mutation with call-site provenance and asserts refcount
    >= 0, no double-free, and pool conservation (free + referenced +
    LRU-cached == pool) at every flush/park quiesce point, naming
    leaked blocks' allocation sites on failure; ``thread_affinity``
    stamps the engine-owning thread (the async server re-stamps its
    worker at loop start) and raises on JAX dispatch from any other
    thread. Off by default — the disabled path is one attribute load
    per accounting call and nothing is imported. Env ``DS_GRAFTSAN=1``
    force-enables both (the conftest/CI opt-in knob)."""
    enabled: bool = False
    blocksan: bool = True
    thread_affinity: bool = True
    # "raise" fails fast (tests/bench); "warn" logs, counts, and keeps
    # serving (violations still reach ds_blocksan_violations_total)
    mode: Literal["raise", "warn"] = "raise"
    # bounded journal of recent accounting ops kept for leak reports
    # and hang-dump forensics
    journal_size: int = Field(512, ge=16)


class InferenceMeshsanConfig(DeepSpeedConfigModel):
    """Runtime mesh-traffic sanitizer for the serving dispatch families
    (ISSUE 15, ``analysis/meshsan.py`` — the runtime half of the
    shardlint GL060-GL063 static pass; see the training-side
    ``meshsan`` block in runtime/config.py for the full model). The v2
    contract is strict: a tp-sharded forward moves bytes on ``tp``
    only, and any substantial all-to-all/collective-permute in a
    serving executable is the GSPMD silent-reshard signature
    (kilobyte-scale partitioner shuffles are tolerated). Checks ride
    the telemetry executable ledger's HLO walk, once per new
    executable. Off by default; env ``DS_MESHSAN=1`` force-enables."""
    enabled: bool = False
    mode: Literal["raise", "warn"] = "raise"
    # override the auto-seeded contract axes (None = {tp} when tp > 1)
    axes: Optional[list[str]] = None


class InferenceNumsanConfig(DeepSpeedConfigModel):
    """numsan numerics sanitizer, serving side (ISSUE 18,
    ``analysis/numsan.py`` — the runtime half of the numlint
    GL070-GL073 static pass; the training-side block is ``numsan`` in
    runtime/config.py). Probes are opt-in and cadence-gated:

    - every ``probe_interval``-th per-tick dispatch checks the batch
      logits for non-finite values and for ``|logit| > logits_limit``
      (the pre-NaN saturation signature of a mis-scaled KV cache) — a
      small fused reduction plus one host sync on the probe cadence;
    - with a quantized KV cache, the same cadence audits the scale
      slabs (``pools["ks"]/["vs"]``) for non-finite scales
      (``kv_scale_probe``);
    - every quantize site armed at trace time (the KV write,
      ``ops/pallas/quantization.saturation_probe``) reports its
      saturating-code fraction to ``ds_numsan_saturation_ratio{site}``;
      a fraction above ``saturation_ceiling`` is a finding, raised at
      the next dispatch boundary (``drain``).

    Off by default — nothing imported, executables byte-identical. Env
    ``DS_NUMSAN=1`` force-enables (the conftest/CI opt-in knob). Rule
    catalog + probe cost model: docs/static-analysis.md,
    "Numerics"."""
    enabled: bool = False
    mode: Literal["raise", "warn"] = "raise"
    # |logit| beyond this is a "logits-range" finding
    logits_limit: float = Field(1e4, gt=0.0)
    # check logits / KV scales every N-th per-tick dispatch (each
    # check costs one host sync)
    probe_interval: int = Field(16, ge=1)
    # audit the quantized KV scale slabs on the probe cadence
    kv_scale_probe: bool = True
    # saturating-code fraction above this is a finding; the healthy
    # baseline is ~1/head_dim (each written vector's absmax lands
    # exactly on the clip boundary)
    saturation_ceiling: float = Field(0.05, ge=0.0, le=1.0)
    # arm the in-graph quantize-site probes (KV write) at trace time
    saturation_probe: bool = True


class RaggedInferenceEngineConfig(DeepSpeedInferenceConfig):
    """reference: inference/v2/config_v2.py RaggedInferenceEngineConfig
    (state_manager block/pool sizing knobs + the fused-decode loop)."""
    kv_block_size: int = 64
    num_kv_blocks: int = 256
    max_ragged_sequence_count: int = 32   # decode-batch bucket ceiling
    max_chunk_size: int = 256             # prefill chunk (SplitFuse budget)
    # K decode ticks fused into one on-device loop per host dispatch
    # (decode_fused/generate_fused): forward, sampling, KV writes and
    # EOS/budget termination all run in-graph, so decode throughput
    # rides device compute instead of host dispatch RTT. 0/1 disables
    # fusion (per-tick behavior).
    fused_decode_steps: int = 8
    # in-graph sampling defaults (per-call overrides win). temperature
    # 0.0 = greedy; top_k/top_p 0 = no filter.
    sampling_temperature: float = 0.0
    sampling_top_k: int = 0
    sampling_top_p: float = 0.0
    # sequences terminate in-graph when they sample this token
    eos_token_id: Optional[int] = None
    # dispatch-chain depth for the fused drivers (ISSUE 6): how many
    # fused decode dispatches may be in flight before the host drains
    # one. 2 = the PR 1 double buffering (default path byte-identical);
    # deeper chains amortize the host round trip further at the cost
    # of admission latency (a waiting prompt rides out the chain).
    max_inflight_dispatches: int = Field(2, ge=1)
    # device-resident multi-tick serving (ISSUE 6): pre-staged requests
    # (prefilled, blocks reserved) are swapped into finished rows'
    # slots INSIDE the compiled loop (activity-mask swap + staged
    # token/position/table operands), and sampled tokens accumulate in
    # a device-side ring the host reads ONCE per dispatch chain instead
    # of once per dispatch. Off by default — the disabled path is
    # byte-identical to the PR 1 fused driver.
    fused_admission: bool = False
    # runtime sentinels (ISSUE 3, analysis/sentinels.py): every fused
    # decode dispatch runs under a recompile watch (a previously-seen
    # (jit key, operand shapes) signature must hit the executable
    # cache) and jax.transfer_guard("disallow") (implicit host<->device
    # transfers raise; the explicit token drain stays legal). Off by
    # default — zero overhead, nothing imported.
    sentinels: bool = False
    sentinel_mode: str = "raise"          # or "warn"
    # quantized KV cache (ISSUE 12): int8/fp8 pools with per-vector
    # scales, dequant fused into the paged-decode consumers, allocator
    # sized in quantized bytes (see docs/serving.md)
    kv_cache: KVCacheConfig = Field(default_factory=KVCacheConfig)
    # automatic prefix caching: ref-counted KV block sharing with
    # hash-chained reuse across requests (see docs/serving.md)
    prefix_cache: PrefixCacheConfig = Field(
        default_factory=PrefixCacheConfig)
    # speculative decoding (ISSUE 9): prompt-lookup drafting + in-graph
    # K-token verify in the fused decode/serve loops (see
    # docs/serving.md)
    speculative: SpeculativeConfig = Field(
        default_factory=SpeculativeConfig)
    # graftsan runtime sanitizers (ISSUE 11): KV block-accounting
    # journal + conservation checks and the thread-affinity checker
    # (see docs/static-analysis.md, "Concurrency domains & sanitizers")
    graftsan: GraftsanConfig = Field(default_factory=GraftsanConfig)
    # meshsan mesh-traffic sanitizer (ISSUE 15): per-executable
    # collective traffic contracts over the ledger's HLO walk (see
    # docs/static-analysis.md, "SPMD correctness")
    meshsan: InferenceMeshsanConfig = Field(
        default_factory=InferenceMeshsanConfig)
    # numsan numerics sanitizer (ISSUE 18): logits-range / KV-scale
    # probes + quantize-site saturation attribution (see
    # docs/static-analysis.md, "Numerics")
    numsan: InferenceNumsanConfig = Field(
        default_factory=InferenceNumsanConfig)


class InferenceEngineV2:
    """reference: inference/v2/engine_v2.py:30"""

    def __init__(self, model, config: RaggedInferenceEngineConfig,
                 params: Optional[PyTree] = None):
        from ..engine import InferenceEngine
        # reuse v1 for param load/shard/dtype (policy+checkpoint layer)
        self._v1 = InferenceEngine(model, config, params=params)
        # take v1's per-engine module copy (serving flags bound, any
        # training-engine moe_dispatcher stripped), not the raw model
        self.model = getattr(self._v1, "module", model)
        self.params = self._v1.params
        self._config = config
        c = model.config
        self.dtype = config.jax_dtype

        bs = config.kv_block_size
        max_blocks_per_seq = -(-c.max_seq_len // bs)

        # quantized KV cache (ISSUE 12): pool dtype, scale layout and
        # the block budget are resolved BEFORE the state manager so the
        # allocator is sized in quantized bytes — the HBM budget of
        # num_kv_blocks full-precision blocks yields proportionally
        # more quantized blocks (grow_pool), i.e. more resident
        # requests at equal pool bytes.
        kvc = config.kv_cache
        self._kv_quant = bool(kvc.enabled and kvc.dtype != "fp16")
        self._kv_scale_heads = (1 if kvc.granularity == "token"
                                else c.num_kv_heads)
        full_bytes = kv_block_bytes(
            bs, c.num_kv_heads, c.head_dim,
            np.dtype(self.dtype).itemsize)
        if self._kv_quant:
            self._kv_block_bytes = kv_block_bytes(
                bs, c.num_kv_heads, c.head_dim, 1,
                scale_heads=self._kv_scale_heads)
            nb = (quantized_block_budget(config.num_kv_blocks,
                                         full_bytes,
                                         self._kv_block_bytes)
                  if kvc.grow_pool else config.num_kv_blocks)
        else:
            self._kv_block_bytes = full_bytes
            nb = config.num_kv_blocks
        self.num_kv_blocks = nb

        pc = config.prefix_cache
        self.state_manager = DSStateManager(
            block_size=bs, num_blocks=nb,
            max_blocks_per_seq=max_blocks_per_seq,
            prefix_cache=(PrefixCache(
                block_size=bs, min_match_blocks=pc.min_match_blocks,
                max_cached_blocks=pc.max_cached_blocks)
                if pc.enabled else None))
        # logits of sequences finished as a side effect of another
        # caller's drain loop, held for their owner's next tick()
        self._finished_stash: dict[int, jnp.ndarray] = {}
        pool_shape = (c.num_layers, nb, bs, c.num_kv_heads, c.head_dim)

        # TP serving (reference: model_implementations/sharding/): the
        # KV pools shard over the kv-heads dim of the v1 engine's tp
        # mesh; params are already tp-sharded by the v1 layer, so GSPMD
        # propagates head sharding through qkv/attention and inserts the
        # output-projection all-reduce.
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.mesh = self._v1.mesh
        tp = self._v1.topology.model_parallel_size
        if tp > 1 and c.num_kv_heads % tp != 0:
            from ...utils.logging import warning_once
            warning_once(
                f"inference v2: num_kv_heads {c.num_kv_heads} not "
                f"divisible by tp={tp}; KV pools stay replicated")
            pool_spec = P()
        elif tp > 1:
            pool_spec = P(None, None, None, "tp", None)
        else:
            pool_spec = P()
        self._pool_sharding = NamedSharding(self.mesh, pool_spec)
        if self._kv_quant:
            from ...ops.pallas.quantization import KV_STORE_DTYPES
            store = KV_STORE_DTYPES[kvc.dtype]
            scale_shape = pool_shape[:3] + (self._kv_scale_heads,)
            # scale slabs shard with their payload's kv-head axis when
            # per-head (and the pool is head-sharded); per-token scales
            # have no head axis to shard — replicated
            scale_spec = (P(None, None, None, "tp")
                          if pool_spec != P()
                          and self._kv_scale_heads > 1 else P())
            scale_sharding = NamedSharding(self.mesh, scale_spec)
            self._pool_shardings = {
                "k": self._pool_sharding, "v": self._pool_sharding,
                "ks": scale_sharding, "vs": scale_sharding}
            # zero-init scales dequantize untouched slots to exact 0.0
            # — the same dead-slot semantics as the fp16 pools, so the
            # kernel's sanitize_pools=False fast path stays valid
            self.pools = jax.device_put(
                {"k": jnp.zeros(pool_shape, store),
                 "v": jnp.zeros(pool_shape, store),
                 "ks": jnp.zeros(scale_shape, jnp.float32),
                 "vs": jnp.zeros(scale_shape, jnp.float32)},
                dict(self._pool_shardings))
        else:
            self._pool_shardings = {"k": self._pool_sharding,
                                    "v": self._pool_sharding}
            self.pools = jax.device_put(
                {"k": jnp.zeros(pool_shape, self.dtype),
                 "v": jnp.zeros(pool_shape, self.dtype)},
                dict(self._pool_shardings))
        # one jit; XLA caches one executable per bucket shape. tick() is
        # one dispatch per scheduler tick (logits_gather fused into the
        # step); for generation loops where per-dispatch latency matters
        # more than admission control, the v1/hybrid engines compile the
        # whole decode loop into a single program instead.
        # the blocked-flash kernel is an opaque custom call GSPMD cannot
        # partition: with tp>1 it would force pool gathers — use the jnp
        # paged path there (sharding-transparent); shard_map-wrapping the
        # kernel per tp shard is the follow-up
        self._step = jax.jit(
            functools.partial(paged_forward, self.model,
                              use_kernel=(tp <= 1)),
            donate_argnums=(1,),
            out_shardings=(None, dict(self._pool_shardings)))
        # fused-decode executables: one per (num_steps, sampling, eos)
        # combination; XLA adds a per-bucket-shape cache underneath
        self._fused_cache: dict = {}
        # sentinels (opt-in): lazily imported so a sentinel-off serving
        # process never pulls analysis/ or the telemetry package
        self._decode_sentinel = None
        self._hot_guard = None
        self._fused_sigs: set = set()
        # base PRNG key per seed, built once: PRNGKey(int) is an
        # implicit host->device upload, which must not ride every
        # fused dispatch (it would trip the transfer guard — and is
        # per-dispatch host work for a value that never changes)
        self._seed_keys: dict[int, jnp.ndarray] = {}
        if config.sentinels:
            from ...analysis.sentinels import (RecompileSentinel,
                                               hot_path_guard)
            self._decode_sentinel = RecompileSentinel(
                "fused_decode", mode=config.sentinel_mode, warmup_calls=0)
            self._hot_guard = hot_path_guard
        # graftsan runtime sanitizers (ISSUE 11): opt-in via the config
        # block or the DS_GRAFTSAN env knob; lazily imported so a
        # sanitizer-off process never loads analysis/blocksan
        self._blocksan = None
        self._affinity = None
        gs = config.graftsan
        if gs.enabled or os.environ.get("DS_GRAFTSAN", "") \
                not in ("", "0"):
            from ...analysis import blocksan as _bsan
            if gs.blocksan:
                self._blocksan = _bsan.BlockSanitizer(
                    self.num_kv_blocks, mode=gs.mode,
                    journal_size=gs.journal_size)
                if self._kv_quant:
                    # the scale pool partitions block-for-block with
                    # the KV pool; a scale slot outliving (or missing
                    # from) its block's lifecycle is a finding
                    self._blocksan.attach_scale_pool()
                self.state_manager.attach_sanitizer(self._blocksan)
                # registered process-wide so hang-watchdog dumps embed
                # the journal tail (telemetry/flightrec.dump_state)
                _bsan.set_blocksan(self._blocksan)
            if gs.thread_affinity:
                self._affinity = _bsan.ThreadAffinityChecker(mode=gs.mode)
        # meshsan (ISSUE 15): per-executable traffic contracts checked
        # at the dispatch-family registration choke point
        # (_device_truth_observe); opt-in, lazily imported, rides the
        # telemetry ledger's HLO walk
        self._meshsan = None
        ms = config.meshsan
        if ms.enabled or os.environ.get("DS_MESHSAN", "") \
                not in ("", "0"):
            from ...analysis import meshsan as _msan
            contract = _msan.seed_serving_contract(tp=tp)
            if ms.axes is not None:
                contract.axes = frozenset(ms.axes)
            self._meshsan = _msan.MeshSanitizer(mode=ms.mode)
            # the two ledger-observed dispatch families (prefill
            # registers under v2/dispatch — its span name is not a
            # ledger name)
            for fam in ("v2/dispatch", "v2/fused_dispatch"):
                self._meshsan.declare(fam, contract)
            _msan.set_meshsan(self._meshsan)
        # numsan (ISSUE 18): logits-range / KV-scale probes on the
        # dispatch path + trace-time-armed quantize-site saturation
        # attribution (the KV write probe in paged.py). Opt-in, lazily
        # imported; the off path traces byte-identical executables.
        self._numsan = None
        self._numsan_dispatches = 0
        self._logits_stats_fn = None
        ns = config.numsan
        self._numsan_kv_probe = bool(ns.kv_scale_probe)
        if ns.enabled or os.environ.get("DS_NUMSAN", "") \
                not in ("", "0"):
            from ...analysis import numsan as _nsan
            self._numsan = _nsan.NumericsSanitizer(
                mode=ns.mode,
                saturation_ceiling=ns.saturation_ceiling,
                logits_limit=ns.logits_limit,
                probe_interval=ns.probe_interval,
                saturation_probe=ns.saturation_probe)
            # registered process-wide: the quantize-site probes and
            # hang-watchdog dumps read it back without an engine ref
            _nsan.set_numsan(self._numsan)
        # serving counters behind serving_metrics(): host dispatches vs
        # decoded tokens measures how host-free the decode loop is.
        # Schema-driven (SERVING_COUNTER_KEYS) so reset/emission can
        # never drift from the key set consumers see.
        self.serving_stats = dict.fromkeys(SERVING_COUNTER_KEYS, 0)
        # SplitFuse budget, floored to a power of two (bucket shapes must
        # never exceed the configured compute budget)
        self._chunk = 1 << (max(1, config.max_chunk_size).bit_length() - 1)
        pool_mib = self.kv_pool_bytes() / 2**20
        log_dist(
            f"InferenceEngineV2: {nb} KV blocks x {bs} tokens "
            f"({pool_mib:.1f} MiB, kv dtype {self.kv_dtype})")

    # ------------------------------------------------------------------
    def _run(self, uids: list[int]) -> jnp.ndarray:
        """One bucketed forward over the pending tokens of `uids`.
        Returns last-token logits [len(uids), V]."""
        if self._affinity is not None:
            # runtime half of GL050: only the engine-owning thread may
            # reach a JAX dispatch (auto-binds on first use; the async
            # server re-stamps its worker at loop start)
            self._affinity.check("v2/_run")
        mgr = self.state_manager
        seqs = [mgr.seqs[u] for u in uids]
        max_pending = max(s.pending for s in seqs)
        s_bucket = _bucket(min(max_pending, self._chunk))
        b_bucket = _batch_bucket(len(seqs))

        tokens = np.zeros((b_bucket, s_bucket), np.int32)
        pos0 = np.zeros((b_bucket,), np.int32)
        true_len = np.zeros((b_bucket,), np.int32)
        tables = np.stack(
            [mgr.block_table(s) for s in seqs]
            + [mgr.block_table(seqs[0])] * (b_bucket - len(seqs)))
        for i, seq in enumerate(seqs):
            n = min(seq.pending, s_bucket)
            tokens[i, :n] = seq.tokens[seq.seen:seq.seen + n]
            pos0[i] = seq.seen
            true_len[i] = n
        # context bucketing (the reference buckets KV lengths the same
        # way): narrow the block table to the LIVE context's power-of-two
        # block count, so attention cost scales with actual sequence
        # lengths instead of max_blocks_per_seq — the paged kernel's
        # grid and the gather path's page reads both shrink with it.
        # Bounded recompiles: one executable per (batch, chunk, context)
        # bucket triple, each dimension log2-many.
        live_blocks = -(-int((pos0 + true_len).max()) // mgr.block_size)
        k_blocks = min(_bucket(max(live_blocks, 1)), tables.shape[1])
        tables = tables[:, :k_blocks]
        # padded rows must not write: true_len 0 drops their scatters.
        # logits come back already gathered at each row's last valid
        # token (logits_gather fused into the compiled step)
        self.serving_stats["host_dispatches"] += 1
        tel = _telemetry()
        dev_ops = (jnp.asarray(tokens), jnp.asarray(pos0),
                   jnp.asarray(tables), jnp.asarray(true_len))
        if tel is not None:
            # ISSUE 5 hooks BEFORE the dispatch: pools are donated
            # through the step, and first-sight ledger registration
            # must stay outside any sentinel watch
            self._device_truth_observe(tel, "v2/dispatch", self._step,
                                       dev_ops)
        # span measures the host-side dispatch (enqueue; the device work
        # itself lands in the XPlane via the TraceAnnotation mirror)
        with (tel.span("v2/dispatch",
                       dispatch_id=self.serving_stats["host_dispatches"],
                       rows=len(seqs), chunk=s_bucket)
              if tel is not None else _NULLCM):
            logits, self.pools = self._step(
                self.params, self.pools, *dev_ops)
        for i, seq in enumerate(seqs):
            seq.seen += int(true_len[i])
            # prefix cache: blocks this chunk completed are now fully in
            # the pool — index them for reuse (no-op when disabled)
            mgr.publish_full_blocks(seq)
        if self._numsan is not None:
            self._numsan_probe(logits[:len(seqs)])
        return logits[:len(seqs)]

    # ------------------------------------------------------------------
    # reference API
    def schedule(self, batch_uids: Sequence[int],
                 batch_tokens: Sequence[Sequence[int]],
                 do_checks: bool = True) -> None:
        """Admit new tokens into the sequence state (KV blocks reserved,
        no compute) — the scheduling half of the reference's put():107.
        Raises before any state mutation if the batch cannot fit."""
        uids = [int(u) for u in batch_uids]
        mgr = self.state_manager
        for u, toks in zip(uids, batch_tokens):
            if len(toks) == 0:
                raise ValueError(
                    f"sequence {u}: schedule()/put() needs at least one "
                    f"token (an empty list would never finish a tick)")
        # prefix-cache pre-pinning: matched blocks are ref-bumped BEFORE
        # any check or allocation, so (a) the admission math credits
        # exactly the blocks reuse will skip and (b) an earlier
        # sequence's allocation in this batch cannot evict a later
        # sequence's hit out from under it.
        pins: dict[int, list] = {}
        if mgr.cache is not None:
            for u, toks in zip(uids, batch_tokens):
                seq = mgr.seqs.get(u)
                if u not in pins and (seq is None
                                      or (not seq.tokens
                                          and not seq.blocks)):
                    m = mgr.prefix_match(toks)
                    if m:
                        mgr.pin_prefix(m)
                        pins[u] = m
        try:
            if do_checks:
                # cumulative admission over the whole batch, so a failure
                # raises before any state mutation
                need = 0
                for u, toks in zip(uids, batch_tokens):
                    seq = mgr.seqs.get(u)
                    seq_blocks = len(seq.blocks) if seq else 0
                    seq_need = mgr.blocks_needed(
                        seq or SequenceDescriptor(uid=u, tokens=[]),
                        len(toks))
                    if seq_blocks + seq_need > mgr.max_blocks_per_seq:
                        raise RuntimeError(
                            f"sequence {u} would exceed the max length "
                            f"({mgr.max_blocks_per_seq * mgr.block_size} "
                            f"tokens)")
                    need += seq_need - len(pins.get(u, ()))
                if need > mgr.available_blocks:
                    raise RuntimeError(
                        f"cannot schedule batch: needs {need} KV blocks, "
                        f"{mgr.available_blocks} allocatable — the pool "
                        "is exhausted (flush finished sequences)")
            for u, toks in zip(uids, batch_tokens):
                mgr.extend(u, list(map(int, toks)),
                           pinned=pins.pop(u, None))
                # re-admission invalidates any logits stashed when this
                # uid finished during another caller's drain: the stashed
                # value is from the old position and tick() must not
                # surface it while the uid has pending tokens again
                # (mirrors flush()). Popped only after extend() succeeds
                # — a failed admission (do_checks=False + exhausted pool)
                # must leave the stash intact for the original caller.
                self._finished_stash.pop(u, None)
        except BaseException:
            for m in pins.values():
                mgr.unpin_prefix(m)
            raise

    def tick(self) -> dict[int, jnp.ndarray]:
        """ONE scheduler tick (the compute half of the reference's
        put():107): a single bucketed forward over every sequence with
        pending tokens — prefill chunks (SplitFuse budget) and the decode
        batch ride the same pass. Returns {uid: last-token logits} for
        sequences whose pending tokens finished this tick (including any
        stashed by a concurrent put() that drained them as a side
        effect). Callers may schedule() new sequences between ticks —
        mid-prompt admission, which folding the loop into put() would
        forfeit."""
        mgr = self.state_manager
        out = dict(self._finished_stash)
        self._finished_stash.clear()
        run_uids = [u for u, s in mgr.seqs.items() if s.pending]
        run_uids = run_uids[:self._config.max_ragged_sequence_count]
        if run_uids:
            logits = self._run(run_uids)
            out.update({u: logits[i] for i, u in enumerate(run_uids)
                        if not mgr.seqs[u].pending})
        return out

    def put(self, batch_uids: Sequence[int],
            batch_tokens: Sequence[Sequence[int]],
            do_checks: bool = True) -> jnp.ndarray:
        """schedule() + tick()-until-drained for the given sequences;
        returns last-token logits [n, V] in uid order (the reference
        put():107 plus the caller loop DeepSpeed-MII wraps around it).
        Use schedule()/tick() directly for inter-tick admission."""
        uids = [int(u) for u in batch_uids]
        uid_set = set(uids)
        self.schedule(uids, batch_tokens, do_checks)
        mgr = self.state_manager
        final: dict[int, jnp.ndarray] = {}
        while any(mgr.seqs[u].pending for u in uids):
            for u, lg in self.tick().items():
                if u in uid_set:
                    final[u] = lg
                else:
                    # a sequence someone else schedule()d finished as a
                    # side effect of our drain: stash its logits for
                    # that caller's next tick() instead of dropping them
                    self._finished_stash[u] = lg
        return jnp.stack([final[u] for u in uids])

    def query(self, uid: int) -> tuple[int, int]:
        """(cached_tokens, allocated_blocks) for a sequence (reference:
        engine_v2.query:158)."""
        seq = self.state_manager.seqs.get(uid)
        if seq is None:
            return (0, 0)
        return (seq.seen, len(seq.blocks))

    def can_schedule(self, uid: int, n_tokens: int) -> bool:
        return self.state_manager.can_schedule(uid, n_tokens)

    @property
    def free_blocks(self) -> int:
        """Schedulable KV-block headroom. Matches the admission math:
        cached blocks with refcount zero count as free (the allocator
        evicts them on demand)."""
        return self.state_manager.available_blocks

    # ------------------------------------------------------------------
    # KV-pool byte truth (ISSUE 12): the numbers ds_kv_pool_bytes /
    # ds_kv_bytes_per_token export and the bench kvquant stage gates
    @property
    def kv_dtype(self) -> str:
        """Storage format of the KV payload pools ("fp16" family names
        the engine compute dtype when quantization is off)."""
        return (self._config.kv_cache.dtype if self._kv_quant
                else str(np.dtype(self.dtype)))

    def kv_pool_bytes(self) -> int:
        """Actual HBM bytes of the paged KV pools — payload slabs plus
        (when quantized) the per-vector scale slabs. Computed from the
        live arrays, so it is definitionally what the ledger's
        ``memory_analysis()`` sees as pool operand bytes."""
        return int(sum(int(np.prod(l.shape)) * l.dtype.itemsize
                       for l in self.pools.values()))

    def kv_bytes_per_token(self) -> float:
        """KV bytes one cached token costs across all layers (k+v,
        scales included) — pool bytes over pool token capacity."""
        return (self.kv_pool_bytes()
                / (self.num_kv_blocks * self._config.kv_block_size))

    def flush(self, uids) -> None:
        """Release finished sequences' KV blocks; accepts one uid or an
        iterable (reference: engine_v2.flush:242 takes uids)."""
        if isinstance(uids, (int, np.integer)):
            uids = [uids]
        for u in uids:
            self.state_manager.flush(int(u))
            self._finished_stash.pop(int(u), None)

    # ------------------------------------------------------------------
    # cross-mesh KV migration (ISSUE 13): park()/restore generalized so
    # the KV BYTES move between engines instead of being recomputed —
    # the transport between the prefill engine and the decode replicas
    # (and between decode replicas) in disaggregated serving. The
    # hand-off is host-mediated (device_get -> wire -> device scatter);
    # on a multi-slice TPU deployment this is exactly the ICI/DCN
    # boundary the bytes would cross anyway.

    def export_request(self, uid: int, *, n_generated: int = 0,
                       source: str = "") -> KVExportState:
        """Serialize one sequence's KV block set and release it from
        this engine: the blocks holding written KV (positions < seen)
        are gathered from the pools — quantized codes and scale slabs
        AS-IS, no dequantize — and the sequence is flushed (blocksan
        conservation runs at that quiesce; with the prefix cache on,
        published full blocks stay warm in the LRU like any park).
        Export happens at a dispatch boundary: exactly one pending
        token, which becomes the importing engine's first fused-
        dispatch input, so greedy continuation is bit-identical."""
        if self._affinity is not None:
            self._affinity.check("v2/export_request")
        mgr = self.state_manager
        seq = mgr.seqs.get(int(uid))
        if seq is None:
            raise RuntimeError(f"export_request: unknown uid {uid}")
        if seq.pending != 1:
            raise RuntimeError(
                f"export_request: sequence {uid} must have exactly one "
                f"pending token (a dispatch boundary), got {seq.pending}")
        bs = mgr.block_size
        n_payload = min(-(-seq.seen // bs), len(seq.blocks))
        if n_payload:
            idx = jnp.asarray(np.asarray(seq.blocks[:n_payload],
                                         np.int32))
            payload = jax.device_get(
                {k: jnp.take(v, idx, axis=1)
                 for k, v in self.pools.items()})
        else:
            # nothing written yet (single-token prompt): layout-only
            # payload, zero wire bytes
            payload = {k: np.zeros((v.shape[0], 0)
                                   + tuple(v.shape[2:]),
                                   np.dtype(v.dtype))
                       for k, v in self.pools.items()}
        handoff_id = None
        if self._blocksan is not None:
            handoff_id = self._blocksan.on_export(
                int(uid), seq.blocks[:n_payload], seq.seen)
        state = KVExportState(
            tokens=list(seq.tokens), n_generated=int(n_generated),
            seen=int(seq.seen), block_size=bs, kv_dtype=self.kv_dtype,
            payload=payload, handoff_id=handoff_id,
            source=source or f"engine-{id(self):x}")
        mgr.flush(int(uid))
        return state

    def _import_fn(self, width: int):
        """Donated pool scatter for one import, cached per power-of-two
        block-index width (pad indices point past the pool; mode='drop'
        discards their writes) — bounded executables, pools updated
        in place."""
        key = ("kv_import", width)
        if key not in self._fused_cache:
            def scatter(pools, idx, payload):
                return {k: pools[k].at[:, idx].set(payload[k],
                                                   mode="drop")
                        for k in pools}
            self._fused_cache[key] = jax.jit(
                scatter, donate_argnums=(0,),
                out_shardings=dict(self._pool_shardings))
        return self._fused_cache[key]

    def import_request(self, uid: int, state: KVExportState) -> int:
        """Admit a migrated sequence position-exactly: allocate blocks
        for the full history, scatter the travelled payload (quantized
        blocks + scales land untouched in their storage dtype), and
        re-publish the full-block chain into this engine's prefix
        cache. Returns the pending input token of the next fused
        dispatch. Raises — before any pool mutation — on a KV-layout
        mismatch or when the pool cannot hold the sequence."""
        if self._affinity is not None:
            self._affinity.check("v2/import_request")
        mgr = self.state_manager
        if state.kv_dtype != self.kv_dtype:
            raise ValueError(
                f"import_request: migrated KV dtype "
                f"{state.kv_dtype!r} != this engine's "
                f"{self.kv_dtype!r} — migration never converts "
                "payload formats")
        if state.block_size != mgr.block_size:
            raise ValueError(
                f"import_request: migrated block size "
                f"{state.block_size} != {mgr.block_size}")
        if set(state.payload) != set(self.pools):
            raise ValueError(
                f"import_request: payload slabs "
                f"{sorted(state.payload)} != pool slabs "
                f"{sorted(self.pools)}")
        for k, a in state.payload.items():
            pool = self.pools[k]
            want = (pool.shape[0],) + tuple(pool.shape[2:])
            got = (a.shape[0],) + tuple(a.shape[2:])
            if want != got:
                raise ValueError(
                    f"import_request: payload slab {k!r} shape "
                    f"{got} != pool layout {want}")
        n_payload = state.payload_blocks
        seq = mgr.import_sequence(int(uid), state.tokens, state.seen,
                                  n_payload)
        try:
            if n_payload:
                width = _bucket(n_payload)
                idx = np.full((width,), self.num_kv_blocks, np.int32)
                idx[:n_payload] = seq.blocks[:n_payload]
                pay = {}
                for k, a in state.payload.items():
                    if width > n_payload:
                        pad = np.zeros((a.shape[0],
                                        width - n_payload)
                                       + tuple(a.shape[2:]), a.dtype)
                        a = np.concatenate([a, pad], axis=1)
                    pay[k] = jnp.asarray(a)
                self.pools = self._import_fn(width)(
                    self.pools, jnp.asarray(idx), pay)
        except BaseException:
            mgr.flush(int(uid))     # no leak on a failed scatter
            raise
        if self._blocksan is not None:
            self._blocksan.on_import(int(uid),
                                     seq.blocks[:n_payload],
                                     state.handoff_id)
        elif state.handoff_id is not None:
            # the EXPORTER was sanitized: clear its in-transit entry
            # even though this pool runs unsanitized, or the hand-off
            # would read as dropped
            from ...analysis import blocksan as _bsan
            _bsan.record_import(state.handoff_id)
        mgr._quiesce("import")
        return int(state.tokens[-1])

    def sample_first_tokens(self, firsts: dict, temperature: float,
                            top_k: int, top_p: float,
                            seed: int) -> dict[int, int]:
        """Sample each uid's first generated token from its last-prompt
        logits with the SAME op and position keying as the in-graph
        fused loop (one batched device call). Shared by the serve
        loop's co-located prefill and the disaggregated prefill engine,
        so a hand-off's first token is bit-identical to the co-located
        one — sampling is position-keyed per (seed, uid, position),
        invariant to which engine ran the prefill."""
        from ...ops import sampling
        if not firsts:
            return {}
        mgr = self.state_manager
        uids_f = list(firsts)
        base = self._base_key(seed)
        row_keys = jax.vmap(lambda u: jax.random.fold_in(base, u))(
            jnp.asarray(np.asarray(uids_f, np.uint32)))
        keys = sampling.position_keys(
            row_keys,
            jnp.asarray(np.asarray([mgr.seqs[u].seen for u in uids_f])))
        toks_dev = sampling.sample_tokens_batched(
            jnp.stack([firsts[u] for u in uids_f]).astype(jnp.float32),
            keys, temperature=temperature, top_k=top_k, top_p=top_p)
        return {u: int(t)
                for u, t in zip(uids_f, jax.device_get(toks_dev))}

    def prefill_request(self, uid: int, prompt, *,
                        temperature: Optional[float] = None,
                        top_k: Optional[int] = None,
                        top_p: Optional[float] = None,
                        seed: int = 0) -> int:
        """Disaggregated-prefill producer half (ISSUE 13): chunked
        prefill of one prompt on THIS engine plus the first generated
        token, leaving the sequence at the exact dispatch-boundary
        state (one pending token) ``export_request`` ships — the same
        state the co-located serve loop reaches before its first fused
        dispatch, so the downstream decode is bit-identical either
        way. Returns the first token."""
        temperature, top_k, top_p, _ = self._sampling_args(
            temperature, top_k, top_p, None)
        uid = int(uid)
        self.schedule([uid], [[int(t) for t in prompt]])
        mgr = self.state_manager
        try:
            logits = None
            while mgr.seqs[uid].pending:
                logits = self._run([uid])
            tok = self.sample_first_tokens(
                {uid: logits[0]}, temperature, top_k, top_p, seed)[uid]
            mgr.extend(uid, [tok])
        except BaseException:
            self.flush(uid)
            raise
        self.serving_stats["decoded_tokens"] += 1
        return tok

    # ------------------------------------------------------------------
    # fused multi-step decode: K ticks per host dispatch, sampling and
    # termination in-graph (the FastGen kernel-resident decode loop)

    def _base_key(self, seed: int) -> jnp.ndarray:
        key = self._seed_keys.get(seed)
        if key is None:
            # bound the cache: seed is a caller-supplied kwarg, and a
            # server feeding a fresh seed per request must not grow
            # this dict forever (keys are cheap to rebuild)
            if len(self._seed_keys) >= 64:
                self._seed_keys.clear()
            key = self._seed_keys.setdefault(seed,
                                             jax.random.PRNGKey(seed))
        return key

    def _sampling_args(self, temperature, top_k, top_p, eos_id):
        """Per-call overrides over the config's sampling defaults."""
        c = self._config
        return (float(c.sampling_temperature if temperature is None
                      else temperature),
                int(c.sampling_top_k if top_k is None else top_k),
                float(c.sampling_top_p if top_p is None else top_p),
                (c.eos_token_id if eos_id is None else int(eos_id)))

    def _fused_fn(self, num_steps: int, temperature: float, top_k: int,
                  top_p: float, eos_id: Optional[int]):
        key = (num_steps, temperature, top_k, top_p, eos_id)
        if key not in self._fused_cache:
            tp = self._v1.topology.model_parallel_size
            pool_sh = dict(self._pool_shardings)
            self._fused_cache[key] = jax.jit(
                functools.partial(
                    fused_decode_loop, self.model, num_steps=num_steps,
                    eos_id=eos_id, temperature=temperature, top_k=top_k,
                    top_p=top_p, use_kernel=(tp <= 1)),
                donate_argnums=(1,),
                out_shardings=(None, None, None, None, None, None,
                               pool_sh))
        return self._fused_cache[key]

    def _serve_fn(self, num_steps: int, temperature: float, top_k: int,
                  top_p: float, eos_id: Optional[int]):
        """Ring-mode executable (ISSUE 6): the fused decode loop with
        in-graph admission of pre-staged requests and the device-side
        output ring (paged.fused_serve_loop). Cached beside the plain
        fused executables under a mode-tagged key."""
        key = ("serve", num_steps, temperature, top_k, top_p, eos_id)
        if key not in self._fused_cache:
            tp = self._v1.topology.model_parallel_size
            pool_sh = dict(self._pool_shardings)
            self._fused_cache[key] = jax.jit(
                functools.partial(
                    fused_serve_loop, self.model, num_steps=num_steps,
                    eos_id=eos_id, temperature=temperature, top_k=top_k,
                    top_p=top_p, use_kernel=(tp <= 1)),
                donate_argnums=(1,),
                out_shardings=(None,) * 11 + (pool_sh,))
        return self._fused_cache[key]

    def _spec_fn(self, num_steps: int, temperature: float, top_k: int,
                 top_p: float, eos_id: Optional[int]):
        """Speculative-decode executable (ISSUE 9): the fused decode
        loop with prompt-lookup drafting and the 1+draft_len verify
        forward (paged.fused_spec_decode_loop). draft_len/min_ngram
        are static from the config block (one executable family per
        setting)."""
        sp = self._config.speculative
        key = ("spec", num_steps, sp.draft_len, sp.min_ngram,
               temperature, top_k, top_p, eos_id)
        if key not in self._fused_cache:
            tp = self._v1.topology.model_parallel_size
            pool_sh = dict(self._pool_shardings)
            self._fused_cache[key] = jax.jit(
                functools.partial(
                    fused_spec_decode_loop, self.model,
                    num_steps=num_steps, draft_len=sp.draft_len,
                    min_ngram=sp.min_ngram, eos_id=eos_id,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    use_kernel=(tp <= 1)),
                donate_argnums=(1,),
                out_shardings=(None,) * 9 + (pool_sh,))
        return self._fused_cache[key]

    def _spec_serve_fn(self, num_steps: int, temperature: float,
                       top_k: int, top_p: float,
                       eos_id: Optional[int]):
        """Ring-mode speculative executable: in-graph admission +
        per-row device output ring + prompt-lookup verify
        (paged.fused_spec_serve_loop)."""
        sp = self._config.speculative
        key = ("spec_serve", num_steps, sp.draft_len, sp.min_ngram,
               temperature, top_k, top_p, eos_id)
        if key not in self._fused_cache:
            tp = self._v1.topology.model_parallel_size
            pool_sh = dict(self._pool_shardings)
            self._fused_cache[key] = jax.jit(
                functools.partial(
                    fused_spec_serve_loop, self.model,
                    num_steps=num_steps, draft_len=sp.draft_len,
                    min_ngram=sp.min_ngram, eos_id=eos_id,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    use_kernel=(tp <= 1)),
                donate_argnums=(1,),
                out_shardings=(None,) * 14 + (pool_sh,))
        return self._fused_cache[key]

    def _history_rows(self, uids: list[int], bb: int) -> np.ndarray:
        """Right-aligned recent-token history rows [bb, history_window]
        for the prompt-lookup drafter, -1-filled (pad rows all -1) —
        the committed history INCLUDING the pending token, so drafts
        continue from the next dispatch input. Prefix-cache-shared
        prompt blocks are in ``seq.tokens`` like any other committed
        token, so a cache-hit admission seeds the same window a cold
        one would."""
        hw = int(self._config.speculative.history_window)
        hist = np.full((bb, hw), -1, np.int32)
        for i, u in enumerate(uids):
            hist[i] = self.state_manager.history_tail(u, hw)
        return hist

    def _spec_operands(self, uids: list[int], k: int,
                       budgets: dict[int, int], seed: int):
        """:meth:`_fused_operands` plus the drafter's history window.
        The reserve horizon grows to ``k * (1 + draft_len)``: a
        K-step speculative dispatch may commit that many tokens per
        row (still budget-capped; in-graph drafts are clamped to
        ``remaining - 1`` so KV writes never pass the reserved
        blocks)."""
        el = int(self._config.speculative.draft_len)
        wide = {u: min(int(budgets[u]), k * (1 + el)) for u in uids}
        for u in uids:
            # _fused_operands reserves min(k, budget); top up to the
            # speculative horizon first (idempotent delta)
            self.state_manager.reserve(u, max(wide[u], 1))
        ops = self._fused_operands(uids, k, budgets, seed)
        hist = jnp.asarray(self._history_rows(uids, int(ops[0].shape[0])))
        return ops + (hist,)

    def _fused_operands(self, uids: list[int], k: int,
                        budgets: dict[int, int], seed: int):
        """Host-side build of one fused dispatch's operands. Every uid
        must have exactly ONE pending token (its next input — the last
        sampled/committed token); blocks covering the dispatch horizon
        are preallocated here so the in-graph KV writes always land in
        real blocks."""
        mgr = self.state_manager
        seqs = [mgr.seqs[u] for u in uids]
        for u, s in zip(uids, seqs):
            if s.pending != 1:
                raise RuntimeError(
                    f"fused decode: sequence {u} must have exactly one "
                    f"pending token (the dispatch input), got {s.pending}")
            mgr.reserve(u, min(k, max(int(budgets[u]), 1)))
        bb = _batch_bucket(len(seqs))
        tokens = np.zeros((bb,), np.int32)
        pos = np.zeros((bb,), np.int32)
        act = np.zeros((bb,), bool)
        rem = np.zeros((bb,), np.int32)
        for i, (u, s) in enumerate(zip(uids, seqs)):
            tokens[i] = s.tokens[-1]
            pos[i] = s.seen
            act[i] = budgets[u] > 0
            rem[i] = budgets[u]
        tables = np.stack([mgr.block_table(s) for s in seqs]
                          + [mgr.block_table(seqs[0])] * (bb - len(seqs)))
        # narrow to the blocks actually held (context + reserved
        # horizon) — bounded executables per power-of-two width
        kb = min(_bucket(max(max(len(s.blocks) for s in seqs), 1)),
                 tables.shape[1])
        tables = tables[:, :kb]
        # per-row PRNG keys: uid folded into the base key (pad rows get
        # sentinel ids); each loop step folds in the token position, so
        # sampling is invariant to the dispatch grouping
        base = self._base_key(seed)
        # via numpy: jnp.asarray of a LIST is an implicit
        # convert_element_type upload (trips the transfer guard); a
        # numpy array takes the explicit device_put path
        ids = jnp.asarray(np.asarray(
            list(uids) + [(1 << 30) + i for i in range(bb - len(uids))],
            np.uint32))
        row_keys = jax.vmap(lambda u: jax.random.fold_in(base, u))(ids)
        return (jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(tables),
                jnp.asarray(act), jnp.asarray(rem), row_keys)

    def _fused_dispatch_scope(self, fn_key: tuple, ops: tuple,
                              variant: str = "host"):
        """Sentinel scope for ONE fused dispatch: a new (jit key,
        operand shape/dtype, variant) signature may compile; a seen one
        must hit the executable cache — and under the transfer guard no
        implicit host transfer may ride the dispatch (operands are
        already device arrays; the loop carry never leaves the device).

        ``variant`` separates host-built operands from device-carry
        operands: their avals match but their shardings don't (fresh
        ``jnp.asarray`` uploads vs committed jit outputs), so XLA keeps
        one executable per variant — a fact this sentinel itself
        surfaced when first wired in."""
        if self._affinity is not None:
            # every fused dispatch path (decode_fused, chain mode, ring
            # mode) enters through this scope — one affinity choke point
            self._affinity.check("v2/fused_dispatch")
        s = self._decode_sentinel
        if s is None:
            return _NULLCM
        sig = (fn_key, variant,
               tuple((tuple(a.shape), str(a.dtype)) for a in ops))
        if sig not in self._fused_sigs:
            self._fused_sigs.add(sig)
            s.expect("new fused bucket/sampling signature")
        import contextlib
        stack = contextlib.ExitStack()
        stack.enter_context(s.watch())
        stack.enter_context(self._hot_guard())
        return stack

    def decode_fused(self, batch_uids: Sequence[int],
                     k_steps: Optional[int] = None, *,
                     budgets: Optional[dict[int, int]] = None,
                     temperature: Optional[float] = None,
                     top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     eos_id: Optional[int] = None,
                     seed: int = 0) -> dict[int, list[int]]:
        """ONE fused dispatch: advance every uid up to
        ``min(k_steps, budgets[uid])`` tokens inside a single compiled
        while_loop — forward, sampling, KV writes and EOS/budget
        termination all on device. Each uid needs exactly one pending
        token (e.g. from put() + a sampled continuation, or a previous
        decode_fused). Generated tokens are committed to the sequence
        state; the last one stays pending as the next dispatch's input.
        Returns {uid: [sampled tokens]} (a row that sampled ``eos_id``
        includes it and stops)."""
        uids = [int(u) for u in batch_uids]
        if not uids:
            return {}
        cfg = self._config
        k = max(1, int(k_steps if k_steps is not None
                       else (cfg.fused_decode_steps or 8)))
        temperature, top_k, top_p, eos = self._sampling_args(
            temperature, top_k, top_p, eos_id)
        b = {u: int(budgets[u]) if budgets is not None else k
             for u in uids}
        st = self.serving_stats
        spec = self._config.speculative.enabled
        tel = _telemetry()
        t0 = time.perf_counter() if tel is not None else 0.0
        with (tel.span("v2/fused_dispatch",
                       dispatch_id=st["fused_dispatches"] + 1,
                       rows=len(uids), k=k)
              if tel is not None else _NULLCM):
            if spec:
                sp = self._config.speculative
                ops = self._spec_operands(uids, k, b, seed)
                fn = self._spec_fn(k, temperature, top_k, top_p, eos)
                fn_key = ("spec", k, sp.draft_len, sp.min_ngram,
                          temperature, top_k, top_p, eos)
            else:
                ops = self._fused_operands(uids, k, b, seed)
                fn = self._fused_fn(k, temperature, top_k, top_p, eos)
                fn_key = (k, temperature, top_k, top_p, eos)
            if tel is not None:
                self._device_truth_observe(tel, "v2/fused_dispatch",
                                           fn, ops)
            st["host_dispatches"] += 1
            st["fused_dispatches"] += 1
            with self._fused_dispatch_scope(fn_key, ops):
                if spec:
                    (out, out_ptr, steps, _, _, _, _, _, spec_stats,
                     self.pools) = fn(self.params, self.pools, *ops)
                else:
                    out, steps, _, _, _, _, self.pools = fn(
                        self.params, self.pools, *ops)
            toks = np.asarray(out)[:len(uids)]
            if spec:
                ptrs = np.asarray(out_ptr)[:len(uids)]
                self._absorb_spec_stats(np.asarray(spec_stats))
            mgr = self.state_manager
            res: dict[int, list[int]] = {}
            for i, u in enumerate(uids):
                row = [int(t) for t in
                       (toks[i, :ptrs[i]] if spec else toks[i])
                       if t >= 0]
                mgr.commit_device_tokens(u, row)
                res[u] = row
                st["decoded_tokens"] += len(row)
                st["fused_slot_tokens"] += len(row)
                if not spec:
                    # one token per live slot; the spec path's live-slot
                    # count arrives in the device stats instead
                    st["fused_live_slots"] += len(row)
            n_exec = int(steps)
            st["fused_steps"] += n_exec
            st["fused_slots"] += n_exec * len(uids)
        if tel is not None:
            self._record_dispatch_telemetry(
                tel, time.perf_counter() - t0)
        if self._numsan is not None:
            # the fused loop returns tokens, not logits — the numsan
            # work here is the dispatch-boundary choke point: cadenced
            # KV-scale audit, then surface any deferred quantize-site
            # saturation findings from the executed loop
            self._numsan_dispatches += 1
            if (self._numsan_dispatches
                    % self._numsan.probe_interval == 0):
                self.numsan_check_kv_pools()
            self._numsan.drain()
        return res

    def _absorb_spec_stats(self, stats) -> None:
        """Fold one dispatch's (or chain's) device spec counters —
        [proposed, accepted, hit_slots, live_slots] int32 — into
        serving_stats."""
        self.serving_stats["spec_proposed_tokens"] += int(stats[0])
        self.serving_stats["spec_accepted_tokens"] += int(stats[1])
        self.serving_stats["spec_hit_slots"] += int(stats[2])
        self.serving_stats["fused_live_slots"] += int(stats[3])

    def _numsan_probe(self, logits) -> None:
        """Per-tick dispatch numsan hook: every ``probe_interval``-th
        dispatch runs the fused logits stats (non-finite count +
        masked max|logit|) and, with a quantized cache, the KV-scale
        audit — one host sync on the cadence; then drains any deferred
        quantize-site saturation findings (always, pure host work)."""
        san = self._numsan
        self._numsan_dispatches += 1
        if self._numsan_dispatches % san.probe_interval == 0:
            if self._logits_stats_fn is None:
                self._logits_stats_fn = jax.jit(lambda x: (
                    jnp.sum(~jnp.isfinite(x)).astype(jnp.int32),
                    jnp.max(jnp.where(jnp.isfinite(x),
                                      jnp.abs(x), 0.0))))
            nf, ma = self._logits_stats_fn(logits)
            san.check_logits("v2/dispatch", int(nf), float(ma))
            self.numsan_check_kv_pools()
        san.drain()

    def numsan_check_kv_pools(self) -> None:
        """Audit the quantized KV scale slabs for non-finite scales (a
        non-finite activation quantized into the cache poisons every
        later read of its block). Rides the numsan probe cadence;
        callable directly for forensics. No-op without a quantized
        cache or with ``kv_scale_probe`` off."""
        if (self._numsan is None or not self._kv_quant
                or not self._numsan_kv_probe):
            return
        scales = jnp.concatenate([self.pools["ks"].reshape(-1),
                                  self.pools["vs"].reshape(-1)])
        finite = jnp.isfinite(scales)
        nf = int(jnp.sum(~finite))
        ms = float(jnp.max(jnp.where(finite, scales, 0.0)))
        self._numsan.check_kv_scales("v2/kv_pools", nf, ms)

    def _device_truth_observe(self, tel, name: str, fn,
                              dev_ops: tuple) -> None:
        """Flight-recorder heartbeat + executable-ledger observation
        for one v2 dispatch (ISSUE 5; no-ops unless the opt-in knobs
        enabled them). Must run BEFORE the dispatch: the KV pools are
        donated operands."""
        fr = tel.get_flight_recorder()
        if fr is not None:
            fr.progress("v2_dispatch", span=name)
        led = tel.get_ledger()
        if led is not None:
            entry = led.observe(name, fn,
                                (self.params, self.pools)
                                + tuple(dev_ops),
                                mesh=self.mesh)
            if self._meshsan is not None:
                # traffic-contract check (ISSUE 15): once per NEW
                # executable, a set lookup per later dispatch
                self._meshsan.observe_entry(entry)

    def _record_dispatch_telemetry(self, tel, dt: float) -> None:
        """Fused-dispatch boundary metrics (per DISPATCH — K tokens'
        worth of work — never per token)."""
        fr = tel.get_flight_recorder()
        if fr is not None:
            # drain completed = the decode loop made real progress
            # (the hang watchdog's deadline clock resets here)
            fr.progress("v2_drain")
        reg = tel.get_registry()
        if reg is None:
            return
        reg.histogram(
            "ds_serving_fused_dispatch_seconds",
            "host-blocking time of one fused decode dispatch: full "
            "dispatch (operands+enqueue+drain) on the decode_fused "
            "path, ring-buffer drain only on the double-buffered "
            "generate_fused path (its enqueue overlaps device "
            "work)").observe(dt)
        tel.bridges.collect_serving(reg, self.serving_metrics())
        reg.gauge("ds_serving_free_kv_blocks",
                  "schedulable blocks in the paged KV pool (truly free "
                  "plus evictable prefix-cached)").set(
            self.free_blocks, engine="v2")

    def serving_metrics(self) -> dict:
        """Decode-loop efficiency counters (monitor/bench surface):
        ``dispatches_per_token`` — host dispatches per decoded token
        (1.0 = per-tick; ~1/K with the fused loop) and
        ``fused_occupancy`` — fraction of scheduled (row, step) slots
        whose row was still LIVE (1.0 = every scheduled row decoded
        every step; rows going EOS/budget-inactive mid-loop lower it).
        Pad rows added by the batch bucketing are not counted — this
        measures scheduling efficiency over real sequences, not device
        utilization of the padded bucket. Spec-off the numerator equals
        the committed-token count; spec-on it comes from the device
        loops' live-slot counter, so occupancy stays a <= 1.0 fraction
        while ``tokens_per_dispatch`` carries the multiplier.

        With prefix caching the dict additionally carries the cache
        counters (``prefix_hits``/``prefix_misses`` at full-block
        granularity, ``prefix_evictions``, ``prefill_tokens_saved``)
        and occupancy gauges (``prefix_hit_rate``,
        ``prefix_cached_blocks``, ``prefix_evictable_blocks``) — zeros
        when the cache is disabled, so consumers always see one stable
        schema."""
        st = dict(self.serving_stats)
        st.update(self.state_manager.prefix_cache_metrics())
        st["dispatches_per_token"] = (
            st["host_dispatches"] / max(st["decoded_tokens"], 1))
        st["fused_occupancy"] = (
            st["fused_live_slots"] / max(st["fused_slots"], 1))
        # speculative decoding (ISSUE 9): tokens_per_dispatch is the
        # mean tokens COMMITTED per scheduled (row, tick) slot in the
        # fused loops — <= 1.0 spec-off (then it equals
        # fused_occupancy), > 1.0 when verified drafts multiply each
        # forward. spec_acceptance_rate = accepted / proposed drafts.
        st["tokens_per_dispatch"] = (
            st["fused_slot_tokens"] / max(st["fused_slots"], 1))
        st["spec_acceptance_rate"] = (
            st["spec_accepted_tokens"]
            / max(st["spec_proposed_tokens"], 1))
        # active dispatch-chain depth (ISSUE 6 knob) rides along so
        # consumers can correlate dispatch ratios with the configured
        # chain depth
        st["max_inflight_dispatches"] = int(
            self._config.max_inflight_dispatches)
        # KV-pool byte truth (ISSUE 12): pool footprint + per-token
        # cost in the ACTIVE storage format, so a quantized engine's
        # HBM win (and its block-count growth at equal budget) is read
        # straight off the serving metrics. kv_dtype is a string —
        # bridges attach it as the ds_kv_pool_bytes gauge's label;
        # numeric-only consumers (monitor events, --diff) skip it.
        st["kv_pool_bytes"] = self.kv_pool_bytes()
        st["kv_bytes_per_token"] = round(self.kv_bytes_per_token(), 3)
        st["kv_num_blocks"] = int(self.num_kv_blocks)
        st["kv_dtype"] = self.kv_dtype
        return st

    def reset_serving_metrics(self) -> None:
        for k in self.serving_stats:
            self.serving_stats[k] = 0
        self.state_manager.reset_prefix_stats()

    # ------------------------------------------------------------------
    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32,
                 eos_id: Optional[int] = None) -> list[list[int]]:
        """Greedy continuous batching driver over schedule()/tick():
        admits prompts as KV blocks free up — including mid-prefill of
        other prompts, since admission happens between ticks — and
        decodes all live sequences together each tick. What DeepSpeed-MII
        implements on top of put() (reference: mii serving loop).
        ``eos_id`` stops a sequence once it samples that token (the
        token is included in its output). One host round trip per
        decoded token — generate_fused() is the production path."""
        mgr = self.state_manager
        bs = mgr.block_size
        pending = list(enumerate([list(map(int, p)) for p in prompts]))
        live: dict[int, list[int]] = {}
        reserved: dict[int, int] = {}   # uid -> worst-case block budget
        results: dict[int, list[int]] = {}
        max_live = self._config.max_ragged_sequence_count
        # serving-latency telemetry (resolved once per generate call; a
        # per-token observe is one float append when enabled, nothing
        # when disabled)
        tel = _telemetry()
        reg = tel.get_registry() if tel is not None else None
        lat = _LatencyProbe(reg) if reg is not None else None
        # per-request traces (ISSUE 10): the per-tick driver records
        # the same lifecycle the fused serve loop does, so its requests
        # land in the access log / Perfetto tracks too
        rt = tel.get_request_recorder() if tel is not None else None
        if rt is not None:
            for uid, prompt in pending:
                rt.enqueue(uid, priority=1, prompt_tokens=len(prompt),
                           max_new_tokens=max_new_tokens)

        def admit():
            """Admit as many pending prompts as fit, reserving each one's
            worst-case block budget so live sequences can never exhaust
            the pool mid-decode. Prefix-cache hits shrink a prompt's
            admission cost to its UNCACHED blocks (plus pinning parked
            LRU blocks out of the evictable headroom), so a shared
            system prompt stops counting against capacity."""
            batch: list[tuple[int, list[int]]] = []
            allocated = sum(len(mgr.seqs[u].blocks) for u in live)
            headroom = (mgr.available_blocks
                        - (sum(reserved.values()) - allocated))
            while pending and len(live) + len(batch) < max_live:
                uid, prompt = pending[0]
                need = -(-(len(prompt) + max_new_tokens) // bs)
                if need > mgr.max_blocks_per_seq or \
                        need > mgr.allocator.num_blocks:
                    raise ValueError(
                        f"prompt {uid}: {len(prompt)} tokens + "
                        f"{max_new_tokens} new can never fit the KV pool "
                        f"(needs {need} blocks)")
                cost = mgr.admission_cost(prompt, need)
                if cost > headroom:
                    break
                pending.pop(0)
                headroom -= cost
                reserved[uid] = need
                batch.append((uid, prompt))
            if batch:
                self.schedule([u for u, _ in batch],
                              [p for _, p in batch])
                for uid, _ in batch:
                    live[uid] = []
            if lat is not None:
                lat.admitted([u for u, _ in batch], waiting=len(pending))
            if rt is not None:
                for uid, _ in batch:
                    seen = mgr.seqs[uid].seen
                    rt.admitted(uid, queue_depth=len(pending),
                                cached_tokens=seen,
                                cached_blocks=seen // bs)

        try:
            admit()
            while live or pending:
                if not live:
                    admit()
                    if not live:  # reservation math guarantees progress
                        raise RuntimeError(
                            "continuous-batching deadlock: pending "
                            "prompts but nothing admissible")
                    continue
                # one tick advances every pending sequence one chunk; a
                # sequence whose pending drained yields logits -> sample
                t_tick = time.perf_counter() if rt is not None else 0.0
                finished = self.tick()
                decode_uids: list[int] = []
                for u in sorted(finished):
                    if u not in live:
                        # not ours (scheduled by another caller): re-stash
                        self._finished_stash[u] = finished[u]
                        continue
                    # per-token host argmax IS the per-tick driver's cost
                    # model (one RTT per token, documented above);
                    # generate_fused() is the production path
                    live[u].append(int(jnp.argmax(finished[u])))  # graftlint: disable=GL004
                    self.serving_stats["decoded_tokens"] += 1
                    if lat is not None:
                        lat.tokens(u, 1, first=len(live[u]) == 1)
                    if rt is not None:
                        # each tick is this driver's dispatch window:
                        # tick wall lands in decode_active, inter-tick
                        # host time in boundary_gap
                        rt.tokens_landed(u, 1, window_start=t_tick,
                                         steps=1)
                    if (len(live[u]) >= max_new_tokens
                            or (eos_id is not None
                                and live[u][-1] == eos_id)):
                        results[u] = live.pop(u)[:max_new_tokens]
                        reserved.pop(u)
                        self.flush(u)
                        if rt is not None:
                            rt.finished(u, "completed")
                    else:
                        decode_uids.append(u)
                if decode_uids:
                    self.schedule(decode_uids,
                                  [[live[u][-1]] for u in decode_uids],
                                  do_checks=False)  # blocks pre-reserved
                admit()
        except BaseException:
            # an error mid-drive (e.g. a later prompt's oversized
            # ValueError raised from admit()) must not strand the
            # already-scheduled sequences' KV blocks on a shared engine
            for u in list(live):
                self.flush(u)
            if rt is not None:
                for u in list(live) + [uid for uid, _ in pending]:
                    rt.finished(u, "aborted")
            raise
        return [results[i] for i in range(len(prompts))]

    # ------------------------------------------------------------------
    def generate_fused(self, prompts: Sequence[Sequence[int]],
                       max_new_tokens: int = 32, *,
                       k_steps: Optional[int] = None,
                       temperature: Optional[float] = None,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None,
                       eos_id: Optional[int] = None,
                       seed: int = 0) -> list[list[int]]:
        """Continuous batching where the host is ONLY an admission
        layer: every live sequence advances up to K tokens per dispatch
        inside the fused on-device loop (sampling, KV writes and
        EOS/budget termination in-graph), so decode throughput rides
        K·compute per host round trip instead of one RTT per token.

        Between dispatches the host admits new prompts, prefills them
        through the bucketed chunk path, and drains finished tokens
        from the dispatch's output ring buffer. Dispatches chain up to
        ``max_inflight_dispatches`` deep (default 2 — double
        buffering): while dispatches run on device, the host drains
        the OLDEST one's ring buffer — chaining works because the
        loop's carry (next tokens, positions, active masks) stays on
        device, so dispatch N+1 needs no host read of dispatch N. With
        ``fused_admission`` the chain goes further device-resident:
        waiting prompts are pre-staged and swapped into finished rows'
        slots inside the compiled loop, and the host reads one output
        ring per CHAIN instead of per dispatch. Greedy decode is
        token-identical to generate(); stochastic decode is
        dispatch-schedule-invariant (position-keyed sampling), so
        per-tick and fused-K agree there too.

        The scheduler itself lives in
        :class:`~.serve_loop.FusedServeLoop` (shared with the async
        serving front end, ``deepspeed_tpu.serving``); this wrapper
        runs it closed-loop over a fixed prompt list."""
        from .serve_loop import FusedServeLoop
        loop = FusedServeLoop(self, k_steps=k_steps,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p, eos_id=eos_id, seed=seed,
                              strict=True)
        results: dict[int, list[int]] = {}
        for i, p in enumerate(prompts):
            loop.submit(p, max_new_tokens, uid=i)
            results[i] = []
        while loop.has_work():
            for evt in loop.step():
                results[evt.uid].extend(evt.tokens)
        return [results[i][:max_new_tokens]
                for i in range(len(prompts))]
