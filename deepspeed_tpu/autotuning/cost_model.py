"""Device-truth cost model for the planner (ISSUE 7 tentpole part 1).

Two models, one calibration source:

- :class:`MemoryModel` — the audited per-device byte accounting the old
  ``memory_per_device`` table grew into: ZeRO-stage param/grad/optimizer
  terms with per-term CEILING division (sharding allocates
  ``ceil(P/N)`` elements per device — flooring the whole expression
  under-reported by up to N-1 elements per term), an explicit
  activation term driven by microbatch x sequence x remat policy
  (previously a silent ``OVERHEAD = 1.3`` factor), and the optimizer
  offload ratio. ``audit()`` cross-checks a prediction against the
  executable ledger's ``memory_analysis()`` peak for the same step.

- :class:`CostModel` — predicted step seconds from analytic
  FLOPs/bytes plus a :class:`Calibration`: effective device FLOPs/s and
  fixed per-step overhead fitted from a short measured run (one or two
  points), per-mesh-axis algorithm-bandwidth LOWER bounds pulled from
  the ledger's HLO collective traffic over the span tracer's measured
  window (``ExecutableLedger.axis_algbw_bounds``), and the overlap
  ratio that decides how much collective time the schedule hides under
  compute (T3-style; the default 0.71 is the domino chunked-overlap
  ratio of a CPU run before the chip, a proxy with no chip measurement
  behind it: ROADMAP.md queue 3 item 5).

Everything here is host-only arithmetic (graftlint GL041 contract for
``autotuning/``): no jax tracing, no device dispatch — the planner
feeds it AOT ``cost_analysis()``/``memory_analysis()`` facts and
measured seconds.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

ADAM_STATE_BYTES = 16  # fp32 master + 2 fp32 moments per param
GRAD_BYTES = 4         # grads accumulate in fp32 (engine _step_parts accumulate)

# per-layer live-activation multiplier by remat policy: how many
# [micro_batch, seq, hidden]-sized residuals each layer keeps across the
# backward. Full recompute keeps only the layer-boundary residual; the
# save-more policies keep attention/MLP intermediates too. (Under every
# policy an attention layer on attn_impl="flash" keeps its output as well,
# one more such tensor: this table knows neither the attention
# implementation nor a stack's kinds, and leaves it to `fits`' safety
# factor.) Coarse by design — audited against ledger memory_analysis(), not
# derived from it.
REMAT_ACTIVATION_FACTOR = {
    "nothing_saveable": 1.0,
    "segments": 2.0,                       # attention residuals kept
    "save_attn_ffn": 2.0,
    "dots_saveable": 3.0,
    "dots_with_no_batch_dims_saveable": 3.0,
    "checkpoint_dots": 3.0,
    "everything_saveable": 6.0,
    "none": 6.0,                           # remat off: everything live
}


def ceil_div(a: int, b: int) -> int:
    return -(-int(a) // max(int(b), 1))


# mesh axes the ZeRO++ wire protocol quantizes traffic on (the sharded
# data-parallel extent; runtime/zeropp.py scope) — axis labels from the
# HLO walk may be combinations like "fsdp+zps"
WIRE_SHARD_AXES = ("fsdp", "zps")


def wire_dtype_bytes(wire_dtype: str) -> float:
    """Effective wire bytes per payload element for a qwZ/qgZ wire
    format, per-block fp32 scale overhead included (delegates to the
    kernel module's single source of truth — including its QBLOCK
    default, so a block-size retune can't silently diverge the cost
    model from the actual wire)."""
    from ..ops.pallas.quantization import wire_bytes_per_element
    return wire_bytes_per_element(wire_dtype)


def quantized_wire_facts(facts: "AOTFacts", wire_dtype: str,
                         axes: tuple[str, ...] = WIRE_SHARD_AXES) -> \
        "AOTFacts":
    """Analytic wire-dtype transform of fp32-wire AOT facts: the
    sharded-DP axes' collective payload scales by the wire ratio
    (int8 + scales ~ 0.25x), and the quantize/dequantize bracket is
    charged as two extra HBM passes over the moved payload in
    ``bytes_accessed`` (that term participates in the memory-bandwidth
    roofline, so compute-bound calibrations penalize the bracket while
    bandwidth-bound ones are dominated by the comm credit). Used by
    the planner to score ``wire_dtype`` grid variants without a second
    AOT compile; a real compile of the variant config supersedes it."""
    if wire_dtype in ("fp32", "f32", "none"):
        return facts
    ratio = wire_dtype_bytes(wire_dtype) / 4.0
    by_axis: dict[str, float] = {}
    moved = 0.0
    for axis, nbytes in facts.collective_bytes_by_axis.items():
        parts = set(axis.split("+"))
        if parts and parts <= set(axes):
            by_axis[axis] = nbytes * ratio
            moved += nbytes
        else:
            by_axis[axis] = nbytes
    return dataclasses.replace(
        facts,
        bytes_accessed=facts.bytes_accessed + 2.0 * moved,
        collective_bytes_by_axis=by_axis)


def hbm_headroom_bytes(device=None) -> int:
    """Schedulable device-memory headroom (bytes_limit minus bytes in
    use) from the backend's memory_stats — the same source as the
    ``ds_hbm_headroom_bytes`` gauge. 0 when the backend won't say
    (CPU): callers must treat 0 as "unknown", not "full"."""
    from ..utils.memory import device_memory_stats
    stats = device_memory_stats(device)
    limit = int(stats.get("bytes_limit", 0) or 0)
    if limit <= 0:
        return 0
    in_use = int(stats.get("bytes_in_use", 0) or 0)
    return max(limit - in_use, 0)


@dataclasses.dataclass(frozen=True)
class MemoryModel:
    """Audited per-device training-state byte model (reference:
    autotuner.py get_instantiation_memory_required_per_module Z0-Z3,
    ZeRO-Infinity §3 memory tables). ``world`` is the sharded
    data-parallel degree (fsdp x zps); replicated axes (dp, tp for the
    state) don't divide these terms."""

    num_params: int
    bytes_per_el: int = 2          # compute-dtype param bytes
    world: int = 1
    optim_bytes_per_param: int = ADAM_STATE_BYTES

    def _shard(self, per_param_bytes: int) -> int:
        # per-device elements are ceil(P/N); bytes multiply AFTER the
        # shard split (the old table floored the whole product)
        return ceil_div(self.num_params, self.world) * per_param_bytes

    def param_bytes(self, stage: int) -> int:
        if stage >= 3:
            return self._shard(self.bytes_per_el)
        return self.num_params * self.bytes_per_el

    def grad_bytes(self, stage: int) -> int:
        if stage >= 2:
            return self._shard(GRAD_BYTES)
        return self.num_params * GRAD_BYTES

    def optimizer_bytes(self, stage: int, offload_ratio: float = 0.0) -> int:
        on_device = max(0.0, 1.0 - float(offload_ratio))
        full = (self._shard(self.optim_bytes_per_param) if stage >= 1
                else self.num_params * self.optim_bytes_per_param)
        return int(full * on_device)

    def activation_bytes(self, micro_batch: int, seq_len: int,
                         hidden: int, num_layers: int,
                         remat_policy: str = "nothing_saveable",
                         vocab_size: int = 0,
                         logits_materialized: bool = True) -> int:
        """Live activations for one micro-batch through the backward:
        per-layer residuals scaled by the remat policy's keep factor,
        a few working copies of the stream, and the [B, S, V] logits +
        fp32 softmax when the loss materializes them (loss_chunk=0)."""
        if micro_batch <= 0 or seq_len <= 0 or hidden <= 0:
            return 0
        factor = REMAT_ACTIVATION_FACTOR.get(remat_policy, 3.0)
        stream = micro_batch * seq_len * hidden * self.bytes_per_el
        total = int(stream * (num_layers * factor + 4))
        if vocab_size > 0 and logits_materialized:
            total += micro_batch * seq_len * vocab_size * (
                self.bytes_per_el + 4)
        return total

    def total_bytes(self, stage: int, *, micro_batch: int = 0,
                    seq_len: int = 0, hidden: int = 0,
                    num_layers: int = 0,
                    remat_policy: str = "nothing_saveable",
                    offload_ratio: float = 0.0,
                    vocab_size: int = 0) -> int:
        return (self.param_bytes(stage) + self.grad_bytes(stage)
                + self.optimizer_bytes(stage, offload_ratio)
                + self.activation_bytes(micro_batch, seq_len, hidden,
                                        num_layers, remat_policy,
                                        vocab_size=vocab_size))

    def fits(self, budget_bytes: int, stage: int,
             safety_factor: float = 1.1, **kw) -> bool:
        """True when the modeled bytes (x fragmentation safety) fit the
        budget; a budget of 0 means "unknown" and always fits."""
        if budget_bytes <= 0:
            return True
        return self.total_bytes(stage, **kw) * safety_factor <= budget_bytes

    def audit(self, predicted_bytes: int, ledger_memory: dict) -> dict:
        """Cross-check a prediction against the ledger's normalized
        ``memory_analysis()`` dict for the same executable. Returns
        {predicted, ledger_peak, rel_err}; rel_err is None when the
        ledger has no peak (CPU backends sometimes expose nothing) —
        None, not NaN, so plan artifacts stay strict JSON."""
        peak = int(ledger_memory.get("peak", 0) or 0)
        rel = (abs(predicted_bytes - peak) / peak if peak > 0 else None)
        return {"predicted_bytes": int(predicted_bytes),
                "ledger_peak_bytes": peak, "rel_err": rel}


@dataclasses.dataclass
class Calibration:
    """Measured constants the step-time predictor runs on. Built from a
    short calibration run (``fit``), from a live telemetry window
    (``from_telemetry``), or synthetically in tests. Contains no
    wall-clock state: predictions from the same calibration are
    deterministic."""

    flops_per_s: float             # effective device FLOPs/s (measured)
    overhead_s: float = 0.0        # fixed per-step host/dispatch cost
    mem_bw_bytes_per_s: float = 0.0   # 0 = ignore the bytes roofline term
    axis_algbw_bytes_per_s: dict[str, float] = dataclasses.field(
        default_factory=dict)
    default_algbw_bytes_per_s: float = 0.0
    # per-axis collective bytes of the run the FLOPs rate was fitted on:
    # that rate already contains the baseline's exposed comm, so the
    # predictor charges only payload in EXCESS of these
    baseline_comm_bytes_by_axis: dict[str, float] = dataclasses.field(
        default_factory=dict)
    overlap_ratio: float = 0.71    # measured domino chunked-overlap ratio
    headroom_bytes: int = 0
    # observed wire width per axis (bytes/element, min over the axis's
    # collectives) from the HLO walk's dtype records — 4.0 on an
    # fp32-wire run, ~1.0 once qwZ/qgZ carry int8/fp8 payloads; report-
    # only (the byte-denominated terms above already use observed wire
    # bytes), kept so plan artifacts show WHICH wire the bounds were
    # measured at
    axis_wire_bytes_per_el: dict[str, float] = dataclasses.field(
        default_factory=dict)
    source: str = "synthetic"

    @classmethod
    def fit(cls, points: list[tuple[float, float]],
            **kw) -> "Calibration":
        """Least-squares ``t = overhead + flops / F`` from measured
        ``(flops, seconds)`` points. One point pins overhead to 0; two
        or more solve both (overhead clamped non-negative — a negative
        intercept means the run was noise-dominated, and a negative
        fixed cost would let predictions go negative)."""
        pts = [(float(f), float(t)) for f, t in points
               if f > 0 and t > 0]
        if not pts:
            raise ValueError("calibration needs >=1 (flops, seconds) "
                             "point with positive values")
        if len(pts) == 1:
            f, t = pts[0]
            return cls(flops_per_s=f / t, overhead_s=0.0,
                       source="measured", **kw)
        # closed-form 2-param least squares on (1, flops) -> seconds
        n = len(pts)
        sf = sum(f for f, _ in pts)
        st = sum(t for _, t in pts)
        sff = sum(f * f for f, _ in pts)
        sft = sum(f * t for f, t in pts)
        denom = n * sff - sf * sf
        if denom <= 0:           # identical flops: degenerate, average
            f, t = sf / n, st / n
            return cls(flops_per_s=f / t, overhead_s=0.0,
                       source="measured", **kw)
        slope = (n * sft - sf * st) / denom          # seconds per flop
        intercept = (st - slope * sf) / n
        if slope <= 0:           # bigger steps measured faster: noise;
            f, t = max(pts)      # fall back to the largest point's rate
            return cls(flops_per_s=f / t, overhead_s=0.0,
                       source="measured", **kw)
        return cls(flops_per_s=1.0 / slope,
                   overhead_s=max(intercept, 0.0),
                   source="measured", **kw)

    @classmethod
    def from_telemetry(cls, ledger, span_totals: dict, window_s: float,
                       name: str = "compiled_step",
                       **kw) -> "Calibration":
        """Calibrate from a live run's device-truth telemetry: the
        ledger's per-name dispatched FLOPs joined against the span
        tracer's measured seconds (``SpanTracer.totals_trimmed()``)
        give effective FLOPs/s; the HLO collective traffic over the
        window gives per-axis algbw lower bounds.

        Wire-dtype awareness (ISSUE 8 satellite): every byte figure
        here — the algbw floors, the per-axis comm baseline — comes
        from the HLO walk's decoded payload shapes, NOT from element
        counts at an assumed fp32 width. When the calibration run used
        quantized collectives (qwZ/qgZ), the bounds are measured in the
        int8/fp8 bytes that actually moved, so predict()'s
        excess-vs-baseline comparison stays unit-consistent against
        candidate facts (also HLO-observed bytes) regardless of which
        wire either side ran. The observed per-axis wire width is
        recorded in ``axis_wire_bytes_per_el`` for plan artifacts."""
        rates = ledger.effective_flops_per_s(span_totals)
        if name not in rates:
            raise ValueError(
                f"no measured window for ledger name {name!r}; "
                f"have {sorted(rates)}")
        axis_bw = {axis: row["algbw_bytes_per_s"] for axis, row
                   in ledger.axis_algbw_bounds(window_s).items()}
        wire = getattr(ledger, "axis_wire_bytes_per_el", None)
        if wire is not None:
            kw.setdefault("axis_wire_bytes_per_el", dict(wire()))
        kw.setdefault("headroom_bytes", hbm_headroom_bytes())
        # the fitted rate contains this executable's own exposed comm:
        # record its per-dispatch payload as the baseline so predict()
        # charges candidates only for the excess
        kw.setdefault("baseline_comm_bytes_by_axis",
                      dict(ledger.collective_bytes_by_axis(name)))
        return cls(flops_per_s=rates[name], overhead_s=0.0,
                   axis_algbw_bytes_per_s=axis_bw,
                   source=f"telemetry:{name}", **kw)

    def algbw(self, axis: str) -> float:
        bw = self.axis_algbw_bytes_per_s.get(axis, 0.0)
        return bw if bw > 0 else self.default_algbw_bytes_per_s

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class AOTFacts:
    """Compiler truth for one candidate's compiled step, collected by
    the planner through the ledger's shared ``lower_compiled()`` path
    (no dispatch): normalized ``cost_analysis()`` FLOPs/bytes,
    ``memory_analysis()`` peak, and the HLO collective payload bytes
    attributed per mesh axis."""

    flops: float = 0.0
    bytes_accessed: float = 0.0
    peak_hbm_bytes: int = 0
    memory: dict = dataclasses.field(default_factory=dict)
    collective_bytes_by_axis: dict[str, float] = dataclasses.field(
        default_factory=dict)
    collective_sites: int = 0

    def to_dict(self) -> dict:
        return {"flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "peak_hbm_bytes": self.peak_hbm_bytes,
                "memory": dict(self.memory),
                "collective_bytes_by_axis": dict(
                    self.collective_bytes_by_axis),
                "collective_sites": self.collective_sites}


class CostModel:
    """Step-time predictor: roofline compute plus exposed collective
    time. Pure arithmetic over :class:`AOTFacts` and a
    :class:`Calibration` — deterministic by construction (no clock, no
    RNG), so the planner's ranking is reproducible."""

    def __init__(self, calibration: Calibration):
        self.calibration = calibration

    def predict(self, facts: AOTFacts,
                overlap_ratio: Optional[float] = None) -> dict:
        """{step_s, compute_s, comm_s, comm_exposed_s}. ``comm_s`` sums
        per-axis payload relative to the calibration baseline's (whose
        exposure the fitted FLOPs rate already contains) over that
        axis's measured algbw lower bound: bytes in EXCESS charge time,
        bytes BELOW the baseline credit it back (a quantized-wire
        candidate moving a quarter of the calibration run's payload is
        honestly faster — the fitted rate paid for bytes this candidate
        never sends). Axes with no bandwidth estimate contribute 0 (the
        bound is honest: unknown bandwidth must not invent slowness or
        speed). The overlap ratio hides that fraction of collective
        time under compute; the credited step never drops below the
        fixed per-step overhead."""
        cal = self.calibration
        ov = cal.overlap_ratio if overlap_ratio is None else overlap_ratio
        ov = min(max(float(ov), 0.0), 1.0)
        compute = cal.overhead_s + facts.flops / cal.flops_per_s
        if cal.mem_bw_bytes_per_s > 0:
            compute = max(compute, cal.overhead_s
                          + facts.bytes_accessed / cal.mem_bw_bytes_per_s)
        comm = 0.0
        # union of candidate and baseline axes: an axis the candidate
        # eliminated entirely (absent from its HLO) must credit its
        # full baseline payload, not silently contribute 0
        axes = set(facts.collective_bytes_by_axis) | set(
            cal.baseline_comm_bytes_by_axis)
        for axis in sorted(axes):
            bw = cal.algbw(axis)
            nbytes = facts.collective_bytes_by_axis.get(axis, 0.0)
            excess = nbytes - cal.baseline_comm_bytes_by_axis.get(axis,
                                                                  0.0)
            if bw > 0 and excess != 0:
                comm += excess / bw
        exposed = (1.0 - ov) * comm
        step = max(compute + exposed, cal.overhead_s)
        return {"step_s": step, "compute_s": compute, "comm_s": comm,
                "comm_exposed_s": exposed, "overlap_ratio": ov}


def model_dims(model_config: Any) -> dict:
    """The ModelConfig fields the memory model's activation term needs,
    tolerant of absent attributes (adapter-wrapped modules)."""
    g = lambda a, d=0: int(getattr(model_config, a, d) or d)  # noqa: E731
    chunked = g("loss_chunk") > 0
    return {"hidden": g("hidden_size"), "num_layers": g("num_layers"),
            "vocab_size": 0 if chunked else g("vocab_size"),
            "seq_len": g("max_seq_len")}


def dtype_bytes(dtype: Any) -> int:
    try:
        import numpy as np
        return int(np.dtype(dtype).itemsize)
    except Exception:
        return 2 if "16" in str(dtype) else 4
