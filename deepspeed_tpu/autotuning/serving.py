"""Offline serving autotuner (ISSUE 19 tentpole, offline half).

The train planner (ISSUE 7) ranks mesh/batch/remat candidates against
the ledger's compiled truth; this module does the same for the SERVING
stack: a deterministic :class:`ServingCandidate` grid over the knobs
nobody was turning — fused K x chain depth (``max_inflight_dispatches``)
x ring/plain admission x speculative ``draft_len`` x KV dtype/block
budget x admission bound (shed depth) x replica/disaggregation
topology — scored by :class:`ServingCostModel` against a declarative
:class:`TrafficModel` (arrival rate, prompt/output length mix,
prefix share) and emitted as a ranked :class:`ServingPlan`
(``serving_plan.json``) whose :meth:`ServingPlan.apply` reproduces the
chosen ``ServingConfig`` / ``RaggedInferenceEngineConfig`` exactly, the
way train plans already do.

The cost model is pure host arithmetic over a
:class:`ServingCalibration` (per-tick decode seconds + host dispatch
RTT, measured once or synthesized in tests) — no clock, no RNG, no jax
(the ``autotuning/`` host-only audit covers this file), so the same
inputs rank byte-identically. The queueing/chaining terms encode the
mechanisms the serving loop actually has:

- the host dispatch RTT amortizes over ``k * chain_depth`` ticks
  (chained dispatches overlap host drain with device compute; ring
  mode reads the token ring ONCE per chain) — deep chains and long
  drafts therefore WIN at low load (lower ITL);
- a chain only admits at its boundary, so TTFT carries half a chain
  span of admission latency, and the chain's tail dispatches overrun
  finished rows (device no-ops — the honest price ``_step_ring``
  documents), wasting capacity exactly when capacity binds — deep
  chains therefore LOSE at saturation;
- speculative drafts multiply tokens/tick by ``1 + draft_len *
  acceptance`` but pay the verify-forward compute and widen the KV
  reserve horizon to ``k * (1 + draft_len)`` blocks/row, shrinking the
  resident batch at a fixed block budget — long drafts also lose at
  saturation;
- the queue-wait term is the M/M/1-shaped ``rho / (1 - rho)`` over the
  candidate's effective service rate, capped by the admission bound
  (requests past it shed — fast-fail, not silent wait): unbounded
  admission at three times capacity put 11.2 s of queue wait in an
  open-loop CPU run before the chip, the failure mode this planner
  exists to close.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
from typing import Any, Optional

from .plan import config_diff, deep_merge

SERVING_PLAN_VERSION = 1

# KV cache storage bytes per element by pool dtype — mirrors
# kv_cache.dtype semantics (fp16 reference; int8/fp8 halve the payload
# and carry per-block scales, ~0.53x in practice per the kvquant bench)
KV_DTYPE_BYTES = {"fp16": 2.0, "bf16": 2.0, "int8": 1.06, "fp8": 1.06}


@dataclasses.dataclass(frozen=True)
class TrafficModel:
    """Declarative description of the traffic a serving plan is ranked
    against. Lengths are token counts; ``prefix_share`` is the fraction
    of prompt tokens expected warm in the prefix cache (shared system
    prompts); ``draft_acceptance`` is the expected prompt-lookup draft
    acceptance rate on this traffic (0 = drafts never hit)."""

    arrival_rate_rps: float
    prompt_tokens: int = 128
    output_tokens: int = 64
    prefix_share: float = 0.0
    slo_ttft_ms: float = 1000.0
    slo_itl_ms: float = 50.0
    draft_acceptance: float = 0.3

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TrafficModel":
        return cls(**{k: d[k] for k in
                      (f.name for f in dataclasses.fields(cls))
                      if k in d})


@dataclasses.dataclass(frozen=True)
class ServingCalibration:
    """Measured constants the serving predictor runs on (the serving
    analogue of :class:`~.cost_model.Calibration`): device compute per
    fused decode tick at the reference batch, the host dispatch+drain
    RTT a chain amortizes, and chunked-prefill throughput. Contains no
    wall-clock state — predictions are deterministic."""

    decode_tick_s: float            # device seconds per fused tick
    dispatch_overhead_s: float      # host RTT per dispatch/drain pair
    prefill_tokens_per_s: float = 50_000.0
    # relative extra compute per tick for each drafted token's verify
    # forward slot (the 1 + draft_len wide verify pass)
    draft_verify_cost: float = 0.15
    source: str = "synthetic"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True, order=True)
class ServingCandidate:
    """One point of the serving grid. Frozen + ordered so the grid
    sorts deterministically (the ranking tiebreak is the candidate
    itself, never dict order)."""

    k_steps: int = 8
    chain_depth: int = 2
    ring: bool = False              # fused_admission (in-graph swap)
    draft_len: int = 0              # 0 = speculative decode off
    kv_dtype: str = "fp16"
    kv_blocks: int = 0              # 0 = keep the base pool size
    shed_depth: int = 0             # admission bound (0 = unbounded)
    replicas: int = 1
    disagg: bool = False            # prefill/decode split

    def label(self) -> str:
        parts = [f"k{self.k_steps}", f"d{self.chain_depth}",
                 "ring" if self.ring else "chain"]
        if self.draft_len:
            parts.append(f"spec{self.draft_len}")
        parts.append(self.kv_dtype)
        if self.kv_blocks:
            parts.append(f"kv{self.kv_blocks}")
        if self.shed_depth:
            parts.append(f"q{self.shed_depth}")
        if self.replicas > 1:
            parts.append(f"r{self.replicas}")
        if self.disagg:
            parts.append("disagg")
        return "-".join(parts)

    def config_patch(self) -> dict:
        """The ds-config patch reproducing this candidate: the
        ``inference_v2`` engine block, the ``serving`` front-end block,
        and (for multi-replica/disagg points) the ``router`` block —
        exactly the dicts ``RaggedInferenceEngineConfig`` /
        ``ServingConfig`` / ``RouterConfig`` parse."""
        eng: dict[str, Any] = {
            "fused_decode_steps": self.k_steps,
            "max_inflight_dispatches": self.chain_depth,
            "fused_admission": bool(self.ring),
        }
        if self.draft_len > 0:
            eng["speculative"] = {"enabled": True,
                                  "draft_len": self.draft_len}
        if self.kv_dtype not in ("fp16", "bf16"):
            eng["kv_cache"] = {"enabled": True, "dtype": self.kv_dtype}
        if self.kv_blocks:
            eng["num_kv_blocks"] = self.kv_blocks
        srv: dict[str, Any] = {"k_steps": self.k_steps}
        if self.shed_depth:
            srv["shed_queue_depth"] = self.shed_depth
        patch = {"inference_v2": eng, "serving": srv}
        if self.replicas > 1 or self.disagg:
            patch["router"] = {
                "disaggregation": {"enabled": bool(self.disagg)}}
            patch["replicas"] = self.replicas
        return patch

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["label"] = self.label()
        return d


class ServingCostModel:
    """Deterministic TTFT/ITL/goodput predictor over one candidate and
    one traffic model (see module docstring for the mechanism terms).
    All returned times are SECONDS; the plan rows convert to ms."""

    def __init__(self, calibration: ServingCalibration, *,
                 max_rows: int = 8, kv_block_size: int = 8,
                 base_kv_blocks: int = 128):
        self.cal = calibration
        self.max_rows = max(1, int(max_rows))
        self.kv_block_size = max(1, int(kv_block_size))
        self.base_kv_blocks = max(1, int(base_kv_blocks))

    # -- capacity ------------------------------------------------------
    def resident_rows(self, cand: ServingCandidate,
                      traffic: TrafficModel) -> float:
        """Decode rows resident at steady state: bounded by the engine
        row count AND the KV pool. A quantized pool fits more blocks
        per byte (the candidate's kv_blocks is taken as configured —
        the grid builder already scaled budgets per dtype); the
        speculative reserve horizon ``k * (1 + draft_len)`` holds extra
        blocks per row for the whole residency."""
        blocks = cand.kv_blocks or self.base_kv_blocks
        tokens_per_row = (traffic.prompt_tokens + traffic.output_tokens
                          + cand.k_steps * (1 + cand.draft_len))
        blocks_per_row = math.ceil(tokens_per_row / self.kv_block_size)
        return max(1.0, min(float(self.max_rows),
                            blocks / max(blocks_per_row, 1)))

    def tick_seconds(self, cand: ServingCandidate) -> float:
        """Wall seconds per fused decode tick with the chain's host
        amortization: device compute (drafts widen the verify forward)
        plus the dispatch RTT spread over the chain's ticks. Ring mode
        reads the device token ring once per CHAIN instead of once per
        dispatch — its host share shrinks by the depth again."""
        cal = self.cal
        compute = cal.decode_tick_s * (
            1.0 + cand.draft_len * cal.draft_verify_cost)
        span = cand.k_steps * cand.chain_depth
        host = cal.dispatch_overhead_s / max(span, 1)
        if not cand.ring:
            # chain mode still syncs one drain per dispatch; only the
            # enqueue side pipelines — half the RTT stays exposed
            host = cal.dispatch_overhead_s * (
                0.5 / cand.k_steps + 0.5 / max(span, 1))
        return compute + host

    def predict(self, cand: ServingCandidate,
                traffic: TrafficModel) -> dict:
        """{ttft_s, itl_s, queue_wait_s, goodput_rps, shed_frac,
        rho, capacity_rps, tokens_per_sec} — deterministic arithmetic
        only (the determinism contract test asserts)."""
        cal = self.cal
        tick = self.tick_seconds(cand)
        eff_tok = 1.0 + cand.draft_len * traffic.draft_acceptance
        itl = tick / eff_tok
        rows = self.resident_rows(cand, traffic)

        # raw decode capacity, then the chain-tail overrun tax: a
        # request's last chain runs to the chain boundary, so on
        # average (depth - 1)/2 dispatches of k*(1+draft) device slots
        # no-op past its final token (ring mode's documented price;
        # chain mode declines to extend, paying boundary idleness
        # instead — same first-order waste)
        out = max(traffic.output_tokens, 1)
        overrun = (cand.chain_depth - 1) / 2.0 * cand.k_steps * (
            1 + cand.draft_len)
        waste = overrun / (out + overrun)
        tok_rate = rows * eff_tok / tick * (1.0 - waste)

        # chunked prefill steals decode time co-located; the
        # disaggregated split moves it off the decode mesh entirely
        cold = traffic.prompt_tokens * (1.0 - traffic.prefix_share)
        prefill_s = cold / max(cal.prefill_tokens_per_s, 1.0)
        prefill_frac = 0.0
        if not cand.disagg:
            prefill_frac = min(0.9, traffic.arrival_rate_rps * prefill_s
                               / max(cand.replicas, 1))
        tok_rate *= (1.0 - prefill_frac)
        tok_rate *= max(cand.replicas, 1)

        capacity_rps = tok_rate / out
        offered = traffic.arrival_rate_rps
        rho = offered / max(capacity_rps, 1e-9)

        # M/M/1-shaped queue wait over the per-request service time,
        # capped by the admission bound: with shedding, at most
        # shed_depth requests ever wait ahead of an admitted one
        svc_s = out / max(tok_rate, 1e-9)
        if rho < 1.0:
            queue_wait = rho / (1.0 - rho) * svc_s
        else:
            queue_wait = float("inf")
        shed_frac = max(0.0, 1.0 - 1.0 / rho) if cand.shed_depth else 0.0
        if cand.shed_depth:
            queue_wait = min(queue_wait, cand.shed_depth * svc_s)

        # admission happens at chain boundaries: half a chain span of
        # latency before the first prefill can start
        boundary_s = cand.k_steps * cand.chain_depth * tick / 2.0
        ttft = queue_wait + boundary_s + prefill_s + tick

        # goodput: admitted traffic, discounted by how far the
        # predicted tails overshoot the SLOs (smooth, monotone — a
        # candidate inside both budgets keeps its full admitted rate)
        admitted = min(offered * (1.0 - shed_frac), capacity_rps)
        slo_ttft = traffic.slo_ttft_ms / 1e3
        slo_itl = traffic.slo_itl_ms / 1e3
        factor = 1.0
        if slo_ttft > 0 and ttft > 0:
            factor *= min(1.0, slo_ttft / ttft)
        if slo_itl > 0 and itl > 0:
            factor *= min(1.0, slo_itl / itl)
        goodput = admitted * factor
        return {"ttft_s": ttft, "itl_s": itl,
                "queue_wait_s": queue_wait, "boundary_s": boundary_s,
                "prefill_s": prefill_s, "rho": rho,
                "capacity_rps": capacity_rps, "shed_frac": shed_frac,
                "tokens_per_sec": tok_rate, "goodput_rps": goodput,
                "resident_rows": rows}


@dataclasses.dataclass
class ServingPlan:
    """Ranked serving-planner output + the chosen config patch — the
    serving analogue of :class:`~.plan.Plan` (same JSON artifact
    discipline: no timestamps, no RNG state, byte-identical from the
    same inputs). ``kind`` tags the document so
    ``tools/autotune_report.py`` renders the right table."""

    traffic: dict
    calibration: dict
    candidates: list[dict]          # ranked; pruned ones carry "pruned"
    chosen_index: int
    chosen_patch: dict
    base_config: dict               # {"inference_v2": ..., "serving": ...}
    version: int = SERVING_PLAN_VERSION
    kind: str = "serving"

    @property
    def chosen(self) -> Optional[dict]:
        if 0 <= self.chosen_index < len(self.candidates):
            return self.candidates[self.chosen_index]
        return None

    def ranked(self) -> list[dict]:
        return [c for c in self.candidates
                if not c.get("pruned") and not c.get("error")]

    def apply(self, config: Optional[dict] = None) -> dict:
        """Patch a base config dict (default: the plan's own) with the
        winner. Deep-copies; reproduces the exact
        ``{"inference_v2": ..., "serving": ..., ["router": ...]}``
        dicts the planner scored the winner under."""
        base = json.loads(json.dumps(
            config if config is not None else self.base_config))
        base.pop("autotuning", None)
        return deep_merge(base, self.chosen_patch)

    def engine_config(self, config: Optional[dict] = None):
        """The chosen ``RaggedInferenceEngineConfig`` — constructed,
        not a dict, so ``apply()`` provably reproduces it."""
        from ..inference.v2 import RaggedInferenceEngineConfig
        return RaggedInferenceEngineConfig(
            **self.apply(config).get("inference_v2", {}))

    def serving_config(self, config: Optional[dict] = None):
        """The chosen ``ServingConfig``."""
        from ..serving import ServingConfig
        return ServingConfig(**self.apply(config).get("serving", {}))

    def diff(self) -> dict:
        base = json.loads(json.dumps(self.base_config))
        base.pop("autotuning", None)
        return config_diff(base, self.apply())

    def to_dict(self) -> dict:
        return {"version": self.version, "kind": self.kind,
                "traffic": dict(self.traffic),
                "calibration": dict(self.calibration),
                "candidates": [dict(c) for c in self.candidates],
                "chosen_index": self.chosen_index,
                "chosen_patch": dict(self.chosen_patch),
                "config_diff": self.diff(),
                "base_config": dict(self.base_config)}

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_json())
            f.write("\n")
        return path

    @classmethod
    def from_dict(cls, d: dict) -> "ServingPlan":
        if d.get("version") != SERVING_PLAN_VERSION \
                or d.get("kind") != "serving":
            raise ValueError(
                f"not a v{SERVING_PLAN_VERSION} serving plan: "
                f"version={d.get('version')!r} kind={d.get('kind')!r}")
        return cls(traffic=dict(d.get("traffic", {})),
                   calibration=dict(d.get("calibration", {})),
                   candidates=[dict(c) for c in d.get("candidates", [])],
                   chosen_index=int(d.get("chosen_index", -1)),
                   chosen_patch=dict(d.get("chosen_patch", {})),
                   base_config=dict(d.get("base_config", {})))

    @classmethod
    def load(cls, path: str) -> "ServingPlan":
        with open(path) as f:
            return cls.from_dict(json.load(f))


class ServingPlanner:
    """Deterministic grid -> memory prune -> cost-model ranking ->
    :class:`ServingPlan`. The search space comes from the
    ``autotuning.serving_*`` config lists (see
    :class:`~.config.AutotuningConfig`); the base engine/serving config
    is always a grid point, so a plan can never choose something worse
    than the hand-tuned start under its own model."""

    def __init__(self, cfg, calibration: ServingCalibration,
                 traffic: TrafficModel, *,
                 base_engine_config: Optional[dict] = None,
                 base_serving_config: Optional[dict] = None,
                 max_rows: int = 8, kv_block_size: int = 8,
                 base_kv_blocks: int = 128,
                 kv_budget_bytes: int = 0,
                 kv_bytes_per_token_fp16: float = 0.0):
        self.cfg = cfg
        self.calibration = calibration
        self.traffic = traffic
        self.base_engine = dict(base_engine_config or {})
        self.base_serving = dict(base_serving_config or {})
        self.max_rows = int(max_rows)
        self.kv_block_size = int(kv_block_size)
        self.base_kv_blocks = int(base_kv_blocks)
        self.kv_budget_bytes = int(kv_budget_bytes)
        self.kv_bytes_per_token_fp16 = float(kv_bytes_per_token_fp16)
        self.model = ServingCostModel(
            calibration, max_rows=max_rows,
            kv_block_size=kv_block_size, base_kv_blocks=base_kv_blocks)

    # -- grid ----------------------------------------------------------
    def candidates(self) -> list[ServingCandidate]:
        """The deterministic candidate list: sorted cartesian product
        of the config's serving grids, the base point first (when
        ``include_base``), duplicates dropped."""
        c = self.cfg
        grid = sorted(set(itertools.product(
            sorted(set(int(k) for k in c.serving_k_steps)),
            sorted(set(int(d) for d in c.serving_chain_depths)),
            sorted(set(bool(r) for r in c.serving_ring_modes)),
            sorted(set(int(l) for l in c.serving_draft_lens)),
            sorted(set(str(d) for d in c.serving_kv_dtypes)),
            sorted(set(int(b) for b in c.serving_kv_blocks)),
            sorted(set(int(q) for q in c.serving_shed_depths)),
            sorted(set(int(r) for r in c.serving_replicas)),
            sorted(set(bool(d) for d in c.serving_disagg)))))
        out = []
        if c.include_base:
            out.append(self._base_candidate())
        for (k, d, ring, dl, kvd, kvb, q, rep, dis) in grid:
            cand = ServingCandidate(
                k_steps=k, chain_depth=d, ring=ring, draft_len=dl,
                kv_dtype=kvd, kv_blocks=kvb, shed_depth=q,
                replicas=rep, disagg=dis)
            if cand not in out:
                out.append(cand)
        return out

    def _base_candidate(self) -> ServingCandidate:
        eng, srv = self.base_engine, self.base_serving
        kv = eng.get("kv_cache", {}) or {}
        sp = eng.get("speculative", {}) or {}
        return ServingCandidate(
            k_steps=int(eng.get("fused_decode_steps", 8) or 8),
            chain_depth=int(eng.get("max_inflight_dispatches", 2)),
            ring=bool(eng.get("fused_admission", False)),
            draft_len=(int(sp.get("draft_len", 0))
                       if sp.get("enabled") else 0),
            kv_dtype=str(kv.get("dtype", "fp16")
                         if kv.get("enabled") else "fp16"),
            kv_blocks=int(eng.get("num_kv_blocks", 0) or 0),
            shed_depth=int(srv.get("shed_queue_depth", 0) or 0))

    def prune(self, cand: ServingCandidate) -> Optional[str]:
        """Reason string when a candidate cannot run, else None. The
        only hard constraint is the KV pool byte budget (0 = unknown =
        always fits, the MemoryModel convention)."""
        if self.kv_budget_bytes > 0 and self.kv_bytes_per_token_fp16 > 0:
            blocks = cand.kv_blocks or self.base_kv_blocks
            scale = (KV_DTYPE_BYTES.get(cand.kv_dtype, 2.0)
                     / KV_DTYPE_BYTES["fp16"])
            nbytes = (blocks * self.kv_block_size
                      * self.kv_bytes_per_token_fp16 * scale)
            if nbytes > self.kv_budget_bytes:
                return (f"kv pool {nbytes / 2 ** 20:.0f} MiB over "
                        f"budget {self.kv_budget_bytes / 2 ** 20:.0f}"
                        " MiB")
        return None

    # -- ranking -------------------------------------------------------
    def plan(self, plan_path: str = "") -> ServingPlan:
        rows: list[dict] = []
        scored: list[tuple] = []
        for cand in self.candidates():
            row = cand.to_dict()
            reason = self.prune(cand)
            if reason is not None:
                row["pruned"] = reason
                rows.append(row)
                continue
            pred = self.model.predict(cand, self.traffic)
            row["predicted_ttft_ms"] = round(pred["ttft_s"] * 1e3, 3) \
                if math.isfinite(pred["ttft_s"]) else None
            row["predicted_itl_ms"] = round(pred["itl_s"] * 1e3, 4)
            row["predicted_queue_wait_ms"] = (
                round(pred["queue_wait_s"] * 1e3, 3)
                if math.isfinite(pred["queue_wait_s"]) else None)
            row["predicted_goodput_rps"] = round(pred["goodput_rps"], 4)
            row["predicted_shed_frac"] = round(pred["shed_frac"], 4)
            row["predicted_rho"] = round(pred["rho"], 4) \
                if math.isfinite(pred["rho"]) else None
            row["predicted_tokens_per_sec"] = round(
                pred["tokens_per_sec"], 2)
            rows.append(row)
            # rank: goodput desc, then queue wait, ITL, and the ordered
            # candidate itself — a full deterministic order
            scored.append((-pred["goodput_rps"], pred["queue_wait_s"],
                           pred["itl_s"], cand, row))
        scored.sort(key=lambda t: t[:3] + (t[3],))
        ranked_rows = [t[4] for t in scored]
        for rank, row in enumerate(ranked_rows):
            row["rank"] = rank
        # candidates list in rank order, pruned rows trailing
        ordered = ranked_rows + [r for r in rows if r.get("pruned")]
        chosen_index = 0 if ranked_rows else -1
        chosen_patch = {}
        if ranked_rows:
            chosen_patch = scored[0][3].config_patch()
        plan = ServingPlan(
            traffic=self.traffic.to_dict(),
            calibration=self.calibration.to_dict(),
            candidates=ordered, chosen_index=chosen_index,
            chosen_patch=chosen_patch,
            base_config={"inference_v2": dict(self.base_engine),
                         "serving": dict(self.base_serving)})
        if plan_path:
            plan.save(plan_path)
        return plan


def summarize_serving(plan: "ServingPlan | dict") -> dict:
    """Headline numbers for a bench stage record / report row."""
    d = plan.to_dict() if isinstance(plan, ServingPlan) else dict(plan)
    cands = d.get("candidates", [])
    ranked = [c for c in cands if not c.get("pruned")
              and not c.get("error")]
    chosen = (cands[d["chosen_index"]]
              if 0 <= d.get("chosen_index", -1) < len(cands) else None)
    out: dict[str, Any] = {
        "n_candidates": len(cands),
        "n_ranked": len(ranked),
        "n_pruned": sum(1 for c in cands if c.get("pruned")),
    }
    if chosen is not None:
        out["chosen"] = chosen.get("label")
        for k in ("predicted_ttft_ms", "predicted_itl_ms",
                  "predicted_goodput_rps", "measured_goodput_rps"):
            if chosen.get(k) is not None:
                out[k] = chosen[k]
    return out
