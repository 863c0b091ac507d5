"""Plan artifact (ISSUE 7 tentpole part 3).

The planner's output is a JSON document — ranked candidates with
predicted (and, for the measured top-K, observed) step time, per-axis
collective bytes, the calibration it was scored under, and the chosen
config diff — plus :meth:`Plan.apply`, which patches a base config
dict so users consume the planner's decision instead of hand-edited
configs. The artifact deliberately carries no
timestamps or RNG state: the same inputs produce a byte-identical
plan (the determinism contract tests assert).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional

PLAN_VERSION = 1


def deep_merge(base: dict, patch: dict) -> dict:
    """Recursive dict merge (patch wins; nested dicts merge key-wise).
    Returns a new dict; inputs are not mutated."""
    out = dict(base)
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def config_diff(base: dict, patched: dict, prefix: str = "") -> dict:
    """Flat {dotted.path: (base_value, new_value)} over leaves that
    differ — the human-readable "what did the planner change" view."""
    out: dict = {}
    keys = sorted(set(base) | set(patched))
    for k in keys:
        path = f"{prefix}.{k}" if prefix else str(k)
        a, b = base.get(k), patched.get(k)
        if isinstance(a, dict) and isinstance(b, dict):
            out.update(config_diff(a, b, path))
        elif isinstance(b, dict) and a is None:
            out.update(config_diff({}, b, path))
        elif a != b:
            out[path] = [a, b]
    return out


@dataclasses.dataclass
class Plan:
    """Ranked planner output + the chosen config patch."""

    n_devices: int
    model_info: dict
    calibration: dict
    candidates: list[dict]          # ranked; pruned ones carry "pruned"
    chosen_index: int               # into candidates; -1 = nothing ranked
    chosen_patch: dict              # ds-config patch of the winner
    base_config: dict               # the config the search started from
    version: int = PLAN_VERSION

    @property
    def chosen(self) -> Optional[dict]:
        if 0 <= self.chosen_index < len(self.candidates):
            return self.candidates[self.chosen_index]
        return None

    def ranked(self) -> list[dict]:
        """Candidates that were AOT-compiled and scored (not pruned,
        no compile error), in rank order."""
        return [c for c in self.candidates
                if not c.get("pruned") and not c.get("error")]

    def apply(self, config: Optional[dict] = None) -> dict:
        """Patch a config dict (default: the plan's own base) with the
        chosen candidate's diff. Deep-copies; reproduces the exact
        trial config the planner measured/compiled the winner under."""
        base = json.loads(json.dumps(
            config if config is not None else self.base_config))
        base.pop("autotuning", None)
        return deep_merge(base, self.chosen_patch)

    def diff(self) -> dict:
        """{dotted.path: [base, chosen]} of what apply() changes."""
        base = json.loads(json.dumps(self.base_config))
        base.pop("autotuning", None)
        return config_diff(base, self.apply())

    def to_dict(self) -> dict:
        return {"version": self.version,
                "n_devices": self.n_devices,
                "model_info": dict(self.model_info),
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
    def from_dict(cls, d: dict) -> "Plan":
        if d.get("version") != PLAN_VERSION:
            raise ValueError(
                f"plan version {d.get('version')!r} != {PLAN_VERSION}")
        return cls(n_devices=int(d["n_devices"]),
                   model_info=dict(d.get("model_info", {})),
                   calibration=dict(d.get("calibration", {})),
                   candidates=[dict(c) for c in d.get("candidates", [])],
                   chosen_index=int(d.get("chosen_index", -1)),
                   chosen_patch=dict(d.get("chosen_patch", {})),
                   base_config=dict(d.get("base_config", {})))

    @classmethod
    def load(cls, path: str) -> "Plan":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def summarize(plan: "Plan | dict") -> dict:
    """Headline numbers for a stage record / report: candidate counts,
    the winner's predicted vs measured step time, and the worst
    prediction error over the measured set."""
    d = plan.to_dict() if isinstance(plan, Plan) else dict(plan)
    cands = d.get("candidates", [])
    ranked = [c for c in cands if not c.get("pruned")
              and not c.get("error")]
    measured = [c for c in ranked
                if c.get("measured_step_ms") is not None]
    errs = [abs(c["predicted_step_ms"] - c["measured_step_ms"])
            / c["measured_step_ms"] for c in measured
            if c.get("measured_step_ms")]
    chosen = (cands[d["chosen_index"]]
              if 0 <= d.get("chosen_index", -1) < len(cands) else None)
    out: dict[str, Any] = {
        "n_candidates": len(cands),
        "n_ranked": len(ranked),
        "n_pruned": sum(1 for c in cands if c.get("pruned")),
        "n_measured": len(measured),
    }
    if errs:
        out["prediction_rel_err"] = round(max(errs), 4)
    if chosen is not None:
        out["chosen"] = chosen.get("label")
        out["predicted_step_ms"] = chosen.get("predicted_step_ms")
        if chosen.get("measured_step_ms") is not None:
            out["measured_step_ms"] = chosen["measured_step_ms"]
        if chosen.get("measured_tokens_per_sec") is not None:
            out["plan_tokens_per_sec"] = chosen["measured_tokens_per_sec"]
    return out
