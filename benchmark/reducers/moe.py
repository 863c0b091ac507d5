"""What a routed layer that holds a share of its experts counts (PR 31):
the program's ``ds_moe_held_*`` counters, read from its telemetry registry
at the end of a traced run. The compiled step returns the counts as device
scalars whether the run is traced or not; with telemetry on, the engine
feeds the registry from the step before the one it has just dispatched
(``deepspeed_tpu/moe/dispatch.py`` ``record_held_expert_counts``), so no
host callback sits in the program and the last step is not in the sums.
None where the program has no such counters (a program from before them,
or a model without such a layer)."""

from __future__ import annotations

from lib.reducers import reducer
from reducers.program import scope_ms_per_step

NAMES = ("ds_moe_held_rows_total", "ds_moe_held_calls_total",
         "ds_moe_dropped_rows_total", "ds_moe_held_experts",
         "ds_moe_held_tokens_step_min", "ds_moe_held_tokens_step_max")


def _counters() -> dict | None:
    try:
        from deepspeed_tpu.utils.telemetry_probe import active_telemetry
        tel = active_telemetry()
        reg = tel.get_registry() if tel is not None else None
        if reg is None:
            return None
        found = {name: reg.get(name) for name in NAMES}
        if None in found.values():
            return None
        return {name: metric.value() for name, metric in found.items()}
    except Exception:       # a program without these: nothing to read
        return None


def _held_tokens(ctx) -> float | None:
    """Mean rows a held expert a routed-layer call, once a run; None where
    nothing was counted or a routed row was not computed."""
    if "held_expert_tokens" in ctx:
        return ctx["held_expert_tokens"]
    c = _counters()
    mean = None
    if c and c["ds_moe_held_calls_total"] and c["ds_moe_held_experts"]:
        if c["ds_moe_dropped_rows_total"]:
            print(f"held_expert_tokens: {c['ds_moe_dropped_rows_total']} "
                  f"rows routed to held experts were NOT computed",
                  flush=True)
        else:
            calls = c["ds_moe_held_calls_total"]
            mean = c["ds_moe_held_rows_total"] / (
                calls * c["ds_moe_held_experts"])
            print(f"held_expert_tokens: mean {mean} over {calls} routed-"
                  f"layer calls; a step's mean between "
                  f"{c['ds_moe_held_tokens_step_min']} and "
                  f"{c['ds_moe_held_tokens_step_max']}", flush=True)
    ctx["held_expert_tokens"] = mean
    return mean


@reducer
def held_expert_tokens(ctx, args):
    """Mean rows (token, choice) a held expert a routed-layer call; None
    where the program counted nothing, or where it counted rows routed to
    a held expert and not computed (a dropped token is not a slower
    number, it is a wrong one: ``correct`` has no say here, so the metric
    goes missing instead)."""
    return _held_tokens(ctx)


@reducer
def held_experts_roofline_pct(ctx, args):
    """Least time the chip could take for the step's held-expert calls AT
    THE ROWS THE PROGRAM COUNTED (``moe_call_cost`` with ``rows``: the
    counted mean a held expert, times the experts held) over the device
    time per step under the scopes matching ``scope``. Where nothing was
    counted the cost is a balanced router's. None where the program names
    no such scope."""
    ms = scope_ms_per_step(ctx, {"pattern": args["scope"],
                                 "module": args["module"]})
    if not ms:
        return None
    m, arch = ctx["model"], ctx["arch"]
    local = max(1, ctx["sequences"] // ctx["chips"])
    counted = _held_tokens(ctx)
    rows = None if counted is None else counted * m["num_experts"]
    least = sum(arch.least_seconds(
        arch.moe_call_cost(m, local, ctx["seq_len"], backward=backward,
                           rows=rows), ctx["peaks"])[0]
        for backward in (False, True))
    return 100.0 * 1e3 * least / ms
