"""The held dispatch's sweep counted where it runs (PR 68): the trips of
its chunk loop a layer call, the row tiles with a live row against the
tiles its chunks hold, and the steps in which a call took a second trip,
from the counters the program keeps of the sweep's OWN loop bound
(``deepspeed_tpu/moe/sharded_moe.py`` ``_held_sweep`` ->
``models/stack.py`` ``_held_metrics`` -> ``moe/dispatch.py``
``record_held_expert_counts``: outputs of the compiled step, fed to the
registry one step behind, as ``reducers/moe.py``'s); and what such a step
costs on the device's clock, from the host event ``moe_extra_trip`` the
recorder leaves for each.

The counters are read from the live registry at the end of a traced run,
or from ``ctx["registry_snapshot"]`` (``tests/sweep_split.py``: the
``<cell>.metrics.json`` the run exported). Every reader returns None where
the program has no such counters or events (a parent from before them, a
model without held experts) and none raises for that.

Where the event lies: the recorder runs inside the ``step_boundary`` span
that follows ``train_batch`` of step N and reads step N - 1's counts (the
registry is one step behind), so the event of a step with an extra trip
lies, on the device's clock, inside or just before the run of step N; it
speaks of the run BEFORE that one. That holds where the caller waits for
every step (a traced run does); where it does not, ``clock_bracket`` has no
midpoint and nothing is read.
"""

from __future__ import annotations

import statistics

from lib import trace as tr
from lib.reducers import reducer
from reducers import program

# the reducers' names for what they read: registry name and labels
COUNTERS = {
    "rows": ("ds_moe_held_rows_total", {}),
    "calls": ("ds_moe_held_calls_total", {}),
    "steps": ("ds_moe_held_steps_total", {}),
    "trips": ("ds_moe_sweep_trips_total", {}),
    "live": ("ds_moe_sweep_tiles_total", {"state": "live"}),
    "swept": ("ds_moe_sweep_tiles_total", {"state": "swept"}),
    "tile": ("ds_moe_sweep_tile_rows", {}),
    "extra": ("ds_moe_sweep_extra_trip_steps_total", {}),
}
EVENT = r"^moe_extra_trip$"


def _snapshot(ctx) -> dict | None:
    if "registry_snapshot" in ctx:
        return ctx["registry_snapshot"]
    try:
        from deepspeed_tpu.utils.telemetry_probe import active_telemetry
        tel = active_telemetry()
        reg = tel.get_registry() if tel is not None else None
        return None if reg is None else reg.snapshot()
    except Exception:       # a program without a registry: nothing to read
        return None


def value(snapshot: dict, name: str, **labels):
    """One number of a registry's snapshot, or None where it is not there."""
    return next((v["value"] for v in snapshot.get(name, {}).get("values", ())
                 if v["labels"] == labels), None)


def counters(ctx) -> dict | None:
    """``COUNTERS`` as numbers, read once a run; None where one is not
    there (a counter at 0 is there)."""
    if "sweep_counters" not in ctx:
        snap = _snapshot(ctx) or {}
        found = {key: value(snap, name, **labels)
                 for key, (name, labels) in COUNTERS.items()}
        ctx["sweep_counters"] = None if None in found.values() else found
    return ctx["sweep_counters"]


@reducer
def sweep_trips_per_call(ctx, args):
    """Trips of the chunk loop a routed-layer call, over the finished
    steps: 1.0 where no share was sent more than a chunk holds."""
    c = counters(ctx)
    return c["trips"] / c["calls"] if c and c["calls"] else None


@reducer
def sweep_extra_trip_steps_pct(ctx, args):
    """Share of the finished steps in which some call took a second trip."""
    c = counters(ctx)
    return 100.0 * c["extra"] / c["steps"] if c and c["steps"] else None


@reducer
def sweep_tile_pad_share_pct(ctx, args):
    """100 x (1 - rows / (live tiles x rows a tile)): the padding inside
    the row tiles the kernels run."""
    c = counters(ctx)
    if not c or not c["live"] or not c["tile"]:
        return None
    return 100.0 * (1.0 - c["rows"] / (c["live"] * c["tile"]))


@reducer
def sweep_dead_tile_share_pct(ctx, args):
    """100 x (1 - live tiles / swept tiles): tiles a chunk holds, gathers
    and adds, and no kernel runs."""
    c = counters(ctx)
    if not c or not c["swept"]:
        return None
    return 100.0 * (1.0 - c["live"] / c["swept"])


def traced_steps(ctx, args) -> list[dict] | None:
    """One row a complete run of ``module`` in the trace, in time order:
    ``device_ms`` (the ops' union inside it, mean over chips) and
    ``extra_trip`` (an event matching ``EVENT`` speaks of it). A run that
    was under way when the trace began (it starts before the first
    ``train_batch`` span the trace holds: a profiler started by hand inside
    the window) is cut off at its front and is left out, as the last run
    is. Read once; None without a trace, a device or a bracket's
    midpoint."""
    if "sweep_steps" not in ctx:
        t = ctx.get("trace")
        br = None if t is None else program._bracket(ctx, args)
        rows = None
        if t is not None and t.chips() and br and br["midpoint"] is not None:
            runs = [(a, b) for _, a, b in t.modules(t.chips()[0],
                                                    args["module"])]
            busy = tr.per_step_seconds(
                t, args["module"], lambda c: tr.merge(tr._iv(t.ops(c))))
            rows = [{"device_ms": 1e3 * s, "extra_trip": False}
                    for s in busy]
            for _, at, _ in t.host_spans(EVENT):
                at += br["midpoint"]
                # the run the recorder ran beside (or just before): the
                # step AFTER the one whose counts it had in hand
                beside = next((i for i, (_, b) in enumerate(runs)
                               if b > at), len(runs))
                if 1 <= beside <= len(rows):
                    rows[beside - 1]["extra_trip"] = True
            seen = min((a for _, a, _ in t.host_spans(r"^train_batch$")),
                       default=0.0) + br["midpoint"]
            rows = rows[sum(a < seen for a, _ in runs):]
        ctx["sweep_steps"] = rows
    return ctx["sweep_steps"]


@reducer
def extra_trip_cost_ms(ctx, args):
    """Device ms of the traced steps a ``moe_extra_trip`` event speaks of
    (median), less the median of the traced steps none speaks of; None
    where no traced step took an extra trip (or every one did)."""
    rows = traced_steps(ctx, args) or ()
    long = [r["device_ms"] for r in rows if r["extra_trip"]]
    rest = [r["device_ms"] for r in rows if not r["extra_trip"]]
    if not long or not rest:
        return None
    return statistics.median(long) - statistics.median(rest)
