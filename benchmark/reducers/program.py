"""Reducers that read what the PROGRAM names (PR 24): its device scopes
(through the executable ledger's ``<prefix>.op_scopes.json``), its set-up
spans, its compile seconds by phase, and the host and device clocks of
one trace; and two lists a kind may add to a traced run's ``breakdown``
(``device_scopes``, ``idle_gaps_aligned``).

Every reader returns None where the program has no such scope, span or
counter (a parent commit from before PR 24 has none), and the metric is
then left out; none raises for that.

context keys read here, beside those of ``lib.reducers``: ``op_scopes_path``
(the exported map {ledger entry: {HLO instruction name: scope path}}),
``ledger_entry`` (the kind's entry in that map: its step program),
``program`` (``lib.telemetry.program_state()`` once nothing more compiles: at
the start of the window or after it),
``program_at_build`` (the same when the engine was built), ``setup_s``,
``pre_build_s`` (process start to the call that builds the engine),
``step_rows`` (steptrace's rows of the window's steps).

A v5e trace event carries no ``op_name``: its name is the HLO instruction
text without metadata and its stats are two device times (looked at on
the chip, PR 24), so an event is joined to a scope by its instruction
name through the map the program exports. Scope paths look like
``fwd:ds.layers/ds.attn/ds.flash_fwd``; see
``deepspeed_tpu/telemetry/scopes.py``.
"""

from __future__ import annotations

import json
import re
import statistics

from lib import trace as tr
from lib.reducers import reducer

_INSTRUCTION = re.compile(r"^%?([^\s=]+)")


# -- device scopes -----------------------------------------------------------
def _scope_map(ctx) -> dict | None:
    if "op_scopes" not in ctx:
        path = ctx.get("op_scopes_path")
        maps = {}
        if path:
            with open(path) as f:
                maps = json.load(f)
        ctx["op_scopes"] = maps.get(ctx.get("ledger_entry"))
    return ctx["op_scopes"]


def _scoped_ops(ctx, chip) -> list:
    """(start, end, scope path) of the device ops of ``chip`` the map
    knows; read once per chip, every scope metric asks for it."""
    cache = ctx.setdefault("_scoped_ops", {})
    if chip not in cache:
        scope_map = _scope_map(ctx)
        cache[chip] = [
            (a, b, scope_map[name]) for text, a, b in ctx["trace"].ops(chip)
            if (name := _INSTRUCTION.match(text).group(1)) in scope_map]
    return cache[chip]


def _matching(ctx, chip, rx):
    """Merged intervals of the device ops of ``chip`` whose scope path
    matches ``rx``. A ``while`` op counts with its own scope, so the
    little waits between the ops of its body count with it."""
    return tr.merge((a, b) for a, b, path in _scoped_ops(ctx, chip)
                    if rx.search(path))


@reducer
def scope_ms_per_step(ctx, args):
    """Device time of the ops under the scopes matching ``pattern`` inside
    one run of ``module``: merged intervals, median over the complete
    steps, mean over chips (as ``device_op_ms_per_step``). With
    ``outside`` in place of ``pattern``: the device time under NO scope
    matching it (all op intervals less the matching ones)."""
    t, scope_map = ctx.get("trace"), _scope_map(ctx)
    if t is None or not scope_map:
        return None
    rx = re.compile(args.get("pattern") or args["outside"])

    def intervals(c):
        inside = _matching(ctx, c, rx)
        if "outside" in args:
            return tr.subtract(tr.merge(tr._iv(t.ops(c))), inside)
        return inside

    xs = tr.per_step_seconds(t, args["module"], intervals)
    return 1e3 * statistics.median(xs) if xs else None


def device_scopes(ctx, n: int = 10) -> list[list]:
    """Seconds of chip 0's leaf device ops by scope path over the traced
    window, [[scope path, seconds]] by decreasing seconds ("" = under no
    scope). Beside ``trace.top_ops``, which has them by op name."""
    t, scope_map = ctx.get("trace"), _scope_map(ctx)
    if t is None or not scope_map or not t.chips():
        return []
    acc: dict[str, float] = {}
    for text, a, b in t.ops(t.chips()[0], leaves=True):
        path = scope_map.get(_INSTRUCTION.match(text).group(1), "")
        acc[path] = acc.get(path, 0.0) + (b - a)
    return [[k, v] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


# -- one clock for host spans and the device trace ---------------------------
NEAR = 0.05     # s; a step is far longer than the clocks are apart


def clock_bracket(t, module: str, launch: str) -> dict | None:
    """Limits on the device clock minus the host clock, in seconds, from
    pairs of events whose order in real time is known:

    - a run of ``module`` starts on the device no earlier than the host
      began to launch it (the earliest event matching ``launch`` inside
      the ``compiled_step`` span, or the span itself): device start
      minus launch start is at least the offset, so the LEAST over the
      steps is an upper limit;
    - when the caller blocks on every step, a run ends no later than the
      next ``train_batch`` span begins: device end minus that start is
      at most the offset, so the GREATEST over the steps is a lower
      limit. If it lies above the upper limit the caller did not block;
      the lower limit is then dropped (``lower`` None, no midpoint).
    """
    chips = t.chips()
    if not chips:
        return None
    runs = tr.complete_steps(t, module, chips[0])
    steps = t.host_spans(r"^compiled_step$")
    batches = t.host_spans(r"^train_batch$")
    launches = t.host_spans(launch)
    if not runs or not steps:
        return None
    uppers, lowers = [], []
    for d0, d1 in runs:
        _, s0, s1 = min(steps, key=lambda e: abs(e[1] - d0))
        if abs(s0 - d0) < NEAR:
            inside = [e[1] for e in launches if s0 <= e[1] <= s1]
            uppers.append(d0 - min(inside, default=s0))
        if batches:
            _, b0, _ = min(batches, key=lambda e: abs(e[1] - d1))
            if abs(b0 - d1) < NEAR:
                lowers.append(d1 - b0)
    if not uppers:
        return None
    upper = min(uppers)
    lower = max(lowers) if lowers else None
    if lower is not None and lower > upper:
        lower = None
    return {"upper": upper, "lower": lower, "steps": len(uppers),
            "midpoint": None if lower is None else (upper + lower) / 2}


def _bracket(ctx, args):
    if "clock_bracket" not in ctx:
        t = ctx.get("trace")
        ctx["clock_bracket"] = None if t is None else clock_bracket(
            t, args["module"], args["launch"])
    return ctx["clock_bracket"]


@reducer
def clock_bracket_us(ctx, args):
    """Width of the bracket on the device-minus-host clock offset, in
    microseconds: how exactly an idle gap can be pinned to a host span."""
    br = _bracket(ctx, args)
    if br is None or br["lower"] is None:
        return None
    return 1e6 * (br["upper"] - br["lower"])


def idle_gaps_aligned(ctx, span_pattern: str, n: int = 10) -> list[list]:
    """``trace.idle_gaps_by_span`` with the host's spans moved onto the
    device's clock by the bracket's midpoint."""
    t, br = ctx.get("trace"), ctx.get("clock_bracket")
    if t is None or not br or br["midpoint"] is None:
        return []
    off = br["midpoint"]
    moved = tr.Trace(t.devices, {
        line: [(name, a + off, b + off) for name, a, b in evs]
        for line, evs in t.host.items()})
    return tr.idle_gaps_by_span(moved, span_pattern, n)


@reducer
def host_span_ms_median(ctx, args):
    """Median length of the host spans matching ``span`` in the trace."""
    t = ctx.get("trace")
    if t is None:
        return None
    xs = [b - a for _, a, b in t.host_spans(args["span"])]
    return 1e3 * statistics.median(xs) if xs else None


# -- set-up phases -----------------------------------------------------------
def _compile_total(state: dict, phases) -> float | None:
    by_phase = state.get("compile_s")
    if not by_phase:
        return None
    return sum(by_phase.get(p, 0.0) for p in phases)


@reducer
def setup_import_s(ctx, args):
    return ctx.get("program", {}).get("import_s")


@reducer
def setup_compile_s(ctx, args):
    """Seconds in jax's compile path between the moment the engine was
    built and the start of the window, over ``phases``: lowering and
    backend compile of the step, the agreement check's programs and the
    small ones. What compiled while the engine was built lies inside
    ``setup_init_s``'s spans and is not counted again. ``cache_load`` lies
    inside ``backend_compile``, and ``jaxpr_trace`` events nest (a jitted
    function traced inside another reports its seconds twice), so
    neither is one of the phases; tracing stays where it ran."""
    total = _compile_total(ctx.get("program", {}), args["phases"])
    if total is None:
        return None
    built = _compile_total(ctx.get("program_at_build", {}), args["phases"])
    return total - (built or 0.0)


@reducer
def setup_init_s(ctx, args):
    """The ``spans`` of engine construction (what compiles inside them
    included)."""
    spans = ctx.get("program", {}).get("spans", {})
    got = [spans[s][0] for s in args["spans"] if s in spans]
    return sum(got) if got else None
