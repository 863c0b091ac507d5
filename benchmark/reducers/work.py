"""Reducers that read what KIND of work each device op is (PR 36): the
executable ledger's ``<prefix>.op_work.json``, written beside
``<prefix>.op_scopes.json`` by the same walk of the compiled step
(``deepspeed_tpu/telemetry/scopes.py`` ``op_work``): {ledger entry: {HLO
instruction name: {"scope": path, "kind": one of matmul / kernel /
collective / move / elementwise / control / other, "bytes": result plus
operands, "mixed": a fusion of more than one scope path}}}.

A scope says whose the time is and blurs where XLA fuses across two (a
fusion takes its root's); the kind is read from the instruction itself.
Both readers take ``pattern`` (a regex on the scope path), optionally
``exclude`` (a regex on it) and ``kinds`` (a list), and ``module``; both
read LEAF ops only, so a ``while`` does not lend its whole interval to a
kind. Each returns None where the program wrote no such file (a parent
from before PR 36), and the metric is then left out.

context keys read here: ``op_scopes_path`` (the file's path is that one's
with ``.op_scopes.json`` replaced), ``ledger_entry``, ``trace``.
"""

from __future__ import annotations

import json
import os
import re
import statistics

from lib import trace as tr
from lib.reducers import reducer
from reducers.program import _INSTRUCTION


def work_map(ctx) -> dict | None:
    """{instruction name: {"scope", "kind", "bytes", "mixed"}} of the
    kind's ledger entry, or None where there is no such file."""
    if "op_work" not in ctx:
        path = (ctx.get("op_scopes_path") or "").replace(
            ".op_scopes.json", ".op_work.json")
        maps = {}
        if path.endswith(".op_work.json") and os.path.isfile(path):
            with open(path) as f:
                maps = json.load(f)
        ctx["op_work"] = maps.get(ctx.get("ledger_entry"))
    return ctx["op_work"]


def leaf_work(ctx, chip) -> list:
    """(start, end, instruction name, its row of the map) of the leaf
    device ops of ``chip`` the map knows; read once per chip."""
    cache = ctx.setdefault("_leaf_work", {})
    if chip not in cache:
        rows = work_map(ctx)
        cache[chip] = [
            (a, b, name, rows[name])
            for text, a, b in ctx["trace"].ops(chip, leaves=True)
            if (name := _INSTRUCTION.match(text).group(1)) in rows]
    return cache[chip]


def selector(args):
    """The test ``args`` put to a row of the map."""
    rx = re.compile(args["pattern"])
    ex = re.compile(args["exclude"]) if args.get("exclude") else None
    kinds = set(args["kinds"]) if args.get("kinds") else None

    def wanted(row) -> bool:
        return bool(rx.search(row["scope"])
                    and (ex is None or not ex.search(row["scope"]))
                    and (kinds is None or row["kind"] in kinds))
    return wanted


def leaf_ms_per_step(ctx, module: str, wanted) -> float | None:
    """Device time of the leaf ops whose row of the map ``wanted`` takes,
    inside one run of ``module``: merged intervals, median over the
    complete steps, mean over chips."""
    xs = tr.per_step_seconds(
        ctx["trace"], module, lambda c: tr.merge(
            (a, b) for a, b, _, row in leaf_work(ctx, c) if wanted(row)))
    return 1e3 * statistics.median(xs) if xs else None


@reducer
def work_ms_per_step(ctx, args):
    """Device time of the leaf ops whose scope path matches ``pattern``
    (and not ``exclude``) and whose kind is one of ``kinds`` (every kind
    if not given) inside one run of ``module``."""
    if ctx.get("trace") is None or not work_map(ctx):
        return None
    return leaf_ms_per_step(ctx, args["module"], selector(args))


@reducer
def work_gib_per_step(ctx, args):
    """GiB at the boundaries of the same ops: the sum of ``bytes`` over
    the leaf events that start inside one run of ``module`` (an
    instruction in a loop counts once per event), median over the
    complete steps, mean over chips. An upper bound on their HBM traffic
    (``bytes`` counts what the compiler holds in VMEM; a sliced operand
    and an in-place update count by what they touch); a count, so it
    repeats to the digit."""
    t = ctx.get("trace")
    if t is None or not work_map(ctx) or not t.chips():
        return None
    wanted = selector(args)
    per_chip = []
    for c in t.chips():
        events = [(a, row["bytes"]) for a, _, _, row in leaf_work(ctx, c)
                  if wanted(row)]
        per_chip.append([
            sum(n for a, n in events if lo <= a < hi)
            for lo, hi in tr.complete_steps(t, args["module"], c)])
    steps = min(len(x) for x in per_chip)
    if not steps:
        return None
    return statistics.median(
        sum(x[i] for x in per_chip) / len(per_chip)
        for i in range(steps)) / 2 ** 30
