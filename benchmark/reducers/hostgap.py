"""The HOST's side of a step (PR 52): the idle gap between two runs of the
step executable, cut into the parts of ``train_batch``'s path that hold it.

For every pair of consecutive runs of ``module`` on chip 0 the gap ``G`` is
``lib.trace.step_gaps``' (the last op of run i to the first op of run
i+1). The host's events, moved onto the device's clock by the midpoint of
``reducers.program.clock_bracket``, cut it in time order:

    device's last op | train_batch starts | batch_to_device starts | it ends
    | train_batch/observe ends | compiled_step starts | the launch starts
    | device's first op

    caller    the wake-up from ``block_until_ready`` and the caller's own
              code (``step_boundary`` of the step before lies in it)
    prepare   ``train_batch/prepare``: the span's start to the transfer
    h2d       ``batch_to_device``
    observe   ``train_batch/observe``: the transfer's end to the span's end
    dispatch  ``compiled_step``'s start to the earliest event matching
              ``launch`` inside it: Python, ``PjitFunction``, ``shard_args``
    launch    that event's start to the device's first op
    other     whatever of ``G`` no part claims: the sliver between two
              spans where all are there, and what an older program (no
              ``train_batch/prepare``, no ``train_batch/observe``) leaves
              unnamed

The seven sum to ``G`` for every step. When the caller blocks on every
step, ``prepare``, ``h2d``, ``observe`` and ``dispatch`` are differences of
HOST events alone and need no clock; ``caller`` and ``launch`` each hold
one device event, so an error of the offset moves them by equal and
opposite amounts: their sum is exact, their split is known to half the
bracket (``clock_bracket_us.train``).

``gap_part_ms`` (args ``module``, ``launch``, ``part``) is the median of one
part over the steps, in ms; None where the program has no such span, the
trace no such event, or the bracket no midpoint (the caller did not
block: the host's path then runs beside the device and holds no gap).
"""

from __future__ import annotations

import statistics

from lib import trace as tr
from lib.reducers import reducer
from reducers import program

PARTS = ("caller", "prepare", "h2d", "observe", "dispatch", "launch")


def gap_edges(t, module: str) -> list[tuple[float, float]]:
    """(the last op's end of run i, the first op's start of run i+1) on
    chip 0: the ends of ``lib.trace.step_gaps``' gaps, in its order."""
    chips = t.chips()
    if not chips:
        return []
    ops = tr.merge(tr._iv(t.ops(chips[0])))
    runs = []
    for _, a, b in t.modules(chips[0], module):
        inside = tr.clip(ops, a, b)
        if inside:
            runs.append((inside[0][0], inside[-1][1]))
    return [(runs[i][1], runs[i + 1][0]) for i in range(len(runs) - 1)]


def _inside(events, lo, hi):
    """The first of ``events`` (sorted by start) that starts in [lo, hi]."""
    return next((e for e in events if lo <= e[1] <= hi), None)


def host_path(t, launch: str) -> list[dict]:
    """The host's cuts of each step whose ``compiled_step`` span lies in a
    ``train_batch`` span, on the HOST's clock: ``batch``, ``h2d0``,
    ``h2d1``, ``step`` (always); ``prepare`` (is the span there),
    ``observe1``, ``launch`` (None where the program or the trace has no
    such event)."""
    out = []
    batches = t.host_spans(r"^train_batch$")
    transfers = t.host_spans(r"^batch_to_device$")
    prepares = t.host_spans(r"^train_batch/prepare$")
    observes = t.host_spans(r"^train_batch/observe$")
    launches = t.host_spans(launch)
    for _, s0, s1 in t.host_spans(r"^compiled_step$"):
        batch = next((b for b in batches if b[1] <= s0 and s1 <= b[2]),
                     None)
        h2d = batch and _inside(transfers, batch[1], s0)
        if not h2d:
            continue
        prepare = _inside(prepares, batch[1], h2d[1])
        observe = _inside(observes, h2d[2], s0)
        first = _inside(launches, s0, s1)
        out.append({"batch": batch[1], "h2d0": h2d[1], "h2d1": h2d[2],
                    "step": s0, "prepare": prepare is not None,
                    "observe1": observe and observe[2],
                    "launch": first and first[1]})
    return out


def gap_parts(t, module: str, launch: str, offset: float) -> list[dict]:
    """One row a gap of ``step_gaps``: ``gap`` and the seven parts in
    seconds (a part that cannot be read is None and its time lies in
    ``other``), with ``offset`` = device clock minus host clock."""
    rows = []
    path = host_path(t, launch)
    for end, start in gap_edges(t, module):
        # the step whose launch this gap waits for
        p = min(path, key=lambda p: abs(p["step"] + offset - start),
                default=None)
        if p is None or abs(p["step"] + offset - start) >= program.NEAR:
            continue
        row = {"gap": start - end,
               "caller": p["batch"] + offset - end,
               "prepare": (p["h2d0"] - p["batch"] if p["prepare"]
                           else None),
               "h2d": p["h2d1"] - p["h2d0"],
               "observe": (None if p["observe1"] is None
                           else p["observe1"] - p["h2d1"]),
               "dispatch": (None if p["launch"] is None
                            else p["launch"] - p["step"]),
               "launch": (None if p["launch"] is None
                          else start - (p["launch"] + offset))}
        row["other"] = row["gap"] - sum(row[k] or 0.0 for k in PARTS)
        rows.append(row)
    return rows


def parts_of(ctx, args) -> list[dict] | None:
    """``gap_parts`` of the run's trace at the bracket's midpoint, read
    once; None where there is no trace or no midpoint."""
    if "gap_parts" not in ctx:
        t, br = ctx.get("trace"), program._bracket(ctx, args)
        ctx["gap_parts"] = (
            None if t is None or not br or br["midpoint"] is None
            else gap_parts(t, args["module"], args["launch"],
                           br["midpoint"]))
    return ctx["gap_parts"]


@reducer
def gap_part_ms(ctx, args):
    """Median over the steps of one part (``part``: one of ``PARTS`` or
    ``other``) of the gap between two runs of ``module``, in ms."""
    rows = parts_of(ctx, args)
    xs = [r[args["part"]] for r in rows or ()
          if r[args["part"]] is not None]
    return 1e3 * statistics.median(xs) if xs else None
