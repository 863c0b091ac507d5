"""The fixed set of reducers a per-layer metric file may name. Each takes
the run's context and the file's ``args`` and returns a number, or None
when there is nothing to read (the harness then leaves the metric out).

context keys: ``trace`` (lib.trace.Trace or None), ``peaks``, ``model``
(HF-keyed numbers as built), ``tokens_per_s``, ``chips``, ``seq_len``,
``sequences``, ``memory_peak_bytes``.
"""

from __future__ import annotations

import statistics

from . import flops, trace as tr


def _median_ms(xs):
    return 1e3 * statistics.median(xs) if xs else None


def device_op_ms_per_step(ctx, args):
    """Device time of ops matching ``pattern`` inside one run of
    ``module``: median over the complete steps of the trace."""
    t = ctx.get("trace")
    if t is None:
        return None
    lines = tuple(args.get("lines", (tr.OPS_LINE,)))
    return _median_ms(tr.per_step_seconds(
        t, args["module"], lambda c: tr.merge(tr._iv(t.ops(
            c, args["pattern"], args.get("exclude"), lines)))))


def exposed_op_ms_per_step(ctx, args):
    """Time of ops matching ``pattern`` with no other leaf op running on
    the same chip, inside one run of ``module``: median over steps."""
    t = ctx.get("trace")
    if t is None:
        return None
    lines = tuple(args.get("lines", (tr.OPS_LINE, tr.ASYNC_LINE)))

    def exposed(c):
        coll = tr.merge(tr._iv(t.ops(c, args["pattern"], None, lines)))
        other = tr.merge(tr._iv(t.ops(c, None, exclude=args["pattern"],
                                      leaves=True)))
        return tr.subtract(coll, other)

    return _median_ms(tr.per_step_seconds(t, args["module"], exposed))


def busy_ms_per_step(ctx, args):
    """Union of device op intervals inside one run of ``module``."""
    t = ctx.get("trace")
    if t is None:
        return None
    return _median_ms(tr.per_step_seconds(
        t, args["module"], lambda c: tr.merge(tr._iv(t.ops(c)))))


def step_gap_ms_median(ctx, args):
    if ctx.get("trace") is None:
        return None
    gaps = tr.step_gaps(ctx["trace"], args["module"])
    return 1e3 * statistics.median(gaps) if gaps else None


def idle_share_pct(ctx, args):
    if ctx.get("trace") is None:
        return None
    lo, hi = tr.window(ctx["trace"])
    if hi <= lo:
        return None
    return 100.0 * (1.0 - tr.busy_seconds(ctx["trace"]) / (hi - lo))


def hbm_peak_gib(ctx, args):
    b = ctx.get("memory_peak_bytes")
    return None if not b else b / 2 ** 30


def mfu_pct(ctx, args):
    """Required fwd+bwd FLOPs per token x tokens/s of the traced run's
    window, over chips x peak. Recomputation is not counted."""
    if not ctx.get("tokens_per_s"):
        return None
    need = flops.train_flops_per_token(ctx["model"], ctx["seq_len"])
    return (100.0 * need * ctx["tokens_per_s"]
            / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]))


def flash_roofline_pct(ctx, args):
    """Least time the chip could take for the step's flash calls (forward
    and one-pass backward of every layer, this chip's sequences) over
    their measured device time per step."""
    ms = device_op_ms_per_step(ctx, args)
    if not ms:
        return None
    m = ctx["model"]
    local = max(1, ctx["sequences"] // ctx["chips"])
    least = 0.0
    for backward in (False, True):
        cost = flops.flash_call_cost(m, local, ctx["seq_len"],
                                     backward=backward)
        least += flops.least_seconds(cost, ctx["peaks"])[0]
    least *= m["num_hidden_layers"]
    return 100.0 * (1e3 * least) / ms


REDUCERS = {f.__name__: f for f in (
    device_op_ms_per_step, exposed_op_ms_per_step, busy_ms_per_step,
    step_gap_ms_median, idle_share_pct, hbm_peak_gib, mfu_pct,
    flash_roofline_pct)}
