"""The one table of reducers a per-layer metric file may name, filled by
``@reducer`` from this module and from every module under
``benchmark/reducers/`` (a later PR adds a kernel's roofline reducer as a
new file there; a name defined twice is an error). Each takes the run's
context and the file's ``args`` and returns a number, or None when there
is nothing to read (the harness then leaves the metric out).

context keys: ``trace`` (lib.trace.Trace or None), ``peaks``, ``arch`` (the
configuration's architecture module), ``model`` (its ``WIDTHS`` keys as
built), ``tokens_per_s``, ``chips``, ``seq_len``, ``sequences``,
``memory_peak_bytes``; ``reducers/program.py`` lists those it adds.
"""

from __future__ import annotations

import functools
import importlib
import statistics

from . import files, trace as tr

REDUCERS: dict = {}         # name -> reader(ctx, args)


def reducer(fn):
    """Register ``fn`` under its own name."""
    old = REDUCERS.get(fn.__name__)
    if old is not None and old.__module__ != fn.__module__:
        raise files.BenchmarkFileError(
            f"reducer {fn.__name__!r} is defined twice: in {old.__module__} "
            f"and in {fn.__module__}")
    REDUCERS[fn.__name__] = fn
    return fn


@functools.cache
def _import_directory() -> None:
    for stem in files.known_modules("reducers"):
        importlib.import_module(f"reducers.{stem}")


def find(name: str):
    """The reducer a metric file names; every module under ``reducers/``
    is imported first, so the table is whole and a duplicate shows."""
    _import_directory()
    if name not in REDUCERS:
        raise files.BenchmarkFileError(
            f"no reducer named {name!r} (known: {sorted(REDUCERS)})")
    return REDUCERS[name]


def _median_ms(xs):
    return 1e3 * statistics.median(xs) if xs else None


@reducer
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


@reducer
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


@reducer
def busy_ms_per_step(ctx, args):
    """Union of device op intervals inside one run of ``module``."""
    t = ctx.get("trace")
    if t is None:
        return None
    return _median_ms(tr.per_step_seconds(
        t, args["module"], lambda c: tr.merge(tr._iv(t.ops(c)))))


@reducer
def step_gap_ms_median(ctx, args):
    if ctx.get("trace") is None:
        return None
    gaps = tr.step_gaps(ctx["trace"], args["module"])
    return 1e3 * statistics.median(gaps) if gaps else None


@reducer
def idle_share_pct(ctx, args):
    if ctx.get("trace") is None:
        return None
    lo, hi = tr.window(ctx["trace"])
    if hi <= lo:
        return None
    return 100.0 * (1.0 - tr.busy_seconds(ctx["trace"]) / (hi - lo))


@reducer
def hbm_peak_gib(ctx, args):
    b = ctx.get("memory_peak_bytes")
    return None if not b else b / 2 ** 30


@reducer
def mfu_pct(ctx, args):
    """Required fwd+bwd FLOPs per token x tokens/s of the traced run's
    window, over chips x peak. Recomputation is not counted."""
    if not ctx.get("tokens_per_s"):
        return None
    need = ctx["arch"].train_flops_per_token(ctx["model"], ctx["seq_len"])
    return (100.0 * need * ctx["tokens_per_s"]
            / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]))


def least_ms_per_step(ctx, cost: str, per: str) -> float:
    """Least milliseconds a step's calls of one kernel could take on this
    chip: the architecture's cost function ``cost`` (called as
    ``flash_call_cost`` is: the model, this chip's sequences, the sequence
    length, ``backward=``) for the forward and the backward call, through
    ``least_seconds``; ``per`` = ``layer`` counts one such pair a layer,
    ``step`` one a step."""
    m, arch = ctx["model"], ctx["arch"]
    local = max(1, ctx["sequences"] // ctx["chips"])
    least = 0.0
    for backward in (False, True):
        call = getattr(arch, cost)(m, local, ctx["seq_len"],
                                   backward=backward)
        least += arch.least_seconds(call, ctx["peaks"])[0]
    if per == "layer":
        least *= m["num_hidden_layers"]
    elif per != "step":
        raise files.BenchmarkFileError(
            f"per is {per!r}: a kernel runs once a 'layer' or once a 'step'")
    return 1e3 * least
