"""What a looped stack adds to a traced run (PR 42): the device time of
one scope less the scopes inside it, and the exit distribution the program
counted.

``scope_ms_per_step`` (``reducers/program.py``) merges the intervals of
every op under a scope, and a ``while`` op counts with its own scope: the
scan over the layers inside ``ds.loop`` is one interval from its first op
to its last. What looping itself costs is that interval LESS the
sublayers' (``ds.attn``, ``ds.mlp``), which a pattern alone cannot say::

    "reducer": {"name": "scope_less_ms_per_step",
                "args": {"pattern": "ds\\.loop\\b",
                         "less": "ds\\.(attn|mlp)\\b",
                         "module": "^jit_train_step"}}

The exit gauges (``ds_loop_passes``, ``ds_exit_prob_mean{pass}``) are fed by
the engine from the step's own outputs one step behind
(``deepspeed_tpu/models/ouro.py`` ``record_step_metrics``), as
``reducers/moe.py``'s counters are. Every reader returns None where the
program has no such scope or gauge (a program from before them, a model
that does not loop)."""

from __future__ import annotations

import re
import statistics

from lib import trace as tr
from lib.reducers import reducer
from reducers.program import _matching, _scope_map


@reducer
def scope_less_ms_per_step(ctx, args):
    """Device time under the scopes matching ``pattern`` and under none
    matching ``less``, inside one run of ``module``: merged intervals less
    merged intervals, median over the complete steps."""
    t, scope_map = ctx.get("trace"), _scope_map(ctx)
    if t is None or not scope_map:
        return None
    inside, less = re.compile(args["pattern"]), re.compile(args["less"])
    xs = tr.per_step_seconds(
        t, args["module"],
        lambda c: tr.subtract(_matching(ctx, c, inside),
                              _matching(ctx, c, less)))
    return 1e3 * statistics.median(xs) if xs and any(xs) else None


@reducer
def expected_exit_pass(ctx, args):
    """sum over the passes of t x the mean exit probability of pass t, of
    the last step the registry holds: the depth, in passes, that exit by
    the gate would pay on this traffic at these weights."""
    try:
        from deepspeed_tpu.utils.telemetry_probe import active_telemetry
        tel = active_telemetry()
        reg = tel.get_registry() if tel is not None else None
        passes = reg.get("ds_loop_passes") if reg is not None else None
        prob = reg.get("ds_exit_prob_mean") if reg is not None else None
        if passes is None or prob is None or not passes.value():
            return None
        p = [prob.value(**{"pass": str(i)})
             for i in range(1, int(passes.value()) + 1)]
    except Exception:       # a program without these: nothing to read
        return None
    print(f"expected_exit_pass: mean exit probability by pass {p} "
          f"(sum {sum(p)})", flush=True)
    return sum(i * x for i, x in enumerate(p, start=1))
