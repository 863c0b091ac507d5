"""A kernel's share of its roofline, with the kernel found by the
program's device scope. The time is what lies under a ``ds.`` scope, never
every op matching ``custom_call_target="tpu_custom_call"``: that is EVERY
Pallas kernel, and in a cell with a second one (a grouped matmul or the
rotation's pair beside flash attention) a metric built on it adds the
two. So each kernel of a cell gets a metric file of its own:

    "reducer": {"name": "kernel_roofline_pct",
                "args": {"scope": "ds\\.flash_(fwd|bwd)\\b",
                         "cost": "flash_call_cost", "per": "layer",
                         "module": "^jit_train_step"}}
"""

from __future__ import annotations

from lib.reducers import least_ms_per_step, reducer
from reducers.program import scope_ms_per_step


@reducer
def kernel_roofline_pct(ctx, args):
    """Least time the chip could take for the step's calls of one kernel
    (``cost``: the name of a cost function of the cell's architecture
    module; ``per``: ``layer`` or ``step``) over the device time per step
    under the scopes matching ``scope`` inside one run of ``module``.
    None where the program names no such scope."""
    ms = scope_ms_per_step(ctx, {"pattern": args["scope"],
                                 "module": args["module"]})
    if not ms:
        return None
    return 100.0 * least_ms_per_step(ctx, args["cost"], args["per"]) / ms
