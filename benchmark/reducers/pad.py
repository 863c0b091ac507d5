"""The padding of the held dispatch (PR 38): the share of the rows its
blocks swept that no token filled, from the program's counters
``ds_moe_held_rows_total``, ``ds_moe_held_blocks_total`` and
``ds_moe_held_block_rows`` at the end of a traced run (fed by the engine
from the step's own outputs, one step behind, as ``reducers/moe.py``'s).
None where the program has no such counters (a program from before them,
a model whose layers do not count their blocks)."""

from __future__ import annotations

from lib.reducers import reducer

NAMES = ("ds_moe_held_rows_total", "ds_moe_held_blocks_total",
         "ds_moe_held_block_rows")


@reducer
def held_pad_share_pct(ctx, args):
    """100 x (1 - rows / (blocks x rows a block)) over the run's finished
    steps; prints the counters and the extremes of the load beside it."""
    try:
        from deepspeed_tpu.utils.telemetry_probe import active_telemetry
        tel = active_telemetry()
        reg = tel.get_registry() if tel is not None else None
        if reg is None:
            return None
        found = {name: reg.get(name) for name in NAMES}
        if None in found.values():
            return None
        rows, blocks, block = (found[name].value() for name in NAMES)
        ends = [reg.get(f"ds_moe_load_step_{end}") for end in ("min", "max")]
    except Exception:       # a program without these: nothing to read
        return None
    if not blocks or not block:
        return None
    print(f"held_pad_share: {rows} rows in {blocks} blocks of {block}; the "
          f"load of one expert in one layer of one step between "
          f"{[e.value() if e is not None else None for e in ends]}",
          flush=True)
    return 100.0 * (1.0 - rows / (blocks * block))
