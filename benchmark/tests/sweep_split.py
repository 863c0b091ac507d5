"""By hand, after a traced run of a routed cell IN THIS CHECKOUT: the held
dispatch's sweep as it counted itself, read from what the run left under
``.bench_trace/<cell>/`` (the trace, the registry's snapshot
``<cell>.metrics.json``, the scope map and the program's own spans
``<cell>.trace.json``) through ``reducers/sweep.py`` and the six metric
definitions of ``sweep_metrics.json`` (metric files in all but place, as
``work_metrics.json``'s are: ``work_split.py`` says why). The benchmark's
own runs never run it.

    chiprun -- bash -c 'python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds 45 --trace 1 && python3 benchmark/tests/sweep_split.py <cell>'

One JSON line: ``metrics`` (the six definitions; None where there was
nothing to read), ``counters`` (what the four counted ones are made of:
``reducers/sweep.py`` ``COUNTERS``), ``block_pad_share`` (the cell's
accepted ``moe_pad_share.*`` from the same snapshot, beside
``moe_tile_pad_share.routed``; left out where the cell has none),
``steps`` (a row a traced step: ``device_ms`` and whether a
``moe_extra_trip`` event speaks of it, so ``trips`` is more than ``calls``
there and equal elsewhere) and ``extra_trip_steps`` (every such event of
the WHOLE run from the program's own spans: the ``step`` it speaks of, its
``trips`` and ``calls``; the trace holds the window's first seconds only).
Where the program has no such counters (a parent from before PR 68) the
counted metrics read None and the line says so.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def _extra_trip_steps(path: str) -> list[dict]:
    """The program's ``moe_extra_trip`` spans with the step each speaks
    of: the one before the ``train_batch`` span that precedes it."""
    with open(path) as f:
        events = sorted((e for e in json.load(f)["traceEvents"]
                         if e.get("name") in ("train_batch",
                                              "moe_extra_trip")),
                        key=lambda e: e["ts"])
    out, step = [], None
    for e in events:
        if e["name"] == "train_batch":
            step = e.get("args", {}).get("step")
        elif step is not None:
            out.append({"step": step - 1, **e.get("args", {})})
    return out


def sweep_split(cell_name: str) -> dict:
    from lib import files, reducers, trace as tr
    from lib.tracer import TRACE_ROOT
    from reducers import sweep
    with open(os.path.join(HERE, "sweep_metrics.json")) as f:
        specs = {name: spec for name, spec in json.load(f).items()
                 if cell_name in spec["cells"]}
    cell = files.load_cell(cell_name)
    trace_dir = TRACE_ROOT / cell_name
    with open(trace_dir / f"{cell_name}.metrics.json") as f:
        snapshot = json.load(f)
    ctx = {"trace": tr.Trace.newest_under(str(trace_dir)),
           "registry_snapshot": snapshot, "ledger_entry": "compiled_step",
           "op_scopes_path": str(trace_dir / f"{cell_name}.op_scopes.json")}
    out = {"cell": cell_name}
    out["metrics"] = {
        name: reducers.find(spec["reducer"]["name"])(
            ctx, spec["reducer"]["args"]) for name, spec in specs.items()}
    out["counters"] = sweep.counters(ctx)
    if out["counters"] is None:
        out["nothing_to_read"] = "the program kept no ds_moe_sweep_* counters"
    blocks = sweep.value(snapshot, "ds_moe_held_blocks_total")
    for name in cell["per_layer"]:
        if name.startswith("moe_pad_share.") and blocks:
            out["block_pad_share"] = {name: 100.0 * (
                1.0 - sweep.value(snapshot, "ds_moe_held_rows_total")
                / (blocks * sweep.value(snapshot, "ds_moe_held_block_rows")))}
    cost = specs.get("moe_extra_trip_cost_ms.routed")
    if cost is not None:
        out["steps"] = sweep.traced_steps(ctx, cost["reducer"]["args"])
    out["extra_trip_steps"] = _extra_trip_steps(
        trace_dir / f"{cell_name}.trace.json")
    return out


if __name__ == "__main__":
    print(json.dumps(sweep_split(sys.argv[1])), flush=True)
