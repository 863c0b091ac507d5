"""By hand, after a traced run of a cell IN THIS CHECKOUT: the split of
the step by kind of work and by the parts of the recurrent mixers, read
from what the run left under ``.bench_trace/<cell>/`` (the trace and the
program's ``<cell>.op_work.json``) through ``reducers/work.py`` and the
twenty metric definitions of ``work_metrics.json`` (metric files in all
but place). The benchmark's own runs never run it.

    chiprun -- bash -c 'python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds 45 --trace 1 && python3 benchmark/tests/work_split.py <cell>'

Why it is not twenty files under ``layer_metrics/`` (PR 36): ``run.py``
takes a cell's per-layer metrics from the ``per_layer`` list of
``cells/<cell>.json``, so a metric cannot be added to a cell that is there
without an edit to that file, which only a ``benchmark`` PR may make
(``PERF.md`` section 7 says which edit).

One JSON line: ``metrics`` (the definitions whose ``cells`` name the
cell), ``mixed_share`` (of each metric's time, the share that lies in
fusions of more than one scope path: what "by fusion root" blurs),
``kind_ms`` (the whole step by kind, leaf ops), ``scope_kind_ms`` (its
forty largest parts by scope path and kind), ``other`` (the device ops
of a kind the program's table does not know: there should be none) and
``top`` (the longest non-matmul ops the mixers' metrics read, with the
bytes at their boundary and what that is a second; of the whole step in a
cell without such a mixer). Where the program
wrote no ``op_work.json`` (a parent from before PR 36) the line says so.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def work_split(cell_name: str, top: int = 12) -> dict:
    from lib import reducers, trace as tr
    from lib.tracer import TRACE_ROOT
    from reducers import work
    with open(os.path.join(HERE, "work_metrics.json")) as f:
        specs = {name: spec for name, spec in json.load(f).items()
                 if cell_name in spec["cells"]}
    trace_dir = TRACE_ROOT / cell_name
    t = tr.Trace.newest_under(str(trace_dir))
    ctx = {"trace": t, "ledger_entry": "compiled_step",
           "op_scopes_path": str(trace_dir / f"{cell_name}.op_scopes.json")}
    out = {"cell": cell_name}
    if not work.work_map(ctx) or not t.chips():
        out["nothing_to_read"] = ("the program wrote no op_work.json"
                                  if t.chips() else "no device in the trace")
        return out
    module = "^jit_train_step"
    chip = t.chips()[0]
    steps = tr.complete_steps(t, module, chip)
    leaves = work.leaf_work(ctx, chip)

    ms = lambda wanted: work.leaf_ms_per_step(  # noqa: E731
        ctx, module, wanted)
    out["steps"] = len(steps)
    out["metrics"], out["mixed_share"] = {}, {}
    for name, spec in specs.items():
        args = spec["reducer"]["args"]
        value = reducers.find(spec["reducer"]["name"])(ctx, args)
        out["metrics"][name] = value
        if spec["unit"] == "ms" and value:
            wanted = work.selector(args)
            out["mixed_share"][name] = ms(
                lambda row: wanted(row) and row["mixed"]) / value
    kinds = sorted({row["kind"] for _, _, _, row in leaves})
    out["kind_ms"] = {k: ms(lambda row: row["kind"] == k) for k in kinds}
    out["other"] = sorted({name for _, _, name, row in leaves
                           if row["kind"] == "other"})
    # the step as a matrix: leaf time by scope (no direction) and kind
    matrix: dict = {}
    for a, b, _, row in leaves:
        if steps and steps[0][0] <= a < steps[-1][1]:
            key = (row["scope"].rpartition(":")[2], row["kind"])
            matrix[key] = matrix.get(key, 0.0) + (b - a)
    out["scope_kind_ms"] = [
        [scope, kind, 1e3 * sec / len(steps)] for (scope, kind), sec
        in sorted(matrix.items(), key=lambda kv: -kv[1])[:40]]
    # the longest non-matmul ops the mixers' *_mix_ms read (of the whole
    # step in a cell with no such mixer)
    inside = [work.selector(spec["reducer"]["args"])
              for name, spec in specs.items() if "_mix_ms." in name] \
        or [lambda row: True]
    acc: dict = {}
    for a, b, name, row in leaves:
        if (row["kind"] in ("elementwise", "move") and steps
                and steps[0][0] <= a < steps[-1][1]
                and any(w(row) for w in inside)):
            acc.setdefault(name, [0.0, 0, row])
            acc[name][0] += b - a
            acc[name][1] += 1
    out["top"] = [
        {"op": name, "ms_per_step": 1e3 * sec / len(steps),
         "events_per_step": n / len(steps), "kind": row["kind"],
         "scope": row["scope"], "mixed": row["mixed"],
         "bytes": row["bytes"], "gb_per_s": row["bytes"] * n / sec / 1e9}
        for name, (sec, n, row) in sorted(
            acc.items(), key=lambda kv: -kv[1][0])[:top]]
    return out


if __name__ == "__main__":
    print(json.dumps(work_split(sys.argv[1])), flush=True)
