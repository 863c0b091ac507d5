"""By hand, for a ``benchmark`` PR that folds or renames per-layer metrics:
ONE traced run of a cell in this checkout, read twice on the same trace,
through this tree's metric files and through ANOTHER tree's (the parent's,
unpacked somewhere git ignores), so that every name that went is held
against the name that took its place on one trace and one context. The
benchmark's own runs never run it.

    git archive <parent> benchmark BENCHMARK.json | tar -x -C .chip_parent
    chiprun -- python3 benchmark/tests/fold_check.py <cell> <seed> \
        <seconds> .chip_parent

It is ``run.main`` with ``--trace 1``: the result line is the one the
benchmark prints (this tree's names alone). Before it, one line
``fold_check: {...}`` and the same object in
``chiprun_out/fold_check/<cell>.json``: ``rows`` = [old name, old value,
new name, new value, relative gap, how], where ``how`` is ``same
definition`` (the other tree's file and this tree's say the same thing:
layer, unit, better, source, moves, reducer), ``same name, redefined`` or
``gone`` (nothing here reads it); ``gained`` = this tree's names that no
file of the other tree's cell defines; ``step_parts`` = the five parts by
scope and ``ds.embed`` beside ``device_step_ms.train``. The other tree's
readers run with the other tree's architecture module (a cost function
that changed shows as a gap), and a reducer that is gone from this tree's
table is read through ``GONE`` below.
"""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
sys.path[:0] = [CHECKOUT, BENCH]

DEFINITION = ("layer", "unit", "better", "source", "moves", "reducer")
STEP_PARTS = ("layers_fwd_ms.train", "layers_bwd_ms.train",
              "loss_head_ms.train", "optimizer_ms.train",
              "unscoped_ms.train")


def _flash_roofline_pct(ctx, args):
    """``lib/reducers.py``'s until PR 63: the least time of the flash
    calls over EVERY op matching ``args['pattern']``."""
    from lib import reducers
    ms = reducers.device_op_ms_per_step(ctx, args)
    if not ms:
        return None
    return 100.0 * reducers.least_ms_per_step(
        ctx, "flash_call_cost", "layer") / ms


GONE = {"flash_roofline_pct": _flash_roofline_pct}


def _json(path):
    with open(path) as f:
        return json.load(f)


def _other_arch(other: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"other_architectures_{name}",
        os.path.join(other, "benchmark", "architectures", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(cell_name: str, seed: str, seconds: str, other: str,
         rig: dict | None = None) -> int:
    import run
    from lib import files, reducers
    other = os.path.abspath(other)
    cell = files.load_cell(cell_name)
    theirs = {name: _json(os.path.join(other, "benchmark", "layer_metrics",
                                       f"{name}.json"))
              for name in _json(os.path.join(
                  other, "benchmark", "cells",
                  f"{cell_name}.json"))["per_layer"]}
    ours = {name: files.load_layer_metric(name)
            for name in cell["per_layer"]}
    old_arch = _other_arch(other, cell["config_file"]["architecture"])

    def find(name):
        return GONE.get(name) or reducers.find(name)

    def key(spec):
        return json.dumps([spec[k] for k in DEFINITION], sort_keys=True)

    def both(readers, ctx):
        got = run_layer_metrics(readers, ctx)
        new_arch, old = ctx["arch"], {}
        ctx["arch"] = old_arch
        try:
            for name, spec in theirs.items():
                old[name] = find(spec["reducer"]["name"])(
                    ctx, spec["reducer"].get("args", {}))
        finally:
            ctx["arch"] = new_arch
        by_key = {key(spec): name for name, spec in ours.items()}
        rows, taken = [], set()
        for name, spec in theirs.items():
            new, how = by_key.get(key(spec)), "same definition"
            if new is None:
                new, how = ((name, "same name, redefined") if name in ours
                            else (None, "gone"))
            taken.add(new)
            a = old[name]
            b = got.get(new, {}).get("value") if new else None
            gap = (abs(a - b) / max(abs(a), abs(b), 1e-30)
                   if a is not None and b is not None else None)
            rows.append([name, a, new, b, gap, how])
        embed = reducers.find("scope_ms_per_step")(ctx, {
            "pattern": r"^((fwd|bwd):)?ds\.embed\b",
            "module": "^jit_train_step"})
        table = {
            "cell": cell_name, "seed": int(seed), "rows": rows,
            "gained": {n: got.get(n, {}).get("value")
                       for n in ours if n not in taken},
            "step_parts": {
                **{n: got.get(n, {}).get("value") for n in STEP_PARTS},
                "ds.embed": embed,
                "device_step_ms.train": got.get(
                    "device_step_ms.train", {}).get("value")}}
        out = os.path.join(CHECKOUT, "chiprun_out", "fold_check")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"{cell_name}.json"), "w") as f:
            json.dump(table, f, indent=1)
        print("fold_check: " + json.dumps(table), flush=True)
        return got

    run_layer_metrics, run.layer_metrics = run.layer_metrics, both
    return run.main(["--workload", cell_name, "--seed", seed,
                     "--seconds", seconds, "--trace", "1"], rig=rig)


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
