#!/usr/bin/env python3
"""A traced run of one cell that also reads what the PROGRAM names.

    python3 benchmark/program_trace.py --workload <cell> --seed <n> --seconds <s>

Not the benchmark's command: ``BENCHMARK.json`` runs ``run.py``, whose
traced run reports what the cell file lists through the reducers
``lib/reducers.py`` registers, and PR 24, which changed the program, may
edit neither. This is ``run.py --trace 1`` (the same gate, cache, job,
profiler window and per-layer metrics, through ``run.py``'s own
functions) with the program's executable ledger on, plus:

- every metric file under ``layer_metrics/`` whose reducer is one of
  ``lib/reducers_program.REDUCERS`` and whose ``cells`` name the cell:
  device time by ``ds.`` scope, the set-up phases, ``h2d_ms``, the clock
  bracket;
- ``breakdown.device_scopes`` and ``breakdown.idle_gaps_aligned``;
- the lines ``setup:``, ``clock:`` and ``steptrace ...`` before the
  result line.

A ``benchmark`` PR that takes these into ``run.py`` (``PERF.md`` section 7
lists the edits) deletes this file. Against a program from before PR 24
every reader finds nothing and the metric is left out.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import run                          # noqa: E402  (benchmark/run.py)
from lib import files               # noqa: E402


def program_metrics(cell: dict, ctx: dict) -> dict:
    from lib import reducers_program as rp
    out = {}
    for path in sorted((files.ROOT / "layer_metrics").glob("*.json")):
        spec = files.load_layer_metric(path.stem)
        red = spec["reducer"]
        if red["name"] not in rp.REDUCERS or cell["name"] not in spec["cells"]:
            continue
        value = rp.REDUCERS[red["name"]](ctx, red.get("args", {}))
        if value is None:
            print(f"per-layer metric {path.stem}: nothing to read, left out",
                  flush=True)
            continue
        out[path.stem] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None, rig: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    args.trace = 1
    rig = dict(rig or {})       # tests only; empty in a real run

    cell = files.load_cell(args.workload)
    cell["traffic_file"].update(rig.get("traffic_overrides", {}))

    import jax
    cache_dir = run.enable_cache()
    device, pk = run.device_gate(int(cell["chips"]), rig)
    from lib import compilewatch, reducers_program as rp, train_job
    from lib import trace as trace_mod
    from lib.tracer import Tracer
    compilewatch.install()
    print(f"cell {cell['name']} traced with the program's names on {device} "
          f"jax={jax.__version__} cache_dir={cache_dir} seed={args.seed} "
          f"seconds={args.seconds}", flush=True)

    from deepspeed_tpu import telemetry
    # the ledger walks the step's HLO once, for the map from a trace
    # event's instruction name to the program's device scope
    telemetry.configure(profiler_annotations=True, executable_ledger=True)
    tracer = Tracer(cell["name"],
                    float(cell["traffic_file"]["trace_seconds"]))

    # the job has no hook at the moment the engine is built, and its file
    # is not this PR's to edit: what set-up ran before and inside engine
    # construction is marked from here, round the job's own builder
    at_build = {}
    build = train_job.build_engine

    def build_engine(*a):
        at_build["pre_build_s"] = time.perf_counter() - T_START
        built = build(*a)
        at_build["program_at_build"] = rp.program_state()
        return built

    train_job.build_engine = build_engine
    try:
        result = train_job.run(cell, args, rig, tracer=tracer,
                               t_start=T_START)
    finally:
        train_job.build_engine = build
    tracer.stop()

    device["memory_peak_bytes"] = run.memory_peak_bytes()
    tr = trace_mod.Trace.newest_under(str(tracer.dir))
    ctx = dict(result["context"])
    ctx.update(trace=tr, peaks=pk,
               memory_peak_bytes=device["memory_peak_bytes"],
               setup_s=result["end_to_end"]["setup_s"],
               # nothing compiles inside the window (the job prints
               # compiles_in_window), so the program's account now is its
               # account at the start of the window
               program=rp.program_state(),
               step_rows=rp.step_rows(ctx["steps"]), **at_build)
    ctx.update(rp.export(str(tracer.dir), cell["name"]))
    lo, hi = trace_mod.window(tr)
    device["busy_s"] = trace_mod.busy_seconds(tr)
    device["window_s"] = hi - lo
    metrics = run.layer_metrics(cell, ctx)
    metrics.update(program_metrics(cell, ctx))
    spans = cell["traffic_file"].get("span_pattern", ".")
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "device": device, "metrics": metrics,
            "breakdown": {
                "device_ops": trace_mod.top_ops(tr, 10),
                "idle_gaps": trace_mod.idle_gaps_by_span(tr, spans, 10),
                "device_scopes": rp.device_scopes(ctx, 20),
                "idle_gaps_aligned": rp.idle_gaps_aligned(ctx, spans, 10)}}
    for text in rp.report_lines(ctx, metrics):
        print(text, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
