#!/usr/bin/env python3
"""Run one benchmark cell on the TPU this process is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell (``benchmark/cells/<cell>.json``), its configuration and
its traffic mix by name, and by the names THEY give the architecture
module (``architectures/``) and the loop of the traffic kind (``kinds/``);
builds the system under test through the program's normal entry points,
checks it against the architecture's plain float32 reference, warms up
every shape the traffic uses (all of that is ``setup_s``), measures for
``--seconds``, and prints one JSON object as the LAST line of stdout.
``--trace 0`` reports the cell's end-to-end metrics with the program's
telemetry off; ``--trace 1`` turns on the program's spans and its
executable ledger, profiles a few seconds of the window and reports the
cell's per-layer metrics, each through the reducer its file names
(``lib/reducers.py`` and ``reducers/``).

There is no CPU mode: without a TPU whose ``device_kind`` is in
``benchmark/lib/peaks.py``, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result. See ``benchmark/README.md``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # process start, as near as Python sees

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
for p in (CHECKOUT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import files               # noqa: E402


class GateFailure(SystemExit):
    pass


def device_gate(chips: int, rig: dict) -> tuple[dict, dict]:
    """The device block of the result and the chip's published peaks, or
    exit: no TPU, an unknown device_kind, or a chip count other than the
    cell's. Only the benchmark's own CPU tests hand in ``peaks`` (of a
    chip that does not exist), and with them leave to run off a TPU."""
    import jax
    from lib import peaks
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" and "peaks" not in rig:
        raise GateFailure(
            f"benchmark: needs a TPU; JAX reports platform {d.platform!r} "
            f"({d.device_kind}). There is no CPU mode.")
    if len(devices) != chips:
        raise GateFailure(
            f"benchmark: the cell asks for {chips} chip(s), JAX reports "
            f"{len(devices)}")
    pk = rig.get("peaks") or peaks.peak(d.device_kind)   # raises if unknown
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devices)}, pk)


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)))
    return peak


def enable_cache() -> str:
    """JAX's persistent compile cache at the program's fixed path inside
    the checkout (``<checkout>/.jax_cache``), or where
    ``JAX_COMPILATION_CACHE_DIR`` says; every executable is kept, however
    quickly it compiled and however many there are, so a second run
    builds nothing."""
    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no LRU eviction: under a cap below a cell's working set (the chip
    # tool's machines come with JAX_COMPILATION_CACHE_MAX_SIZE=192 MiB)
    # each entry is evicted before the next run asks for it, and every
    # run compiles everything again
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


def layer_readers(cell: dict) -> list:
    """(name, metric file, reducer) of the cell's per-layer metrics; a
    reducer that is not found fails here, before any device work."""
    from lib import reducers
    specs = [files.load_layer_metric(name) for name in cell["per_layer"]]
    return [(spec["name"], spec, reducers.find(spec["reducer"]["name"]))
            for spec in specs]


def layer_metrics(readers: list, ctx: dict) -> dict:
    out = {}
    for name, spec, read in readers:
        value = read(ctx, spec["reducer"].get("args", {}))
        if value is None:
            print(f"per-layer metric {name}: nothing to read, left out",
                  flush=True)
            continue
        out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None, rig: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    rig = dict(rig or {})       # tests/cpu_rig.py only; empty in a real run

    cell = files.load_cell(args.workload)
    # the benchmark's own CPU tests shrink the traffic with the model
    cell["traffic_file"].update(rig.get("traffic_overrides", {}))
    readers = layer_readers(cell) if args.trace else []

    import jax
    cache_dir = enable_cache()
    device, pk = device_gate(int(cell["chips"]), rig)
    from lib import compilewatch
    compilewatch.install()
    print(f"cell {cell['name']}: config {cell['config']} x traffic "
          f"{cell['traffic']} on {device} jax={jax.__version__} "
          f"cache_dir={cache_dir} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace}", flush=True)

    tracer = None
    if args.trace:
        from deepspeed_tpu import telemetry
        from lib.tracer import Tracer
        # the ledger walks the step's HLO once, for the map from a trace
        # event's instruction name to the program's device scope
        telemetry.configure(profiler_annotations=True,
                            executable_ledger=True)
        tracer = Tracer(cell["name"],
                        float(cell["traffic_file"]["trace_seconds"]))

    result = cell["job"].run(cell, args, rig, tracer=tracer,
                             t_start=T_START)
    if tracer is not None:
        tracer.stop()

    units = {m["name"]: m["unit"]
             for m in files.benchmark_json()["end_to_end"]}
    snap = compilewatch.snapshot()
    print(f"executables built or loaded: {snap['executables']} "
          f"(cache hits {snap['cache_hits']}, builds over 1 s: "
          f"{compilewatch.slow_builds()})", flush=True)
    device["memory_peak_bytes"] = memory_peak_bytes()
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "device": device}
    if not args.trace:
        want = cell["end_to_end"]
        line["metrics"] = {k: {"value": float(result["end_to_end"][k]),
                               "unit": units[k]} for k in want}
    else:
        from lib import trace as trace_mod
        tr = trace_mod.Trace.newest_under(str(tracer.dir))
        ctx = dict(result["context"])
        ctx.update(trace=tr, peaks=pk,
                   memory_peak_bytes=device["memory_peak_bytes"])
        lo, hi = trace_mod.window(tr)
        device["busy_s"] = trace_mod.busy_seconds(tr)
        device["window_s"] = hi - lo
        line["metrics"] = layer_metrics(readers, ctx)
        spans = cell["traffic_file"].get("span_pattern", ".")
        line["breakdown"] = {
            "device_ops": trace_mod.top_ops(tr, 10),
            "idle_gaps": trace_mod.idle_gaps_by_span(tr, spans, 10)}
        # what the kind itself adds to a traced run, if anything
        traced = getattr(cell["job"], "traced", None)
        more = traced(ctx, line["metrics"], spans) if traced else {}
        line["breakdown"].update(more.get("breakdown", {}))
        for text in more.get("lines", ()):
            print(text, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
