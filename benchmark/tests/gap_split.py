"""By hand, after a traced run of a cell IN THIS CHECKOUT: the host's side
of a step, read from the trace the run left under ``.bench_trace/<cell>/``
through ``reducers/hostgap.py`` and the six metric definitions of
``gap_metrics.json`` (metric files in all but place, as
``work_metrics.json``'s are: ``work_split.py`` says why). The benchmark's
own runs never run it.

    chiprun -- bash -c 'python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds 10 --trace 1 && python3 benchmark/tests/gap_split.py <cell> \
        [tokens/s of an UNTRACED run of the same code]'

One JSON line: ``bracket_us`` (the limits on device clock minus host clock
and their midpoint, which moves the host's events), ``metrics`` (the six
definitions: each part's median), ``host_gap_ms`` (``host_gap_ms.train``:
the median gap) beside ``parts_p50_sum_ms``, ``parts_ms`` (p5 / p50 / p95
over the steps of the six parts, ``other``, their ``sum`` and the
``gap``), ``steps`` (every step's row, ms; ``sum`` is the seven parts'),
``idle_ms_per_step`` (chip 0's idle by innermost host span, on the aligned
clock, a step: bare ``train_batch`` is what its children leave),
``in_compiled_step`` (every host event that starts inside a
``compiled_step`` span, by thread and name: events a step, median start
after the span's and median length, us; ``nested_in`` names the event of
the same thread that holds it) and, with the tokens/s of an untraced run,
``untraced`` (its step, the trace's ``device_step_ms`` and the gap that
run implies: what the host's path costs with the tracing OFF).
"""

import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def _pct(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def _in_compiled_step(t) -> list[dict]:
    steps = t.host_spans(r"^compiled_step$")
    acc: dict = {}
    for line, events in t.host.items():
        for _, s0, s1 in steps:
            inside = [e for e in events
                      if s0 <= e[1] <= s1 and e[0] != "compiled_step"]
            for name, a, b in inside:
                holders = [h for h in inside
                           if h[1] <= a and b <= h[2] and h != (name, a, b)]
                # the nearest holder; an event of the same extent and
                # name (jaxlib opens PjitFunction twice) holds its twin
                holder = min(holders, key=lambda h: h[2] - h[1],
                             default=None)
                row = acc.setdefault((line, name), {
                    "n": 0, "start": [], "dur": [], "nested_in": set()})
                row["n"] += 1
                row["start"].append(a - s0)
                row["dur"].append(b - a)
                row["nested_in"].add(holder[0] if holder else "")
    return sorted(
        ({"thread": line, "name": name,
          "events_per_step": row["n"] / len(steps),
          "start_us_p50": 1e6 * statistics.median(row["start"]),
          "dur_us_p50": 1e6 * statistics.median(row["dur"]),
          "nested_in": sorted(row["nested_in"])}
         for (line, name), row in acc.items()),
        key=lambda r: r["start_us_p50"])


def gap_split(cell_name: str, untraced_tokens_per_s=None) -> dict:
    from lib import files, reducers, trace as tr
    from lib.tracer import TRACE_ROOT
    from reducers import hostgap, program
    with open(os.path.join(HERE, "gap_metrics.json")) as f:
        specs = {name: spec for name, spec in json.load(f).items()
                 if cell_name in spec["cells"]}
    cell = files.load_cell(cell_name)
    t = tr.Trace.newest_under(str(TRACE_ROOT / cell_name))
    ctx = {"trace": t}
    out = {"cell": cell_name}
    args = next(iter(specs.values()))["reducer"]["args"]
    br = program._bracket(ctx, args)
    rows = hostgap.parts_of(ctx, args)
    if not rows:
        out["nothing_to_read"] = (
            "no device in the trace" if not t.chips() else
            "no compiled_step span beside a run of the step" if not br else
            "the bracket has no midpoint: the caller did not block"
            if br["midpoint"] is None else "no gap between two runs")
        return out
    out["bracket_us"] = {k: 1e6 * br[k]
                         for k in ("lower", "upper", "midpoint")}
    out["metrics"] = {
        name: reducers.find(spec["reducer"]["name"])(
            ctx, spec["reducer"]["args"]) for name, spec in specs.items()}
    keys = hostgap.PARTS + ("other",)
    steps = []
    for r in rows:
        row = {k: None if r[k] is None else 1e3 * r[k] for k in keys}
        row["sum"] = sum(v or 0.0 for v in row.values())
        row["gap"] = 1e3 * r["gap"]
        steps.append(row)
    module = {"module": args["module"]}
    out["host_gap_ms"] = reducers.find("step_gap_ms_median")(ctx, module)
    out["parts_p50_sum_ms"] = sum(
        statistics.median(xs) for k in keys
        if (xs := [s[k] for s in steps if s[k] is not None]))
    out["parts_ms"] = {
        k: {"p5": _pct(xs, 0.05), "p50": _pct(xs, 0.5),
            "p95": _pct(xs, 0.95)}
        for k in keys + ("sum", "gap")
        if (xs := [s[k] for s in steps if s[k] is not None])}
    out["steps"] = steps
    n = max(1, tr.step_count(t, args["module"]))
    pattern = cell["traffic_file"]["span_pattern"]
    out["idle_ms_per_step"] = {
        name: 1e3 * sec / n
        for name, sec in program.idle_gaps_aligned(ctx, pattern, 20)}
    out["in_compiled_step"] = _in_compiled_step(t)
    if untraced_tokens_per_s:
        tokens = (int(cell["traffic_file"]["seq_len"]) * int(cell["chips"])
                  * int(cell["traffic_file"]["sequences_per_chip"]))
        device = reducers.find("busy_ms_per_step")(ctx, module)
        step = 1e3 * tokens / float(untraced_tokens_per_s)
        out["untraced"] = {"tokens_per_s": float(untraced_tokens_per_s),
                           "step_ms": step, "device_step_ms": device,
                           "implied_gap_ms": step - device}
    return out


if __name__ == "__main__":
    print(json.dumps(gap_split(sys.argv[1], *sys.argv[2:3])), flush=True)
