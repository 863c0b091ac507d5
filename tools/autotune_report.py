#!/usr/bin/env python
"""Render an autotuning plan artifact (ISSUE 7) as a ranked table.

The plan JSON comes from ``Plan.save()`` (``artifacts/autotune_plan.json``
is one; ``Planner.plan()`` callers write their own). Shows every ranked
candidate with its predicted (and, for the measured top-K, observed)
step time, the compiler-reported AOT peak HBM next to the memory
model's prediction, per-axis collective payload, and the chosen
config diff ``Plan.apply()`` replays.

Serving plans (ISSUE 19, ``ServingPlan.save()`` / the bench
``serve_autotune`` stage, ``artifacts/serving_plan.json``) carry
``kind: "serving"`` and render as the ranked traffic-model table
instead: predicted TTFT/ITL/queue-wait/goodput per candidate plus the
measured truth the bench stamped onto the chosen row.

Stdlib-only on purpose (like tools/graftlint.py): reading a plan must
not need jax.

    python tools/autotune_report.py artifacts/autotune_plan.json
    python tools/autotune_report.py artifacts/serving_plan.json
    python tools/autotune_report.py plan.json --json   # machine-readable
"""

from __future__ import annotations

import argparse
import json
import sys


def _human_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024.0:
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} PB"


def _fmt(v, nd: int = 2) -> str:
    return "-" if v is None else f"{v:.{nd}f}"


def candidate_rows(plan: dict) -> list[dict]:
    """Ranked candidates first (rank order), then compile errors, then
    pruned — the same order the planner emits."""
    return list(plan.get("candidates", []))


def print_serving_report(plan: dict) -> None:
    """Serving-plan rendering (ISSUE 19): the ranked ServingCandidate
    grid from ``ServingPlan.save()`` (``kind: "serving"``, written by
    the bench ``serve_autotune`` stage) — predicted TTFT/ITL/queue-wait
    /goodput per candidate, measured truth where the bench stamped it,
    and the config patch ``ServingPlan.apply()`` replays."""
    tr = plan.get("traffic", {})
    cal = plan.get("calibration", {})
    print(f"serving plan v{plan.get('version')} — "
          f"{tr.get('arrival_rate_rps', 0):g} req/s, "
          f"{tr.get('prompt_tokens', '?')} prompt + "
          f"{tr.get('output_tokens', '?')} output tok, "
          f"SLO ttft {tr.get('slo_ttft_ms', 0):g} ms / "
          f"itl {tr.get('slo_itl_ms', 0):g} ms")
    print(f"calibration: {cal.get('source', '?')}  "
          f"tick {cal.get('decode_tick_s', 0) * 1e3:.3f} ms  "
          f"dispatch RTT {cal.get('dispatch_overhead_s', 0) * 1e3:.3f}"
          f" ms  prefill {cal.get('prefill_tokens_per_s', 0):g} tok/s")
    print()
    hdr = (f"{'rank':>4} {'candidate':<28}{'ttft ms':>9}{'itl ms':>8}"
           f"{'q-wait ms':>10}{'rho':>7}{'shed%':>7}{'goodput':>9}"
           f"{'meas gp':>9}{'meas ttft':>10}")
    print(hdr)
    print("-" * len(hdr))
    for row in candidate_rows(plan):
        if row.get("pruned"):
            print(f"{'--':>4} {row['label']:<28}pruned: "
                  f"{row['pruned']}")
            continue
        rho = row.get("predicted_rho")
        shed = row.get("predicted_shed_frac")
        print(f"{row.get('rank', '?'):>4} {row['label']:<28}"
              f"{_fmt(row.get('predicted_ttft_ms')):>9}"
              f"{_fmt(row.get('predicted_itl_ms')):>8}"
              f"{_fmt(row.get('predicted_queue_wait_ms'), 1):>10}"
              f"{_fmt(rho):>7}"
              f"{('%d' % (shed * 100) if shed is not None else '-'):>7}"
              f"{_fmt(row.get('predicted_goodput_rps'), 1):>9}"
              f"{_fmt(row.get('measured_goodput_rps'), 1):>9}"
              f"{_fmt(row.get('measured_ttft_p99_ms'), 1):>10}")
    chosen_i = plan.get("chosen_index", -1)
    cands = plan.get("candidates", [])
    print()
    if 0 <= chosen_i < len(cands):
        print(f"chosen: {cands[chosen_i]['label']}")
        diff = plan.get("config_diff", {})
        if diff:
            print("config diff (base -> chosen; ServingPlan.apply() "
                  "replays this):")
            for path, (a, b) in sorted(diff.items()):
                print(f"  {path}: {a!r} -> {b!r}")
        else:
            print("config diff: none (the base config won)")
    else:
        print("chosen: none (no candidate ranked)")


def print_report(plan: dict) -> None:
    info = plan.get("model_info", {})
    cal = plan.get("calibration", {})
    print(f"autotune plan v{plan.get('version')} — "
          f"{info.get('model', '?')} "
          f"({info.get('num_params', 0):,} params) on "
          f"{plan.get('n_devices', '?')} device(s)")
    print(f"calibration: {cal.get('source', '?')}  "
          f"eff {cal.get('flops_per_s', 0) / 1e9:.1f} GFLOP/s  "
          f"overhead {cal.get('overhead_s', 0) * 1e3:.2f} ms  "
          f"overlap {cal.get('overlap_ratio', 0):.2f}")
    print()
    hdr = (f"{'rank':>4} {'candidate':<44}{'pred ms':>9}{'meas ms':>9}"
           f"{'err':>7}{'tok/s pred':>12}{'tok/s meas':>12}"
           f"{'peak HBM':>10}{'coll B':>10}")
    print(hdr)
    print("-" * len(hdr))
    for row in candidate_rows(plan):
        if row.get("pruned"):
            why = row["pruned"]
            print(f"{'--':>4} {row['label']:<44}"
                  f"{'pruned: modeled ':>20}"
                  f"{_human_bytes(why.get('modeled_bytes', 0))} > "
                  f"headroom {_human_bytes(why.get('headroom_bytes', 0))}")
            continue
        if row.get("error"):
            print(f"{'!!':>4} {row['label']:<44}error: "
                  f"{row['error'][:60]}")
            continue
        aot = row.get("aot", {})
        err = row.get("prediction_rel_err")
        coll = sum(aot.get("collective_bytes_by_axis", {}).values())
        print(f"{row.get('rank', '?'):>4} {row['label']:<44}"
              f"{_fmt(row.get('predicted_step_ms')):>9}"
              f"{_fmt(row.get('measured_step_ms')):>9}"
              f"{('%d%%' % (err * 100) if err is not None else '-'):>7}"
              f"{_fmt(row.get('predicted_tokens_per_sec'), 0):>12}"
              f"{_fmt(row.get('measured_tokens_per_sec'), 0):>12}"
              f"{_human_bytes(aot.get('peak_hbm_bytes', 0)):>10}"
              f"{_human_bytes(coll):>10}")
    chosen_i = plan.get("chosen_index", -1)
    cands = plan.get("candidates", [])
    print()
    if 0 <= chosen_i < len(cands):
        print(f"chosen: {cands[chosen_i]['label']}")
        diff = plan.get("config_diff", {})
        if diff:
            print("config diff (base -> chosen; Plan.apply() replays "
                  "this):")
            for path, (a, b) in sorted(diff.items()):
                print(f"  {path}: {a!r} -> {b!r}")
        else:
            print("config diff: none (the base config won)")
    else:
        print("chosen: none (no candidate ranked)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render a deepspeed_tpu autotuning plan artifact")
    ap.add_argument("plan", help="plan JSON (Plan.save() output, e.g. "
                                 "artifacts/autotune_plan.json)")
    ap.add_argument("--json", action="store_true",
                    help="emit {summary, chosen, config_diff} as JSON")
    args = ap.parse_args(argv)
    with open(args.plan) as f:
        plan = json.load(f)
    serving = plan.get("kind") == "serving"
    if args.json:
        cands = plan.get("candidates", [])
        ranked = [c for c in cands if "rank" in c]
        measured = [c for c in ranked
                    if c.get("measured_goodput_rps" if serving
                             else "measured_step_ms") is not None]
        errs = [c["prediction_rel_err"] for c in measured
                if c.get("prediction_rel_err") is not None]
        chosen_i = plan.get("chosen_index", -1)
        out = {
            "n_candidates": len(cands),
            "n_ranked": len(ranked),
            "n_measured": len(measured),
            "prediction_rel_err": max(errs) if errs else None,
            "chosen": (cands[chosen_i]
                       if 0 <= chosen_i < len(cands) else None),
            "config_diff": plan.get("config_diff", {}),
        }
        json.dump(out, sys.stdout)
        print()
    elif serving:
        print_serving_report(plan)
    else:
        print_report(plan)
    return 0


if __name__ == "__main__":
    sys.exit(main())
