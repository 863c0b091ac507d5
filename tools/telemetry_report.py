"""Summarize a telemetry dump (ISSUE 2 + 5): span trace + metrics +
executable ledger, multi-rank trace merging, and snapshot diffing.

Usage::

    # per-run report
    python tools/telemetry_report.py TRACE.trace.json \
        [METRICS.prom | METRICS.metrics.json] [--ledger LEDGER.json] \
        [--json]

    # merge per-rank Chrome traces into one Perfetto timeline with
    # rank-labelled tracks (eyeball straggler skew)
    python tools/telemetry_report.py --merge OUT.trace.json \
        r0.trace.json r1.trace.json ...

    # metric-snapshot regression diff (exit 1 on regression)
    python tools/telemetry_report.py --diff A.json B.json \
        [--threshold 0.05]

    # serving regression gate (ISSUE 6 CI wiring): compare the current
    # bench artifact against the previous one, gating ONLY the serving
    # SLO families (tick_p50_ms, dispatches_per_token, TTFT/ITL p99,
    # tokens_per_sec, fused_occupancy) under per-metric direction-aware
    # thresholds; exit 1 on regression
    python tools/telemetry_report.py --diff BENCH_prev.json \
        BENCH_curr.json --gate serving

Reads the Chrome-trace JSON written by
``telemetry.export_artifacts()`` (or any Chrome-trace file with ``X``
events) and prints a per-span-name table — count, total/mean/max ms,
share of top-level wall time — plus, when a metrics file is given, the
scalar metric values (Prometheus text or the registry's JSON snapshot)
and a serving summary rolling up the ``ds_serving_*`` series,
prefix-cache hit/miss/eviction counters included. ``--ledger`` adds
the per-executable device-truth table (FLOPs, HBM, collectives).

``--json`` emits one machine-readable JSON object instead of tables
(the smoke path CI exercises).

``--diff`` flattens ANY two JSON files to numeric leaves (registry
``.metrics.json`` snapshots and ``BENCH_r*.json`` records both work),
prints per-metric deltas, and exits 1 when a metric regressed past
``--threshold`` (relative). Direction is inferred from the metric
name: throughput-like series regress downward, latency-like series
regress upward; unrecognized series are reported but never gate.
"""

from __future__ import annotations

import argparse
import json
import sys


def load_trace(path: str) -> list[dict]:
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    return [e for e in events if e.get("ph") == "X"]


def span_table(events: list[dict]) -> list[dict]:
    """Per-name aggregate over complete ('X') events, sorted by total
    duration descending."""
    agg: dict[str, dict] = {}
    for e in events:
        dur_ms = float(e.get("dur", 0.0)) / 1e3
        a = agg.setdefault(e["name"], {
            "name": e["name"], "count": 0, "total_ms": 0.0,
            "max_ms": 0.0})
        a["count"] += 1
        a["total_ms"] += dur_ms
        a["max_ms"] = max(a["max_ms"], dur_ms)
    rows = sorted(agg.values(), key=lambda r: -r["total_ms"])
    for r in rows:
        r["mean_ms"] = r["total_ms"] / max(r["count"], 1)
    return rows


def parse_prometheus(path: str) -> dict[str, float]:
    """Flat {series: value} from Prometheus text exposition (the
    OpenMetrics exemplar suffix serving histogram buckets carry —
    ``... # {trace_id="..."} v`` — is stripped, keeping the bucket
    count as the series value)."""
    out: dict[str, float] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if " # {" in line:
                line = line.split(" # {", 1)[0].rstrip()
            try:
                series, value = line.rsplit(None, 1)
                out[series] = float(value)
            except ValueError:
                continue
    return out


def parse_metrics_json(path: str) -> dict[str, float]:
    """Flat {series: value} from the registry's JSON snapshot (scalar
    metrics + histogram count/sum/mean)."""
    with open(path) as f:
        snap = json.load(f)
    out: dict[str, float] = {}
    for name, meta in snap.items():
        for entry in meta.get("values", []):
            labels = entry.get("labels") or {}
            suffix = "".join(f"/{k}={v}" for k, v in sorted(labels.items()))
            if meta.get("type") == "histogram":
                out[f"{name}{suffix}_count"] = entry.get("count", 0)
                out[f"{name}{suffix}_sum"] = entry.get("sum", 0.0)
                out[f"{name}{suffix}_mean"] = entry.get("mean", 0.0)
            else:
                out[f"{name}{suffix}"] = entry.get("value", 0.0)
    return out


def serving_summary(metrics: dict) -> dict:
    """Serving-focused rollup of the flat metrics: every
    ``ds_serving_*`` series (fused-decode efficiency, latency histogram
    aggregates, and the prefix-cache hit/miss/eviction counters +
    occupancy gauges), plus a derived block-level
    ``prefix_hit_rate_derived`` when the hit/miss counters are
    present. Runtime-sanitizer violation counters (``ds_blocksan_*`` /
    ``ds_affinity_*``, ISSUE 11; ``ds_meshsan_*``, ISSUE 15) ride
    along when present — a nonzero value there is a correctness
    finding, not a perf number. The MoE router gauges (``ds_moe_*``
    drop-fraction / expert-load / capacity, ISSUE 16) and the fleet
    health gauges (``ds_fleet_*`` per-replica phi / score / state,
    ISSUE 17) join the same table, so MoE and fleet serving health
    read without raw snapshots."""
    out = {k: v for k, v in sorted(metrics.items())
           if "ds_serving_" in k or "ds_blocksan_" in k
           or "ds_affinity_" in k or "ds_meshsan_" in k
           or "ds_kv_" in k or "ds_moe_" in k or "ds_fleet_" in k
           or "ds_numsan_" in k or "ds_steptrace_" in k
           or "ds_train_goodput" in k or "ds_train_badput" in k}

    def total(stem: str):
        vals = [v for k, v in metrics.items() if stem in k
                and not k.endswith(("_mean",))]
        return sum(vals) if vals else None

    hits = total("ds_serving_prefix_hits_total")
    misses = total("ds_serving_prefix_misses_total")
    if hits is not None and misses is not None and hits + misses > 0:
        out["prefix_hit_rate_derived"] = round(hits / (hits + misses), 4)
    return out


def train_summary(metrics: dict) -> dict:
    """Training-focused rollup (ISSUE 18): the ``ds_train_*`` step /
    loss / loss-scale series, the device-truth overflow counter
    (``ds_overflow_steps_total``), and the numsan numerics findings
    (``ds_numsan_violations_total{kind}`` +
    ``ds_numsan_saturation_ratio{site}``) in ONE table — a blown-up
    run reads as "overflow count, which finding kind, which quantize
    site" without raw snapshots. Adds a derived
    ``overflow_rate_derived`` (overflow steps / total steps) when both
    counters are present.

    The steptrace goodput/badput table (ISSUE 20) rides the same
    rollup: ``ds_train_goodput_fraction``,
    ``ds_train_badput_seconds{bucket}``, the per-step component
    p50/p99 gauges and ``ds_steptrace_*`` (recon error, step count,
    regression findings counter) all carry the ``ds_train_`` /
    ``ds_steptrace_`` stems, plus a derived
    ``badput_total_seconds_derived`` sum over the buckets."""
    out = {k: v for k, v in sorted(metrics.items())
           if "ds_train_" in k or "ds_overflow_" in k
           or "ds_numsan_" in k or "ds_steptrace_" in k}
    steps = next((v for k, v in metrics.items()
                  if "ds_train_steps_total" in k), None)
    ov = next((v for k, v in metrics.items()
               if "ds_overflow_steps_total" in k), None)
    if steps and ov is not None and steps > 0:
        out["overflow_rate_derived"] = round(ov / steps, 4)
    badput = [v for k, v in metrics.items()
              if "ds_train_badput_seconds" in k]
    if badput:
        out["badput_total_seconds_derived"] = round(sum(badput), 6)
    return out


def build_report(trace_path: str, metrics_path: str | None,
                 ledger_path: str | None = None) -> dict:
    events = load_trace(trace_path)
    rows = span_table(events)
    report = {
        "trace": trace_path,
        "n_events": len(events),
        "span_names": len(rows),
        "spans": rows,
    }
    if metrics_path:
        if metrics_path.endswith(".json"):
            report["metrics"] = parse_metrics_json(metrics_path)
        else:
            report["metrics"] = parse_prometheus(metrics_path)
        report["serving"] = serving_summary(report["metrics"])
        report["train"] = train_summary(report["metrics"])
    if ledger_path:
        with open(ledger_path) as f:
            report["ledger"] = json.load(f)
    return report


def print_report(report: dict) -> None:
    print(f"trace: {report['trace']} — {report['n_events']} events, "
          f"{report['span_names']} span names")
    print(f"{'span':<28}{'count':>8}{'total ms':>12}{'mean ms':>10}"
          f"{'max ms':>10}")
    for r in report["spans"]:
        print(f"{r['name'][:27]:<28}{r['count']:>8}"
              f"{r['total_ms']:>12.2f}{r['mean_ms']:>10.2f}"
              f"{r['max_ms']:>10.2f}")
    metrics = report.get("metrics")
    if metrics:
        print()
        print(f"{'metric':<64}{'value':>14}")
        for series in sorted(metrics):
            v = metrics[series]
            sval = f"{v:.6g}" if isinstance(v, float) else str(v)
            print(f"{series[:63]:<64}{sval:>14}")
    serving = report.get("serving")
    if serving:
        print()
        print("serving summary (ds_serving_* incl. prefix cache + "
              "graftsan/meshsan sanitizer counters):")
        print(f"{'series':<64}{'value':>14}")
        for series in sorted(serving):
            v = serving[series]
            sval = f"{v:.6g}" if isinstance(v, float) else str(v)
            print(f"{series[:63]:<64}{sval:>14}")
    train = report.get("train")
    if train:
        print()
        print("train summary (ds_train_* + overflow + numsan numerics "
              "findings/saturation):")
        print(f"{'series':<64}{'value':>14}")
        for series in sorted(train):
            v = train[series]
            sval = f"{v:.6g}" if isinstance(v, float) else str(v)
            print(f"{series[:63]:<64}{sval:>14}")
    ledger = report.get("ledger")
    if ledger:
        print()
        print(f"executable ledger ({ledger.get('n_executables', 0)} "
              "executables; compiler cost/memory ground truth):")
        print(f"{'name':<22}{'calls':>7}{'GFLOP':>10}{'GB acc':>9}"
              f"{'peak HBM':>12}{'collectives':>12}  signature")
        for row in ledger.get("executables", []):
            print(f"{row['name'][:21]:<22}{row['calls']:>7}"
                  f"{row['flops'] / 1e9:>10.3f}"
                  f"{row['bytes_accessed'] / 1e9:>9.3f}"
                  f"{row['peak_hbm_bytes']:>12}"
                  f"{len(row.get('collectives', [])):>12}  "
                  f"{row['signature'][:40]}")
        traffic = ledger.get("traffic", {})
        if traffic:
            print("collective traffic (dispatch-weighted, per mesh "
                  "axis):")
            print(f"{'axis/op':<30}{'sites':>7}{'bytes':>16}")
            for key in sorted(traffic):
                row = traffic[key]
                print(f"{key[:29]:<30}{row['sites']:>7}"
                      f"{row['bytes']:>16}")


# ---------------------------------------------------------------------
# --fleet: fleet.json artifact -> per-replica + fleet rollup view
# ---------------------------------------------------------------------

def fleet_report(path: str) -> dict:
    """Per-replica + fleet rollup view from the versioned
    ``fleet.json`` artifact ALONE (``telemetry.export_artifacts``
    writes it when the fleet plane is on) — no registry, no process,
    no other file needed."""
    with open(path) as f:
        doc = json.load(f)
    replicas = doc.get("replicas") or {}
    return {
        "fleet_id": doc.get("fleet_id"),
        "schema_version": doc.get("schema_version"),
        "version": doc.get("version"),
        "n_replicas": len(replicas),
        "replicas": {n: serving_summary(flat)
                     for n, flat in sorted(replicas.items())},
        "fleet": serving_summary(doc.get("fleet_flat") or {}),
        "health": doc.get("health") or {},
        "errors": doc.get("errors") or {},
    }


def print_fleet(report: dict) -> None:
    print(f"fleet '{report['fleet_id']}' — "
          f"{report['n_replicas']} replica(s), artifact version "
          f"{report['version']} (schema v{report['schema_version']})")
    health = report["health"]
    if health:
        print()
        print("replica health (phi-accrual detector + composite "
              "score):")
        print(f"{'replica':<18}{'state':>10}{'phi':>9}{'score':>8}"
              f"{'beats':>8}{'deaths':>8}{'beat age s':>12}")
        for name in sorted(health):
            row = health[name]
            age = row.get("last_heartbeat_age_s")
            print(f"{name[:17]:<18}{row.get('state', '?'):>10}"
                  f"{row.get('phi', 0.0):>9.3f}"
                  f"{row.get('score', 0.0):>8.3f}"
                  f"{row.get('heartbeats', 0):>8}"
                  f"{row.get('deaths', 0):>8}"
                  f"{age if age is not None else '-':>12}")
    names = sorted(report["replicas"])
    series = sorted({s for flat in report["replicas"].values()
                     for s in flat})
    if series:
        print()
        print("per-replica serving series:")
        print(f"{'series':<52}" + "".join(f"{n[:13]:>14}"
                                          for n in names))
        for s in series:
            cells = "".join(
                f"{report['replicas'][n].get(s, ''):>14.6g}"
                if isinstance(report["replicas"][n].get(s), float)
                else f"{report['replicas'][n].get(s, '-')!s:>14}"
                for n in names)
            print(f"{s[:51]:<52}{cells}")
    fleet = report["fleet"]
    if fleet:
        print()
        print("fleet rollup (counters summed exactly across "
              "replicas; gauges summed — see fleet.json aggregates "
              "for min/max/mean):")
        print(f"{'series':<64}{'value':>14}")
        for s in sorted(fleet):
            v = fleet[s]
            sval = f"{v:.6g}" if isinstance(v, float) else str(v)
            print(f"{s[:63]:<64}{sval:>14}")
    if report["errors"]:
        print()
        for name, err in sorted(report["errors"].items()):
            print(f"unreadable replica {name}: {err}")


# ---------------------------------------------------------------------
# --merge: per-rank Chrome traces -> one Perfetto timeline
# ---------------------------------------------------------------------

def merge_traces(out_path: str, inputs: list[str]) -> dict:
    """Merge several per-rank Chrome-trace files into one document
    with rank-labelled process tracks. Each input keeps its own pid
    (re-assigned to its position when inputs collide on pid 0 — the
    common single-process-per-rank case), so Perfetto renders one
    swimlane group per rank and straggler skew is visible at a
    glance."""
    events: list[dict] = []
    seen_pids: set[int] = set()
    meta: dict = {"merged_from": []}
    for rank, path in enumerate(inputs):
        with open(path) as f:
            doc = json.load(f)
        in_events = (doc.get("traceEvents", [])
                     if isinstance(doc, dict) else doc)
        pids = {e.get("pid", 0) for e in in_events}
        remap = {}
        for pid in sorted(pids):
            new = pid if pid not in seen_pids else rank * 10000 + pid
            while new in seen_pids:
                new += 1
            remap[pid] = new
            seen_pids.add(new)
        label_done = set()
        for e in in_events:
            e = dict(e)
            pid = remap.get(e.get("pid", 0), e.get("pid", 0))
            e["pid"] = pid
            if e.get("ph") == "M" and e.get("name") == "process_name":
                # one rank-qualified label per merged process track
                e = {**e, "args": {"name": f"rank {rank}: "
                     f"{(e.get('args') or {}).get('name', '')}"}}
                label_done.add(pid)
            events.append(e)
        for pid in remap.values():
            if pid not in label_done:
                events.append({"name": "process_name", "ph": "M",
                               "pid": pid, "tid": 0,
                               "args": {"name": f"rank {rank}"}})
        meta["merged_from"].append({"rank": rank, "path": path,
                                    "events": len(in_events)})
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "otherData": meta}
    with open(out_path, "w") as f:
        json.dump(doc, f)
    return doc


# ---------------------------------------------------------------------
# --diff: metric-snapshot regression gate
# ---------------------------------------------------------------------

# substrings deciding a metric's good direction for the gate. Checked
# lower-is-better FIRST: latency suffixes are more specific than the
# throughput stems (e.g. ..._tokens_per_sec vs ..._ttft_seconds_mean).
_LOWER_IS_BETTER = ("_seconds", "_ms", "latency", "ttft", "itl",
                    "skew", "dispatches_per_token", "_time")
_HIGHER_IS_BETTER = ("tokens_per_sec", "samples_per_second", "mfu",
                     "tflops", "hit_rate", "occupancy", "throughput",
                     "headroom", "/value")

# --gate serving (ISSUE 6): the serving regression gate CI runs against
# the previous bench artifact (BENCH_r*.json or a telemetry
# .metrics.json snapshot). Only metrics matching these substrings
# participate, each with its own direction (+1 higher-is-better) and
# relative threshold — the serving-SLO numbers get tighter gates than
# the generic --threshold sweep.
_GATES = {
    "serving": (
        ("fused_tick_p50_ms", -1, 0.10),
        ("tick_p50_ms", -1, 0.10),
        ("tick_vs_compute_ratio", -1, 0.10),
        ("dispatches_per_token", -1, 0.05),
        ("ttft_p99", -1, 0.15),
        ("ttft_seconds", -1, 0.15),
        ("itl_p99", -1, 0.15),
        ("itl_seconds", -1, 0.15),
        # speculative decoding (ISSUE 9): draft acceptance and the
        # tokens-committed-per-(row, tick)-slot multiplier must not
        # shrink, and the spec-on overhead on a drafts-never-hit
        # workload must not creep up. Listed before tokens_per_sec /
        # the _ms stems so the more specific names match first.
        ("spec_overhead_ms", -1, 0.10),
        ("acceptance_rate", +1, 0.05),
        ("tokens_per_dispatch", +1, 0.05),
        # per-request latency decomposition (ISSUE 10): bench
        # serve_openloop's `<component>_p50/p99_ms` fields (registry
        # gauge snapshots flatten without the component label, so
        # only the bench JSON participates). Only the OVERHEAD
        # components gate (queue wait, prefill, first-drain,
        # chain-boundary gap, preemption stall); decode_active scales
        # with tokens generated, so gating it would flag longer
        # outputs as regressions.
        # serving control plane (ISSUE 19): goodput under the
        # declared SLOs with the shed/controller armed must not
        # shrink, the controlled queue-wait p99 must not creep back up
        # (unbounded admission's failure), and the offline plan must keep
        # beating the hand-tuned baseline it was ranked against. The
        # deliberately-saturated control arms (uncontrolled_*,
        # baseline_/plan_ ttft/itl points) are excluded below.
        ("goodput_under_slo", +1, 0.05),
        ("queue_wait_p99", -1, 0.15),
        ("plan_vs_baseline", +1, 0.05),
        ("queue_wait", -1, 0.15),
        ("first_drain", -1, 0.15),
        ("boundary_gap", -1, 0.15),
        ("preempt_stall", -1, 0.15),
        ("prefill_p", -1, 0.15),
        # disaggregated serving (ISSUE 13, bench `disagg` stage): the
        # cross-mesh KV hand-off leg of the TTFT telescoping must not
        # creep up, and N-replica aggregate throughput must keep
        # scaling (replica_scaling = aggregate / (N x single-replica)).
        # The disagg ITL-flatness ratio (disagg_itl_p99_drift_...)
        # gates through the existing "itl_p99" stem; the deliberately-
        # unmitigated single-engine control figures are excluded below.
        ("migrate", -1, 0.15),
        ("replica_scaling", +1, 0.05),
        # quantized KV cache (ISSUE 12, bench `kvquant` stage): the
        # per-cached-token byte cost must not creep back up and the
        # resident-batch capacity at equal pool bytes must not shrink
        # (the stage's headline 2-4x lever). Tight thresholds — both
        # are deterministic layout arithmetic, not timing.
        ("kv_bytes_per_token", -1, 0.02),
        ("max_resident_batch", +1, 0.02),
        ("tokens_per_sec", +1, 0.05),
        ("fused_occupancy", +1, 0.05),
    ),
    # autotune stage (ISSUE 7): the planner's cost model must not get
    # less accurate (prediction_rel_err: worst relative error over the
    # measured top-K), and the chosen plan's measured throughput must
    # not regress — neither absolutely nor against the hand-tuned
    # baseline config measured in the same stage (plan_vs_baseline).
    "autotune": (
        ("prediction_rel_err", -1, 0.30),
        ("plan_vs_baseline", +1, 0.05),
        ("plan_tokens_per_sec", +1, 0.05),
    ),
    # comms gate (ISSUE 8): the ZeRO++ quantized-wire win is CI-checked
    # against the previous bench artifact / metrics snapshot — HLO-
    # accounted collective payload must not creep back up (a sharding
    # or wire-protocol regression shows up as bytes before it shows up
    # as time, and the static accounting is noise-free so the
    # threshold is tight), the achieved sharded-DP reduction must not
    # shrink, and throughput stays within the usual ±5%.
    "comms": (
        ("wire_reduction", +1, 0.02),
        ("wire_bytes_per_el", -1, 0.02),
        ("wire_bytes", -1, 0.02),
        ("collective_bytes", -1, 0.02),
        ("tokens_per_sec", +1, 0.05),
    ),
    # MoE gate (ISSUE 16, bench `moe_train` + `moe_serve` stages):
    # training MFU on active-params accounting and its ratio against
    # the equal-active-params dense run must not shrink; the int8
    # dispatch-wire slow-link cut is static HLO byte arithmetic (tight
    # threshold), its loss fidelity must not drift; fused-decode
    # throughput, its step-up vs the equal-active-size dense engine,
    # and the greedy-parity horizon gate the serving half.
    "moe": (
        ("dispatch_wire_cut_slow", +1, 0.02),
        ("dispatch_slow_bytes", -1, 0.02),
        ("loss_rel_err_int8_wire", -1, 0.50),
        ("mfu_vs_dense", +1, 0.05),
        ("moe_mfu", +1, 0.05),
        ("moe_vs_dense", +1, 0.05),
        ("greedy_parity_horizon", +1, 0.0),
        ("tokens_per_sec", +1, 0.05),
    ),
    # fleet gate (ISSUE 17, bench `fleet` stage): a replica is killed
    # under open-loop load — how fast the phi-accrual detector marks
    # it and the router stops placing onto it (detection /
    # detection-to-reroute latency), the multi-window SLO burn rates
    # during the incident, and the per-replica placement skew must not
    # creep up; dropped requests are ZERO-tolerance (the drain-and-
    # reroute contract — any drop from a zero baseline gates), and
    # surviving-fleet throughput must hold.
    "fleet": (
        ("detection_to_reroute_ms", -1, 0.25),
        ("detection_ms", -1, 0.25),
        ("slo_burn_rate", -1, 0.25),
        ("dropped", -1, 0.0),
        ("replica_skew", -1, 0.15),
        ("tokens_per_sec", +1, 0.05),
    ),
    # numerics gate (ISSUE 18, bench `numsan` stage + training
    # snapshots): quantize-site saturation must not creep up from the
    # healthy baseline (silent clipping shows up here long before it
    # shows up as loss), fp16 overflow-skipped steps must not grow
    # (zero-tolerance against a zero baseline), the numsan-disabled
    # path must keep compiling ZERO extra executables (deterministic,
    # zero-tolerance), and the armed-probe run's throughput stays
    # within the usual ±5%.
    "numerics": (
        ("saturation_ratio", -1, 0.0),
        ("overflow_steps", -1, 0.0),
        ("extra_executables", -1, 0.0),
        ("tokens_per_sec", +1, 0.05),
    ),
    # train gate (ISSUE 20, steptrace): run goodput must not shrink,
    # the host-overhead legs of the step telescoping (data wait,
    # checkpoint stall) must not creep up — the stems match the
    # component p50/p99 gauges, the bench fields AND the aggregated
    # JSONL step log (data_wait_ms_p99 etc. via _load_numeric) — and
    # the steptrace-disabled path must keep compiling ZERO extra
    # executables (deterministic, zero-tolerance). Throughput rides at
    # the usual ±5%.
    "train": (
        ("goodput_fraction", +1, 0.05),
        ("data_wait", -1, 0.15),
        ("ckpt_stall", -1, 0.15),
        ("component=checkpoint", -1, 0.15),
        ("checkpoint_ms", -1, 0.15),
        ("extra_executables", -1, 0.0),
        ("tokens_per_sec", +1, 0.05),
    ),
}

# metric families a gate must NOT touch even though a stem matches by
# substring: the host-in-loop per-tick scheduler figures include one
# host round trip per tick (serve7b `per_tick_p50_ms`, serving
# `v2_tick_p50_ms`) and would flap the gate on dispatch-path jitter
# unrelated to the engine.
_GATE_EXCLUDE = {
    # ... plus the disagg stage's CONTROL-arm figures: the single-
    # engine drift ratio and raw per-length chat ITL points exist to
    # show the degradation disaggregation removes — inherently noisy
    # and not a product metric (the disagg_* drift ratio still gates)
    # ... and the ISSUE 19 control arms: the uncontrolled load-step
    # run exists to be terrible (its queue grows unbounded by design),
    # and the saturated serve_autotune latency points grade the
    # traffic, not the engine — the goodput ratios above still gate
    "serving": ("per_tick", "v2_tick", "single_itl", "chat_itl_p99_ms",
                "uncontrolled", "baseline_ttft", "plan_ttft",
                "baseline_itl", "plan_itl", "ctl_itl", "ctl_ttft"),
    # the all-measured error includes the short-step base candidate,
    # the noisiest row — informational, the top-K figure gates
    "autotune": ("rel_err_all",),
}


def _gate_rule(name: str, gate: str):
    """(direction, threshold) for a gated metric, or None when the
    metric does not participate in this gate. First match wins —
    order the table most-specific-first."""
    low = name.lower()
    if any(excl in low for excl in _GATE_EXCLUDE.get(gate, ())):
        return None
    for stem, direction, threshold in _GATES[gate]:
        if stem in low:
            return direction, threshold
    return None


def _flatten_numeric(obj, prefix="") -> dict[str, float]:
    """Any JSON document -> {path: number} over numeric leaves (bool
    excluded). Registry snapshots, bench records, plain dicts all
    flatten the same way."""
    out: dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(_flatten_numeric(v, f"{prefix}/{k}" if prefix
                                        else str(k)))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            out.update(_flatten_numeric(v, f"{prefix}[{i}]"))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix] = float(obj)
    return out


def _load_numeric(path: str) -> dict[str, float]:
    """Numeric leaves of a snapshot file. Accepts a single JSON
    document (registry snapshot, bench record) — or a JSONL log (the
    steptrace step log, the reqtrace access log): JSONL rows aggregate
    per numeric key into ``<key>_{mean,p50,p99,max}`` plus a ``rows``
    count, so two runs of different lengths diff cleanly."""
    with open(path) as f:
        text = f.read()
    try:
        return _flatten_numeric(json.loads(text))
    except json.JSONDecodeError:
        pass
    series: dict[str, list[float]] = {}
    rows = 0
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        rows += 1
        for k, v in _flatten_numeric(json.loads(line)).items():
            series.setdefault(k, []).append(v)
    out: dict[str, float] = {"rows": float(rows)}
    for k, vals in series.items():
        vals.sort()
        out[f"{k}_mean"] = sum(vals) / len(vals)
        out[f"{k}_p50"] = vals[len(vals) // 2]
        out[f"{k}_p99"] = vals[min(len(vals) - 1, int(len(vals) * 0.99))]
        out[f"{k}_max"] = vals[-1]
    return out


def _direction(name: str) -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 report-only."""
    low = name.lower()
    for stem in _LOWER_IS_BETTER:
        if stem in low:
            return -1
    for stem in _HIGHER_IS_BETTER:
        if stem in low:
            return +1
    return 0


def diff_snapshots(path_a: str, path_b: str,
                   threshold: float = 0.05,
                   gate: str | None = None) -> dict:
    """Compare two metric snapshots (A = baseline, B = candidate).
    Returns {rows, regressions, added, removed}; a row regresses when
    its direction-aware relative change exceeds ``threshold``. With
    ``gate`` (e.g. ``"serving"``) only the gate's metric families
    participate, each under its own per-metric threshold."""
    a = _load_numeric(path_a)
    b = _load_numeric(path_b)
    rows, regressions = [], []
    for name in sorted(set(a) & set(b)):
        va, vb = a[name], b[name]
        if gate is not None:
            rule = _gate_rule(name, gate)
            if rule is None:
                continue
            direction, row_threshold = rule
        else:
            direction, row_threshold = _direction(name), threshold
        rel = (vb - va) / abs(va) if va else (0.0 if vb == va
                                             else float("inf"))
        regressed = bool(
            direction == +1 and rel < -row_threshold
            or direction == -1 and rel > row_threshold)
        row = {"metric": name, "a": va, "b": vb, "rel": rel,
               "direction": direction, "threshold": row_threshold,
               "regressed": regressed}
        rows.append(row)
        if regressed:
            regressions.append(row)
    return {"rows": rows, "regressions": regressions,
            "added": sorted(set(b) - set(a)),
            "removed": sorted(set(a) - set(b)),
            "threshold": threshold, "gate": gate}


def print_diff(diff: dict) -> None:
    print(f"{'metric':<58}{'A':>13}{'B':>13}{'delta%':>9}  gate")
    for row in diff["rows"]:
        rel = row["rel"]
        pct = f"{rel * 100:+.2f}" if abs(rel) != float("inf") else "inf"
        gate = ("REGRESSED" if row["regressed"]
                else {1: "up-good", -1: "down-good", 0: ""}
                [row["direction"]])
        print(f"{row['metric'][:57]:<58}{row['a']:>13.6g}"
              f"{row['b']:>13.6g}{pct:>9}  {gate}")
    for name in diff["removed"]:
        print(f"{name[:57]:<58}{'':>13}{'-':>13}{'':>9}  removed")
    for name in diff["added"]:
        print(f"{name[:57]:<58}{'-':>13}{'':>13}{'':>9}  added")
    n = len(diff["regressions"])
    scope = (f"gate '{diff['gate']}' (per-metric thresholds)"
             if diff.get("gate")
             else f"±{diff['threshold'] * 100:.1f}%")
    print(f"\n{n} regression(s) past {scope} "
          f"over {len(diff['rows'])} shared metrics")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarize / merge / diff deepspeed_tpu telemetry "
                    "dumps")
    ap.add_argument("paths", nargs="*",
                    help="report mode: TRACE [METRICS]; --merge mode: "
                         "per-rank trace inputs; --diff mode: A B")
    ap.add_argument("--ledger", default=None,
                    help="per-executable ledger JSON "
                         "(telemetry *.ledger.json)")
    ap.add_argument("--merge", metavar="OUT", default=None,
                    help="merge the input Chrome traces into OUT with "
                         "rank-labelled tracks")
    ap.add_argument("--diff", action="store_true",
                    help="diff two metric snapshots (A B) — JSON "
                         "documents or JSONL logs (steptrace step "
                         "logs aggregate per-key mean/p50/p99/max); "
                         "exit 1 on regression past --threshold")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative regression threshold for --diff "
                         "(default 0.05)")
    ap.add_argument("--gate", choices=sorted(_GATES), default=None,
                    help="restrict --diff to a named gate's metric "
                         "families with per-metric direction-aware "
                         "thresholds (e.g. 'serving': tick_p50_ms, "
                         "dispatches_per_token, TTFT/ITL p99, "
                         "tokens_per_sec); exit 1 on regression")
    ap.add_argument("--fleet", metavar="FLEET_JSON", default=None,
                    help="render per-replica + fleet rollup + health "
                         "views from a telemetry *.fleet.json "
                         "artifact (standalone mode)")
    ap.add_argument("--json", action="store_true",
                    help="emit one machine-readable JSON object")
    args = ap.parse_args(argv)

    if args.fleet:
        report = fleet_report(args.fleet)
        if args.json:
            json.dump(report, sys.stdout)
            print()
        else:
            print_fleet(report)
        return 0

    if args.merge:
        if len(args.paths) < 1:
            ap.error("--merge needs at least one input trace")
        doc = merge_traces(args.merge, args.paths)
        print(f"merged {len(doc['otherData']['merged_from'])} traces "
              f"({len(doc['traceEvents'])} events) -> {args.merge}")
        return 0

    if args.diff:
        if len(args.paths) != 2:
            ap.error("--diff needs exactly two snapshot paths: A B")
        diff = diff_snapshots(args.paths[0], args.paths[1],
                              threshold=args.threshold, gate=args.gate)
        if args.json:
            json.dump(diff, sys.stdout)
            print()
        else:
            print_diff(diff)
        return 1 if diff["regressions"] else 0

    if not args.paths:
        ap.error("report mode needs a trace path "
                 "(or use --merge / --diff)")
    report = build_report(args.paths[0],
                          args.paths[1] if len(args.paths) > 1 else None,
                          ledger_path=args.ledger)
    if args.json:
        json.dump(report, sys.stdout)
        print()
    else:
        print_report(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
