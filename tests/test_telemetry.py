"""Telemetry subsystem (ISSUE 2): span tracer semantics + Chrome-trace
schema, metrics registry + Prometheus exposition, engine/serving
instrumentation, comms bandwidth accounting, and the disabled-mode
overhead guards."""

import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry.registry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Each test starts and ends with telemetry inactive."""
    telemetry.shutdown()
    yield
    telemetry.shutdown()


# ---------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    # labels are independent series
    c.inc(op="a")
    c.inc(3, op="b")
    assert c.value(op="a") == 1.0 and c.value(op="b") == 3.0
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # set_total mirrors an external monotonic counter, never backwards
    c.set_total(10, op="a")
    c.set_total(4, op="a")
    assert c.value(op="a") == 10.0

    g = reg.gauge("depth")
    g.set(7, engine="v2")
    g.dec(2, engine="v2")
    assert g.value(engine="v2") == 5.0

    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["sum"] == pytest.approx(5.555)
    assert s["buckets"][0.01] == 1
    assert s["buckets"][0.1] == 2
    assert s["buckets"][1.0] == 3
    assert s["buckets"][math.inf] == 4

    # idempotent getter returns the same object; type conflict raises
    assert reg.counter("req_total") is c
    with pytest.raises(TypeError):
        reg.gauge("req_total")


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("ds_x_total", "the x").inc(2, op="all_reduce")
    reg.gauge("ds_mem_bytes").set(123.0, kind='we"ird\nname')
    h = reg.histogram("ds_lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05, route="gen")
    h.observe(3.0, route="gen")
    text = reg.prometheus_text()
    assert "# HELP ds_x_total the x" in text
    assert "# TYPE ds_x_total counter" in text
    assert 'ds_x_total{op="all_reduce"} 2.0' in text
    # label escaping: quote and newline
    assert 'kind="we\\"ird\\nname"' in text
    # histogram: cumulative buckets + +Inf + sum/count
    assert 'ds_lat_seconds_bucket{route="gen",le="0.1"} 1' in text
    assert 'ds_lat_seconds_bucket{route="gen",le="1.0"} 1' in text
    assert 'ds_lat_seconds_bucket{route="gen",le="+Inf"} 2' in text
    assert 'ds_lat_seconds_sum{route="gen"} 3.05' in text
    assert 'ds_lat_seconds_count{route="gen"} 2' in text
    # snapshot/json round-trips
    snap = json.loads(reg.to_json())
    assert snap["ds_x_total"]["type"] == "counter"
    assert snap["ds_lat_seconds"]["values"][0]["count"] == 2


def test_prometheus_label_and_help_escaping():
    """Text-exposition escaping audit (ISSUE 10 satellite): label
    VALUES escape backslash, quote and newline — backslash FIRST, so
    escapes aren't re-escaped; HELP text escapes backslash and newline
    but NOT quotes (quotes are legal in help). Request-derived label
    values (trace ids, outcomes, error strings) flow through here."""
    reg = MetricsRegistry()
    reg.counter("ds_esc_total", 'help with "quotes"\nand \\slash').inc(
        1, path='C:\\tmp\n"x"')
    text = reg.prometheus_text()
    # label value: backslash doubled, quote escaped, newline literalized
    assert r'path="C:\\tmp\n\"x\""' in text
    # HELP: backslash + newline escaped, quotes left alone
    assert '# HELP ds_esc_total help with "quotes"\\nand \\\\slash' in text
    # the raw newline from the label value must not split the line
    assert 'C:\\tmp\n' not in text
    # a backslash-only value stays parseable (escape-the-escapes order)
    reg2 = MetricsRegistry()
    reg2.gauge("ds_bs").set(1.0, v="\\")
    assert 'v="\\\\"' in reg2.prometheus_text()


def test_events_for_monitor_flattens_scalars_and_histograms():
    reg = MetricsRegistry()
    reg.gauge("ds_g").set(1.5, k="v")
    h = reg.histogram("ds_h_seconds")
    h.observe(0.2)
    events = reg.events_for_monitor(step=7)
    names = {n for n, _, _ in events}
    assert ("Telemetry/ds_g/k=v", 1.5, 7) in events
    assert "Telemetry/ds_h_seconds_count" in names
    assert "Telemetry/ds_h_seconds_mean" in names
    assert all(s == 7 for _, _, s in events)


# ---------------------------------------------------------------------
# span tracer + Chrome-trace schema
# ---------------------------------------------------------------------

def test_span_nesting_and_chrome_trace_schema(tmp_path):
    telemetry.configure(span_buffer_size=64)
    with telemetry.span("outer", step=1):
        time.sleep(0.002)
        with telemetry.span("inner", dispatch_id=5):
            time.sleep(0.001)
    tracer = telemetry.get_tracer()
    by_name = {s.name: s for s in tracer.spans()}
    assert by_name["outer"].depth == 0 and by_name["inner"].depth == 1
    assert by_name["inner"].dur_us <= by_name["outer"].dur_us

    # export, load back, validate the Chrome trace event schema
    path = tracer.export_chrome_trace(str(tmp_path / "t.trace.json"))
    doc = json.load(open(path))
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 2
    for e in xs:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] > 0 and e["ts"] >= 0
    inner = next(e for e in xs if e["name"] == "inner")
    outer = next(e for e in xs if e["name"] == "outer")
    # containment: the nested event lies inside its parent's interval
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["args"]["dispatch_id"] == 5
    assert outer["args"]["step"] == 1


def test_trace_decorator_and_ring_bound():
    telemetry.configure(span_buffer_size=8)

    @telemetry.trace(name="decorated")
    def f(x):
        return x + 1

    for i in range(20):
        assert f(i) == i + 1
    tracer = telemetry.get_tracer()
    assert len(tracer.spans()) == 8          # ring bounded
    assert tracer.recorded == 20             # totals survive eviction
    sec, cnt = tracer.totals()["decorated"]
    assert cnt == 20 and sec > 0


def test_inactive_span_is_shared_noop():
    assert not telemetry.is_active()
    cm = telemetry.span("x", step=1)
    assert cm is telemetry.NULL_CONTEXT
    with cm:
        pass
    assert telemetry.get_tracer() is None
    assert telemetry.get_registry() is None

    # decorator checks activation per call: no spans recorded while off
    @telemetry.trace
    def g():
        return 1

    assert g() == 1
    telemetry.configure()
    assert g() == 1
    assert telemetry.get_tracer().recorded == 1


def test_jax_compile_events_captured():
    telemetry.configure()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7))
    reg = telemetry.get_registry()
    assert reg.counter("ds_jax_compile_total").value(
        phase="backend_compile") >= 1
    assert reg.counter("ds_compile_seconds_total").value(
        phase="backend_compile") > 0


# ---------------------------------------------------------------------
# engine + serving instrumentation
# ---------------------------------------------------------------------

def test_engine_spans_breakdown_and_monitor_flush(tmp_path, devices8):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2
    engine, _, _, _ = ds.initialize(model=GPT2(size="tiny"), config={
        "train_batch_size": 16,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 2,
        "wall_clock_breakdown": True,
        "telemetry": {"enabled": True},
        "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                        "job_name": "tel"}})
    assert telemetry.is_active()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (16, 17), 0, 512)
    batch = (tokens[:, :-1], tokens[:, 1:])
    for _ in range(2):
        engine.train_batch(batch)
    tracer = telemetry.get_tracer()
    depths = {(s.name, s.depth) for s in tracer.spans()}
    assert ("train_batch", 0) in depths          # nested train-step spans
    assert ("compiled_step", 1) in depths
    assert ("batch_to_device", 1) in depths
    reg = telemetry.get_registry()
    assert reg.counter("ds_train_steps_total").value() == 2
    assert reg.gauge("ds_train_loss").value() > 0
    csv = open(tmp_path / "tel.csv").read()
    # satellite: wall_clock_breakdown -> monitor events at
    # steps_per_print boundaries, sourced from span data
    assert "Train/Samples/elapsed_time_ms_train_batch" in csv
    # registry -> MonitorMaster flush
    assert "Telemetry/ds_train_loss" in csv
    assert "Telemetry/ds_jax_compile_total" in csv


def test_serving_latency_histograms_from_fused_decode(tmp_path, devices8):
    """Acceptance: a CPU fused-decode run produces TTFT/ITL histograms,
    serving counters matching the engine's, a Perfetto-loadable trace
    with nested decode-dispatch spans, and a Prometheus dump carrying
    serving + comms + memory + compile families."""
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    from deepspeed_tpu.parallel.mesh import MeshTopology, TopologyConfig
    from deepspeed_tpu.runtime.config import CommsLoggerConfig
    from deepspeed_tpu.utils.jax_compat import shard_map
    from jax.sharding import PartitionSpec as P
    telemetry.configure()

    # a real collective through the comms facade, so the dump carries
    # the comms family alongside serving/memory/compile
    import deepspeed_tpu.comm.comm as dist_mod
    prev_logger = dist.get_comms_logger()
    dist.configure_comms_logger(CommsLoggerConfig(enabled=True))
    topo = MeshTopology(TopologyConfig(fsdp=8))
    jax.jit(shard_map(lambda s: dist.all_reduce(s, group="fsdp"),
                      mesh=topo.mesh, in_specs=P("fsdp"),
                      out_specs=P("fsdp")))(jnp.arange(8.0))
    model = Llama(size="tiny", max_seq_len=256)
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        dtype="float32", kv_block_size=64, num_kv_blocks=64,
        max_chunk_size=64, fused_decode_steps=4))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.config.vocab_size, 12).tolist()
               for _ in range(3)]
    outs = e.generate_fused(prompts, max_new_tokens=6)
    assert [len(o) for o in outs] == [6, 6, 6]

    reg = telemetry.get_registry()
    m = e.serving_metrics()
    assert reg.counter("ds_serving_decoded_tokens_total").value(
        engine="v2") == m["decoded_tokens"] == 18
    ttft = reg.histogram("ds_serving_ttft_seconds").summary()
    itl = reg.histogram("ds_serving_itl_seconds").summary()
    assert ttft["count"] == 3                    # one per prompt
    assert itl["count"] == 18 - 3                # the rest of the tokens
    assert reg.histogram(
        "ds_serving_fused_dispatch_seconds").summary()["count"] >= 1

    tracer = telemetry.get_tracer()
    depths = {(s.name, s.depth) for s in tracer.spans()}
    assert ("v2/prefill", 0) in depths
    assert ("v2/dispatch", 1) in depths          # nested under prefill
    assert any(n in ("v2/fused_enqueue", "v2/fused_drain")
               for n, _ in depths)

    try:
        paths = telemetry.export_artifacts(str(tmp_path), prefix="serve",
                                           serving_metrics=m)
    finally:
        dist_mod._comms_logger = prev_logger
    doc = json.load(open(paths["trace"]))
    assert any(ev.get("name") == "v2/dispatch"
               for ev in doc["traceEvents"])
    prom = open(paths["prometheus"]).read()
    for family in ("ds_serving_decoded_tokens_total",
                   "ds_serving_ttft_seconds_bucket",
                   'ds_comm_calls_total{op="all_reduce"}',
                   "ds_host_memory_bytes",
                   "ds_jax_compile_total"):
        assert family in prom, family


def test_decode_fused_records_dispatch_histogram():
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    telemetry.configure()
    model = Llama(size="tiny", max_seq_len=256)
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        dtype="float32", kv_block_size=64, num_kv_blocks=64,
        max_chunk_size=64))
    rng = np.random.default_rng(1)
    uids = [0, 1]
    e.put(uids, [rng.integers(0, model.config.vocab_size, 8).tolist()
                 for _ in uids])
    for u in uids:
        e.state_manager.extend(u, [1])
    res = e.decode_fused(uids, k_steps=3)
    assert all(len(v) >= 1 for v in res.values())
    reg = telemetry.get_registry()
    assert reg.histogram(
        "ds_serving_fused_dispatch_seconds").summary()["count"] == 1
    tracer = telemetry.get_tracer()
    assert any(s.name == "v2/fused_dispatch" for s in tracer.spans())
    assert reg.gauge("ds_serving_free_kv_blocks").value(engine="v2") > 0


# ---------------------------------------------------------------------
# comms bandwidth accounting (satellite)
# ---------------------------------------------------------------------

def test_comms_log_summary_with_telemetry_window():
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    telemetry.configure()
    with telemetry.span("train_batch"):
        time.sleep(0.01)
    lg = CommsLogger()
    # sizes (numbers only: nothing is allocated) large enough that the
    # columns' three decimals hold the 1% below when a loaded machine makes
    # the window seven times the sleep (2 MiB over 70 ms printed 0.030 and
    # 0.027: a whole run's one failure, PR 55)
    lg.append("all_reduce", 1 << 30)
    lg.append("all_reduce", 1 << 30)
    lg.append("all_gather", 1 << 10)
    text = lg.log_summary(world_size=8, print_log=False)
    assert "algbw(GB/s)" in text and "busbw(GB/s)" in text
    row = next(l for l in text.splitlines() if "all_reduce (total)" in l)
    cols = row.split()
    algbw, busbw = float(cols[-2]), float(cols[-1])
    assert algbw > 0
    # all_reduce busbw = algbw * 2(n-1)/n (reference get_bw formula)
    assert busbw == pytest.approx(algbw * 2 * 7 / 8, rel=0.01)


def test_window_seconds_counts_depth0_only():
    """A span name recorded at BOTH top level and nested (v2/dispatch
    standalone vs under v2/prefill) must not double-count in the comms
    bandwidth window."""
    telemetry.configure()
    tracer = telemetry.get_tracer()
    with telemetry.span("v2/dispatch"):
        time.sleep(0.002)
    with telemetry.span("v2/prefill"):
        with telemetry.span("v2/dispatch"):
            time.sleep(0.002)
        time.sleep(0.001)
    prefill_s = tracer.totals()["v2/prefill"][0]
    dispatch0_s = tracer.totals()["v2/dispatch"][0] - prefill_s
    # window = depth-0 spans only: the standalone dispatch + prefill
    # (which already contains the nested dispatch)
    win = tracer.window_seconds()
    assert win < tracer.totals()["v2/dispatch"][0] + prefill_s
    assert win == pytest.approx(
        sum(s.dur_us for s in tracer.spans() if s.depth == 0) / 1e6)
    assert dispatch0_s  # silence unused warning; sanity: both recorded


def test_comms_window_rejected_when_tallies_predate_tracer():
    """A tracer configured or clear()ed AFTER collectives were tallied
    would overstate bandwidth; the window must be rejected (satellite:
    the lower-bound claim stays honest)."""
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    telemetry.configure()
    lg = CommsLogger()
    lg.append("all_reduce", 1 << 20)
    with telemetry.span("train_batch"):
        time.sleep(0.005)
    # paired: logger started after the tracer -> window accepted
    row = next(l for l in lg.log_summary(world_size=8, print_log=False)
               .splitlines() if "(total)" in l)
    assert row.split()[-1] != "-"
    # clear() re-opens the tracer window without the logger: rejected.
    # (backdate the logger past the 1s ordering tolerance — in a real
    # run the stage-1 tallies predate the cleared window by much more)
    telemetry.clear()
    with telemetry.span("train_batch"):
        time.sleep(0.001)
    lg.started_unix = telemetry.get_tracer().epoch_unix - 5.0
    row = next(l for l in lg.log_summary(world_size=8, print_log=False)
               .splitlines() if "(total)" in l)
    assert row.split()[-1] == "-"
    # reset() re-pairs them
    lg.reset()
    lg.append("all_reduce", 1 << 20)
    with telemetry.span("train_batch"):
        time.sleep(0.002)
    row = next(l for l in lg.log_summary(world_size=8, print_log=False)
               .splitlines() if "(total)" in l)
    assert row.split()[-1] != "-"


def test_comms_log_summary_edge_cases():
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    # telemetry off -> no measured window: '-' columns, no division
    lg = CommsLogger()
    lg.append("broadcast", 0)            # zero-size message
    text = lg.log_summary(print_log=False)
    assert "broadcast" in text and "-" in text
    # empty logger renders a placeholder, never raises
    assert "no collectives recorded" in CommsLogger().log_summary(
        print_log=False)
    # zero-call op key (defensive)
    lg2 = CommsLogger()
    lg2.comms_dict["ghost_op"]           # creates an empty entry
    assert "ghost_op" in lg2.log_summary(duration_s=1.0, print_log=False)


def test_collect_comms_bridge():
    from deepspeed_tpu.telemetry import bridges
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    reg = MetricsRegistry()
    lg = CommsLogger()
    lg.append("all_reduce", 2048)
    lg.append("all_reduce", 2048)
    bridges.collect_comms(reg, lg)
    assert reg.counter("ds_comm_calls_total").value(op="all_reduce") == 2
    assert reg.counter("ds_comm_bytes_total").value(op="all_reduce") == 4096


def test_flush_to_monitor_writes_events(tmp_path):
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.telemetry import bridges
    telemetry.configure()
    reg = telemetry.get_registry()
    reg.gauge("ds_thing").set(42.0)
    cfg = DeepSpeedConfig.from_any({
        "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                        "job_name": "flush"}})
    mon = MonitorMaster(cfg)
    n = bridges.flush_to_monitor(mon, step=3)
    assert n >= 1
    assert "Telemetry/ds_thing,42.0,3" in open(tmp_path / "flush.csv").read()


# ---------------------------------------------------------------------
# disabled-mode guards (satellite)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def plain_engine():
    """ONE engine built with telemetry off for the cases below: a case
    that wants telemetry configures it itself (the engine probes for it
    every step), so none of them builds an engine of its own."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    telemetry.shutdown()
    engine, _, _, _ = ds.initialize(model=GPT2(size="tiny"), config={
        "train_batch_size": 16,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 100})
    return engine


def _batch(seq=16):
    tokens = jax.random.randint(jax.random.PRNGKey(0), (16, seq + 1), 0, 512)
    return (tokens[:, :-1], tokens[:, 1:])


def test_disabled_mode_zero_events_and_no_hot_path_errors(plain_engine):
    """Telemetry off: engine + fused decode run clean, and no tracer or
    registry state ever comes into existence."""
    assert not telemetry.is_active()
    engine, batch = plain_engine, _batch()
    float(engine.train_batch(batch))
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()
    assert telemetry.get_tracer() is None
    assert telemetry.get_registry() is None
    # device-truth layer (ISSUE 5) obeys the same contract: no
    # ledger/flight-recorder/watchdog state on the disabled path
    assert telemetry.get_ledger() is None
    assert telemetry.get_flight_recorder() is None
    assert telemetry.get_watchdog() is None
    # fleet plane (ISSUE 17): same contract — no ring, detector, or
    # aggregator state while telemetry is off
    assert telemetry.get_timeseries() is None
    assert telemetry.get_health_monitor() is None
    assert telemetry.get_fleet() is None


def test_a_step_with_telemetry_off_imports_nothing(plain_engine):
    """``train_batch``'s three new contexts are the shared no-op when
    telemetry is off: with the package taken out of ``sys.modules`` a step
    (and a step that pulls its batch) runs and puts none of it back."""
    from deepspeed_tpu.runtime import engine as engine_mod
    held = {k: sys.modules.pop(k) for k in list(sys.modules)
            if k.split(".")[:2] == ["deepspeed_tpu", "telemetry"]}
    try:
        assert engine_mod._telemetry() is None
        float(plain_engine.train_batch(_batch()))
        float(plain_engine.train_batch(data_iter=iter([_batch()])))
        assert not [k for k in sys.modules
                    if k.startswith("deepspeed_tpu.telemetry")]
    finally:
        sys.modules.update(held)


def _children(spans, parent):
    return sorted((s for s in spans if s.depth == parent.depth + 1
                   and parent.ts_us <= s.ts_us
                   and s.ts_us + s.dur_us <= parent.ts_us + parent.dur_us),
                  key=lambda s: s.ts_us)


def test_train_batch_spans_cover_the_host_path(plain_engine):
    """The names and their order, every child inside ``train_batch``, and
    what no child covers (bare ``train_batch``: what a device trace's
    idle reads under that name) under 5% of the span."""
    telemetry.configure(executable_ledger=True)
    for _ in range(7):
        plain_engine.train_batch(_batch()).block_until_ready()
    spans = telemetry.get_tracer().spans()
    steps = [s for s in spans if s.name == "train_batch"][1:]
    assert len(steps) == 6
    bare = []
    for step in steps:
        kids = _children(spans, step)
        assert [k.name for k in kids] == [
            "train_batch/prepare", "batch_to_device", "train_batch/observe",
            "compiled_step", "train_batch/account"]
        assert all(a.ts_us + a.dur_us <= b.ts_us
                   for a, b in zip(kids, kids[1:]))
        bare.append(1.0 - sum(k.dur_us for k in kids) / step.dur_us)
    assert sorted(bare)[len(bare) // 2] < 0.05, bare
    names = {s.name for s in spans}
    assert "step_boundary" in names and "step_fetch" not in names
    depth0 = {s.name for s in spans if s.depth == 0}
    assert depth0 == {"train_batch", "step_boundary"}


def test_step_fetch_only_where_the_engine_pulls_the_batch(plain_engine):
    """An iterator's stall lies under ``step_fetch``, before and outside
    ``train_batch``; a caller that hands the batch in opens none."""
    telemetry.configure()

    def slow():
        while True:
            time.sleep(0.02)
            yield _batch()

    it = slow()
    for _ in range(2):
        plain_engine.train_batch(data_iter=it).block_until_ready()
    plain_engine.train_batch(_batch()).block_until_ready()
    spans = telemetry.get_tracer().spans()
    fetch = [s for s in spans if s.name == "step_fetch"]
    assert len(fetch) == 2 and all(s.depth == 0 for s in fetch)
    assert all(s.dur_us >= 20e3 for s in fetch)
    batches = [s for s in spans if s.name == "train_batch"]
    assert len(batches) == 3
    for f, b in zip(fetch, batches):
        assert f.ts_us + f.dur_us <= b.ts_us


def test_ledger_observes_equal_steps_by_comparison(plain_engine,
                                                   monkeypatch):
    """``observe`` walks the operands at first sight and when the batch's
    structure changes, not every step: ten equal steps are one walk and
    ten calls, a new shape registers a second entry, the old shape finds
    the first again."""
    from deepspeed_tpu.telemetry import ledger
    walks = []
    walk = ledger._signature
    monkeypatch.setattr(ledger, "_signature",
                        lambda a, k: walks.append(1) or walk(a, k))
    telemetry.configure(executable_ledger=True)
    for _ in range(10):
        plain_engine.train_batch(_batch()).block_until_ready()
    led = telemetry.get_ledger()
    (first,) = led.entries()
    assert (first.name, first.calls, len(walks)) == ("compiled_step", 10, 1)
    plain_engine.train_batch(_batch(seq=8)).block_until_ready()
    assert len(led.entries()) == 2 and len(walks) == 2
    second = next(e for e in led.entries() if e is not first)
    assert second.calls == 1 and second.signature != first.signature
    plain_engine.train_batch(_batch()).block_until_ready()
    assert (first.calls, second.calls, len(walks)) == (11, 1, 3)
    assert led.calls_by_name() == {"compiled_step": 12}


def test_ledger_short_path_needs_the_callers_word():
    """Without ``struct`` every observation walks (the serving engine's
    call); with it, another callable or another ``struct`` walks again."""
    from deepspeed_tpu.telemetry import ledger
    led = ledger.ExecutableLedger(hlo_collectives=False)
    f = jax.jit(lambda x: x + 1)
    g = jax.jit(lambda x: x + 2)
    x, y = jnp.ones(4), jnp.ones(8)
    a = led.observe("f", f, (x,))
    assert led.observe("f", f, (x,)) is a and a.calls == 2
    assert "f" not in led._last
    b = led.observe("f", f, (x,), struct="x")
    assert b is a and led._last["f"][2] is a
    # the short path trusts the word: the operand is not looked at
    assert led.observe("f", f, (y,), struct="x") is a and a.calls == 4
    c = led.observe("f", f, (y,), struct="y")
    assert c is not a and c.calls == 1
    assert led.observe("f", g, (y,), struct="y") is c and c.calls == 2
    assert led._last["f"][0] is g
    led.clear()
    assert not led._last and len(led) == 0


def test_device_truth_opt_in_defaults_off():
    """Enabling base telemetry must NOT allocate the ISSUE 5 layer:
    ledger, flight recorder, and watchdog are separate opt-ins."""
    telemetry.configure()
    assert telemetry.get_ledger() is None
    assert telemetry.get_flight_recorder() is None
    assert telemetry.get_watchdog() is None
    # the ISSUE 17 fleet plane is its own opt-in too: plain
    # configure() must not allocate the ring/detector/aggregator
    assert telemetry.get_timeseries() is None
    assert telemetry.get_health_monitor() is None
    assert telemetry.get_fleet() is None


def test_disabled_guard_no_import_no_state():
    """The overhead claim, kept honest in a fresh interpreter:
    telemetry-disabled train_batch AND decode_fused never import the
    telemetry package (sys.modules stays clean), so no exporter state
    can possibly be allocated."""
    script = r"""
import sys
import jax, numpy as np
import deepspeed_tpu as ds
from deepspeed_tpu.models import GPT2, Llama
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)

engine, _, _, _ = ds.initialize(model=GPT2(size="tiny"), config={
    "train_batch_size": 4,
    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
    "steps_per_print": 100})
tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 17), 0, 512)
float(engine.train_batch((tokens[:, :-1], tokens[:, 1:])))

model = Llama(size="tiny", max_seq_len=128)
e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
    dtype="float32", kv_block_size=64, num_kv_blocks=32,
    max_chunk_size=64))
e.put([0], [list(range(1, 9))])
e.state_manager.extend(0, [1])
e.decode_fused([0], k_steps=2)

# the serving path too (ISSUE 10): the FusedServeLoop + per-request
# instrumentation must resolve the recorder through the probe, never
# import it — reqtrace rides the same disabled-mode contract
from deepspeed_tpu.inference.v2.serve_loop import FusedServeLoop
loop = FusedServeLoop(e, k_steps=2)
loop.submit([2, 3, 4], max_new_tokens=4)
while loop.has_work():
    loop.step()

assert "deepspeed_tpu.telemetry" not in sys.modules, \
    "telemetry was imported on the disabled path"
assert "deepspeed_tpu.telemetry.reqtrace" not in sys.modules, \
    "reqtrace was imported on the disabled path"
for mod in ("timeseries", "health", "fleet", "steptrace"):
    assert f"deepspeed_tpu.telemetry.{mod}" not in sys.modules, \
        f"{mod} was imported on the disabled path"
print("GUARD_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "GUARD_OK" in proc.stdout


# ---------------------------------------------------------------------
# telemetry_report CLI smoke (satellite — fast, not-slow tier)
# ---------------------------------------------------------------------

def test_telemetry_report_smoke(tmp_path):
    telemetry.configure()
    with telemetry.span("train_batch", step=1):
        with telemetry.span("compiled_step"):
            time.sleep(0.001)
    telemetry.get_registry().gauge("ds_train_loss").set(2.5)
    paths = telemetry.export_artifacts(str(tmp_path), prefix="rpt")

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    report = telemetry_report.build_report(paths["trace"],
                                           paths["prometheus"])
    names = [r["name"] for r in report["spans"]]
    assert "train_batch" in names and "compiled_step" in names
    assert report["metrics"]["ds_train_loss"] == 2.5
    # prom and json snapshot parse to the same scalar
    report2 = telemetry_report.build_report(paths["trace"],
                                            paths["metrics_json"])
    assert report2["metrics"]["ds_train_loss"] == 2.5
    # CLI --json path end-to-end
    rc = telemetry_report.main([paths["trace"], paths["prometheus"],
                                "--json"])
    assert rc == 0
