"""Device-truth observability (ISSUE 5): executable cost/memory
ledger + the shared cost/memory normalizers, HLO collective accounting
with mesh-axis attribution, flight recorder + hang watchdog +
straggler skew, and the telemetry_report merge/diff satellites."""

import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import collectives, flightrec, ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    telemetry.shutdown()
    yield
    telemetry.shutdown()


def _import_report():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    return telemetry_report


# ---------------------------------------------------------------------
# satellite: shared cost/memory normalizers (utils/jax_compat.py)
# ---------------------------------------------------------------------

def test_cost_memory_normalizers():
    from deepspeed_tpu.utils.jax_compat import (normalize_cost_analysis,
                                                normalize_memory_analysis)
    # cost: None / empty / list-wrapped / plain dict all normalize
    assert normalize_cost_analysis(None) == {}
    assert normalize_cost_analysis([]) == {}
    assert normalize_cost_analysis({}) == {}
    assert normalize_cost_analysis([{"flops": 8, "bytes accessed": 32}]
                                   ) == {"flops": 8.0,
                                         "bytes accessed": 32.0}
    assert normalize_cost_analysis({"flops": 4.0})["flops"] == 4.0
    # non-numeric entries are dropped, not crashed on
    assert normalize_cost_analysis([{"flops": 2, "junk": "x"}]) \
        == {"flops": 2.0}

    # memory: None / struct-like / dict / peak fallback
    assert normalize_memory_analysis(None) == {}

    class FakeStats:
        argument_size_in_bytes = 100
        output_size_in_bytes = 50
        temp_size_in_bytes = 25
        alias_size_in_bytes = 0
        generated_code_size_in_bytes = 7

    m = normalize_memory_analysis(FakeStats())
    assert m["argument"] == 100 and m["output"] == 50
    assert m["peak"] == 175          # no backend peak -> arg+out+temp

    class WithPeak(FakeStats):
        peak_memory_in_bytes = 400

    assert normalize_memory_analysis(WithPeak())["peak"] == 400
    assert normalize_memory_analysis(
        {"argument_size_in_bytes": 10, "output_size_in_bytes": 2,
         "temp_size_in_bytes": 1})["peak"] == 13


def test_real_compiled_normalizes_on_cpu():
    """The CPU backend's list-wrapped cost dict and peak-less memory
    struct flow through the normalizers (the satellite's regression
    target: both the ledger and the flops profiler call sites)."""
    from deepspeed_tpu.profiling.flops_profiler.profiler import (
        compiled_cost, compiled_memory, lower_compiled)
    compiled = lower_compiled(lambda x: x * 2 + 1,
                              np.ones((4, 4), np.float32))
    cost = compiled_cost(compiled)
    assert cost.get("flops", 0) > 0
    mem = compiled_memory(compiled)
    assert mem["peak"] > 0 and mem["argument"] > 0


# ---------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------

_SYNTH_HLO = """
HloModule synth
%ar = f32[4,16]{1,0} all-reduce(f32[4,16]{1,0} %p), channel_id=1, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%sum
%ag = f32[8,16]{1,0} all-gather(f32[4,16]{1,0} %ar), channel_id=2, replica_groups=[2,2]<=[4], dimensions={0}
%rs = f32[2,16]{1,0} reduce-scatter(f32[4,16]{1,0} %ar), channel_id=3, replica_groups={{0,1},{2,3}}, dimensions={0}, to_apply=%sum
%cp = f32[4,16]{1,0} collective-permute(f32[4,16]{1,0} %ar), channel_id=4, source_target_pairs={{0,1},{1,0}}
%ars = f32[4]{0} all-reduce-start(f32[4]{0} %q), channel_id=5, replica_groups={{0,1,2,3}}, to_apply=%sum
%ard = f32[4]{0} all-reduce-done(f32[4]{0} %ars)
%one = f32[4]{0} all-reduce(f32[4]{0} %q), channel_id=6, replica_groups={{0},{1},{2},{3}}, to_apply=%sum
"""


def test_analyze_hlo_synthetic_text():
    recs = collectives.analyze_hlo(_SYNTH_HLO, mesh=None, n_devices=4)
    by_op = {}
    for r in recs:
        by_op.setdefault(r["hlo_op"], []).append(r)
    assert by_op["all-reduce"][0]["bytes"] == 4 * 16 * 4
    assert by_op["all-reduce"][0]["group_size"] == 2
    # iota replica_groups form parses like the braces form
    assert by_op["all-gather"][0]["bytes"] == 8 * 16 * 4
    assert by_op["all-gather"][0]["group_size"] == 2
    # reduce-scatter payload is the full input (result x group size)
    assert by_op["reduce-scatter"][0]["bytes"] == 2 * 16 * 4 * 2
    assert by_op["collective-permute"][0]["bytes"] == 4 * 16 * 4
    # async -start counts once; its -done half is ignored
    assert len(by_op["all-reduce-start"]) == 1
    # size-1 groups move no bytes and are dropped
    assert all(r["group_size"] > 1 for r in recs)

    mat = collectives.traffic_matrix(recs, calls=3)
    key = ("n2", "all_reduce")
    assert mat[key]["bytes"] == 4 * 16 * 4 * 3


_WIRE_HLO = """
HloModule wire
%q = s8[4,512]{1,0} all-gather(s8[1,512]{1,0} %a), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
%s = f32[4,1]{1,0} all-gather(f32[1,1]{1,0} %b), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}
%g = f32[2,512]{1,0} reduce-scatter(f32[8,512]{1,0} %c), channel_id=3, replica_groups={{0,1},{2,3}}, dimensions={0}, to_apply=%sum
"""


def test_analyze_hlo_wire_dtype_accounting():
    """Quantized-wire payloads count at their ACTUAL dtype width
    (ISSUE 8): an s8 all-gather is 1 byte/element, its fp32 scales 4,
    and the per-axis wire width folds both — so the qwZ/qgZ win lands
    in ds_hlo_collective_bytes_total without any assumed element
    size, and calibration algbw floors stay unit-consistent."""
    recs = collectives.analyze_hlo(_WIRE_HLO, mesh=None, n_devices=4)
    codes, scales, grads = recs
    assert codes["bytes"] == 4 * 512 * 1
    assert codes["elements"] == 4 * 512
    assert codes["wire_bytes_per_el"] == 1.0
    assert scales["bytes"] == 4 * 1 * 4
    assert scales["wire_bytes_per_el"] == 4.0
    assert grads["bytes"] == 2 * 512 * 4 * 2   # full input, fp32
    mat = collectives.traffic_matrix(recs)
    width = collectives.axis_wire_width(mat)
    # codes + scales fold on the n4 axis: (2048*1 + 4*4)/(2048 + 4)
    assert width["n4"] == pytest.approx((2048 + 16) / 2052)
    assert width["n2"] == 4.0
    # ledger rollup exposes the same number for calibrations
    led = ledger.ExecutableLedger(hlo_collectives=False)
    e = ledger.ExecutableEntry("compiled_step", ())
    e.collectives, e.calls, e.flops = recs, 2, 1e9
    led._entries[("compiled_step", ())] = e
    assert led.axis_wire_bytes_per_el()["n4"] == \
        pytest.approx((2048 + 16) / 2052)
    from deepspeed_tpu.autotuning.cost_model import Calibration
    cal = Calibration.from_telemetry(
        led, {"compiled_step": (0.5, 2)}, window_s=0.5)
    assert cal.axis_wire_bytes_per_el["n4"] == \
        pytest.approx((2048 + 16) / 2052)
    # algbw floor divides the OBSERVED (1-byte) payload by the window
    assert cal.axis_algbw_bytes_per_s["n4"] == pytest.approx(
        2 * (2048 + 16) / 0.5)


def test_ledger_attributes_allreduce_to_mesh_axis(devices8):
    """Acceptance: nonzero all-reduce bytes, attributed to the right
    mesh axis, for a dp>1 collective on the virtual multichip mesh."""
    from deepspeed_tpu.utils.jax_compat import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    telemetry.configure(executable_ledger=True)
    mesh = Mesh(np.array(devices8).reshape(2, 4), ("dp", "tp"))
    f = jax.jit(shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                          in_specs=P("dp", None),
                          out_specs=P("dp", None)))
    x = jax.device_put(np.ones((8, 16), np.float32),
                       NamedSharding(mesh, P("dp")))
    led = telemetry.get_ledger()
    e1 = led.observe("psum_step", f, (x,), mesh=mesh)
    f(x).block_until_ready()
    e2 = led.observe("psum_step", f, (x,), mesh=mesh)
    assert e1 is e2 and e2.calls == 2      # deduped by signature
    ar = [c for c in e1.collectives if c["op"] == "all_reduce"]
    assert ar and ar[0]["bytes"] > 0
    assert ar[0]["axis"] == "dp" and ar[0]["group_size"] == 2
    # traffic is dispatch-weighted: 2 observed calls double the bytes
    traffic = led.traffic()
    assert traffic[("dp", "all_reduce")]["bytes"] == 2 * ar[0]["bytes"]

    # log_summary folds the device-truth section in (satellite)
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    with telemetry.span("psum_step"):
        time.sleep(0.002)
    text = CommsLogger().log_summary(world_size=8, print_log=False)
    assert "HLO collective accounting" in text
    assert "dp" in text and "all_reduce" in text


# ---------------------------------------------------------------------
# engine acceptance: warmed train_batch -> ledger entry + finite MFU
# ---------------------------------------------------------------------

def test_train_batch_ledger_mfu_and_hbm(tmp_path, devices8):
    """Acceptance (CPU smoke rig): the ledger registers the compiled
    train step with nonzero FLOPs, the MFU gauge is finite, peak HBM
    is reported, the flight recorder heartbeats, and the exported
    artifacts carry the ledger table."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2
    engine, _, _, _ = ds.initialize(model=GPT2(size="tiny"), config={
        "train_batch_size": 16,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 1,
        "telemetry": {"enabled": True, "executable_ledger": True,
                      "flight_recorder": True}})
    assert telemetry.is_active()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (16, 17), 0, 512)
    batch = (tokens[:, :-1], tokens[:, 1:])
    for _ in range(2):
        engine.train_batch(batch)

    led = telemetry.get_ledger()
    assert led is not None and len(led) >= 1
    entries = {e.name: e for e in led.entries()}
    step = entries["compiled_step"]
    assert step.flops > 0 and step.calls == 2
    assert step.peak_hbm_bytes > 0
    # world > 1 on the virtual mesh: the compiled step carries real
    # collectives (grad reduction) the comm facade never timed
    assert sum(row["bytes"] for row in led.traffic().values()) > 0

    reg = telemetry.get_registry()
    dispatched = reg.counter("ds_ledger_dispatched_flops_total").value(
        name="compiled_step")
    assert math.isfinite(dispatched) and dispatched > 0
    assert reg.gauge("ds_ledger_peak_hbm_bytes").value(
        name="compiled_step") == step.peak_hbm_bytes
    assert reg.counter("ds_ledger_dispatched_flops_total").value(
        name="compiled_step") == pytest.approx(2 * step.flops)

    fr = telemetry.get_flight_recorder()
    beats = [e for e in fr.events() if e["kind"] == "progress"
             and e["name"] == "train_batch"]
    assert len(beats) == 2

    paths = telemetry.export_artifacts(str(tmp_path), prefix="dt")
    assert os.path.exists(paths["ledger"])
    doc = json.load(open(paths["ledger"]))
    assert doc["n_executables"] >= 1
    assert any(r["name"] == "compiled_step" and r["flops"] > 0
               for r in doc["executables"])
    prom = open(paths["prometheus"]).read()
    assert "ds_ledger_dispatched_flops_total" in prom \
        and "ds_ledger_peak_hbm_bytes" in prom

    # report CLI renders the ledger table
    rpt = _import_report()
    report = rpt.build_report(paths["trace"], paths["metrics_json"],
                              ledger_path=paths["ledger"])
    assert report["ledger"]["n_executables"] >= 1


def test_fused_decode_ledger_entries():
    """v2 dispatch + fused dispatch register distinct ledger entries
    with nonzero FLOPs (observe runs BEFORE dispatch: pool donation
    must not break signature capture)."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    telemetry.configure(executable_ledger=True, flight_recorder=True)
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
    led = telemetry.get_ledger()
    names = {en.name for en in led.entries()}
    assert {"v2/dispatch", "v2/fused_dispatch"} <= names
    assert all(en.flops > 0 for en in led.entries())
    fr = telemetry.get_flight_recorder()
    kinds = {e["name"] for e in fr.events()}
    assert "v2_dispatch" in kinds and "v2_drain" in kinds


def test_quantized_kv_pool_ledger_footprint():
    """Quantized KV cache (ISSUE 12 satellite): the ledger's
    ``memory_analysis()`` truth must SEE the quantized pool's HBM win —
    at equal block count (grow_pool=False), the fused dispatch's
    argument bytes shrink by ~the pool-byte difference the engine's
    own kv_pool_bytes() accounting predicts (the fp32 pool is 3.2x the
    int8+scales pool here)."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Llama
    telemetry.configure(executable_ledger=True)
    model = Llama(size="tiny", max_seq_len=256)
    rng = np.random.default_rng(1)
    args: dict[str, int] = {}
    pools: dict[str, int] = {}
    for name, kv in (("fp", {"enabled": False}),
                     ("q", {"enabled": True, "dtype": "int8",
                            "grow_pool": False})):
        e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
            dtype="float32", kv_block_size=64, num_kv_blocks=64,
            max_chunk_size=64, kv_cache=kv))
        uids = [0, 1]
        e.put(uids, [rng.integers(0, model.config.vocab_size,
                                  8).tolist() for _ in uids])
        for u in uids:
            e.state_manager.extend(u, [1])
        e.decode_fused(uids, k_steps=2)
        led = telemetry.get_ledger()
        ent = [en for en in led.entries()
               if en.name == "v2/fused_dispatch"]
        assert ent, "fused dispatch never registered"
        args[name] = max(en.memory.get("argument", 0) for en in ent)
        pools[name] = e.kv_pool_bytes()
        e.flush(uids)
        telemetry.shutdown()
        telemetry.configure(executable_ledger=True)
    expected_drop = pools["fp"] - pools["q"]
    assert expected_drop > 0.6 * pools["fp"]      # >= ~3x smaller pool
    measured_drop = args["fp"] - args["q"]
    assert measured_drop == pytest.approx(expected_drop, rel=0.1), \
        (args, pools)


# ---------------------------------------------------------------------
# flight recorder + hang watchdog + straggler skew
# ---------------------------------------------------------------------

def test_flight_recorder_ring_and_progress():
    fr = flightrec.FlightRecorder(capacity=8)
    assert fr.stalled_for() is None          # never armed before use
    for i in range(20):
        fr.record("dispatch", "step", i=i)
    events = fr.events()
    assert len(events) == 8                  # ring bounded
    assert [e["slot"] for e in events] == list(range(12, 20))
    assert fr.recorded == 20
    fr.progress("train_batch", step=5)
    assert fr.events()[-1]["kind"] == "progress"
    assert 0 <= fr.stalled_for() < 1.0
    snap = fr.snapshot()
    assert snap["capacity"] == 8 and "train_batch" in \
        snap["progress_age_s"]
    fr.clear()
    assert fr.events() == [] and fr.stalled_for() is None


def test_watchdog_dumps_on_stall(tmp_path):
    """A stalled step must leave a COMPLETE dump artifact behind:
    flight-recorder events, the open span the host was stuck inside,
    and the ledger snapshot."""
    telemetry.configure(executable_ledger=True, flight_recorder=True,
                        watchdog_deadline_s=0.15,
                        watchdog_artifact_dir=str(tmp_path))
    fr = telemetry.get_flight_recorder()
    fr.progress("train_batch", step=3)
    with telemetry.span("train_batch", step=4):
        time.sleep(0.8)                       # stalled: no progress
    dog = telemetry.get_watchdog()
    assert dog is not None and dog.dumps, "watchdog never fired"
    doc = json.load(open(dog.dumps[0]))
    assert doc["reason"].startswith("no progress")
    ev = doc["flight_recorder"]["events"]
    assert any(e["kind"] == "progress" and e["name"] == "train_batch"
               for e in ev)
    assert any(s["name"] == "train_batch" for s in doc["open_spans"])
    assert "ledger" in doc and "thread_stacks" in doc
    assert any("sleep" in "".join(stack)
               for stack in doc["thread_stacks"].values())
    # one dump per stall, not one per poll tick
    assert len(dog.dumps) == 1


def test_watchdog_quiet_on_clean_run(tmp_path):
    telemetry.configure(flight_recorder=True,
                        watchdog_deadline_s=0.3,
                        watchdog_artifact_dir=str(tmp_path))
    fr = telemetry.get_flight_recorder()
    for i in range(10):
        fr.progress("train_batch", step=i)
        time.sleep(0.05)
    dog = telemetry.get_watchdog()
    assert dog is not None and not dog.dumps
    assert list(tmp_path.iterdir()) == []


def test_straggler_skew_gauge_with_fake_timestamps():
    from deepspeed_tpu.comm.comm import ReduceOp
    from deepspeed_tpu.telemetry.registry import MetricsRegistry
    assert flightrec.skew_from_timestamps([10.0]) == 0.0
    assert flightrec.skew_from_timestamps(
        [100.0, 100.25, 100.1]) == pytest.approx(0.25)

    # 4 fake ranks at known offsets: the gauge must read max - min
    fake_ranks = [1000.0, 1000.02, 1000.5, 1000.31]

    def fake_reduce(value, op):
        return {ReduceOp.MIN: min, ReduceOp.MAX: max}[op](fake_ranks)

    reg = MetricsRegistry()
    skew = flightrec.record_straggler_skew(reg, step=7, now=1000.0,
                                           reduce_fn=fake_reduce)
    assert skew == pytest.approx(0.5)
    assert reg.gauge("ds_straggler_skew_seconds").value() == \
        pytest.approx(0.5)
    assert reg.gauge("ds_straggler_last_step").value() == 7
    # single-process real path: no collective, zero skew
    assert flightrec.record_straggler_skew(reg, step=8) == 0.0


# ---------------------------------------------------------------------
# telemetry_report satellites: --merge and --diff
# ---------------------------------------------------------------------

def _write_trace(path, names, pid=0):
    events = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
               "args": {"name": f"deepspeed_tpu rank {pid} (host)"}}]
    for i, name in enumerate(names):
        events.append({"name": name, "ph": "X", "ts": i * 100.0,
                       "dur": 50.0, "pid": pid, "tid": 1})
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path


def test_report_merge_rank_labelled_tracks(tmp_path):
    rpt = _import_report()
    a = _write_trace(tmp_path / "r0.trace.json", ["train_batch"] * 3)
    b = _write_trace(tmp_path / "r1.trace.json", ["train_batch"] * 2)
    out = str(tmp_path / "merged.trace.json")
    assert rpt.main(["--merge", out, str(a), str(b)]) == 0
    doc = json.load(open(out))
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 5
    # the two ranks land on distinct pids with rank-labelled tracks
    assert len({e["pid"] for e in xs}) == 2
    labels = [e["args"]["name"] for e in doc["traceEvents"]
              if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert any(lab.startswith("rank 0") for lab in labels)
    assert any(lab.startswith("rank 1") for lab in labels)


def test_report_diff_regression_gate(tmp_path):
    rpt = _import_report()
    a = tmp_path / "a.json"
    b_bad = tmp_path / "b_bad.json"
    b_ok = tmp_path / "b_ok.json"
    a.write_text(json.dumps({
        "metric": "bench", "tokens_per_sec": 100.0,
        "ttft_seconds_mean": 0.5, "neutral_thing": 3.0}))
    b_bad.write_text(json.dumps({
        "metric": "bench", "tokens_per_sec": 80.0,       # -20% (bad)
        "ttft_seconds_mean": 0.5, "neutral_thing": 9.0}))
    b_ok.write_text(json.dumps({
        "metric": "bench", "tokens_per_sec": 104.0,      # +4% (good)
        "ttft_seconds_mean": 0.45, "neutral_thing": 9.0}))
    assert rpt.main(["--diff", str(a), str(b_ok),
                     "--threshold", "0.05"]) == 0
    assert rpt.main(["--diff", str(a), str(b_bad),
                     "--threshold", "0.05"]) == 1
    # latency direction: +20% ttft regresses even as throughput holds
    b_lat = tmp_path / "b_lat.json"
    b_lat.write_text(json.dumps({
        "metric": "bench", "tokens_per_sec": 100.0,
        "ttft_seconds_mean": 0.62, "neutral_thing": 3.0}))
    diff = rpt.diff_snapshots(str(a), str(b_lat), threshold=0.05)
    assert [r["metric"] for r in diff["regressions"]] \
        == ["ttft_seconds_mean"]
    # neutral metrics report but never gate
    assert all(r["direction"] == 0 for r in diff["rows"]
               if "neutral" in r["metric"])
    # within threshold: no gate
    assert rpt.main(["--diff", str(a), str(a)]) == 0
