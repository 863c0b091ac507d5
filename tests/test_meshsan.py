"""meshsan (ISSUE 15, runtime half): traffic-contract checks over
synthetic HLO-walk records (undeclared-axis traffic, the GSPMD
silent-reshard all-to-all signature, wire-dtype downgrades), contract
seeding from engine configs, ledger-entry dedupe, hang-dump stall
attribution, violation-counter surfacing through telemetry_report, and
the config wiring. Everything here is host-only/synthetic; the
engine-backed variant lives in conftest._SLOW."""

import importlib.util
import json
import os

import pytest

from deepspeed_tpu.analysis.meshsan import (MeshSanError, MeshSanitizer,
                                            TrafficContract, get_meshsan,
                                            seed_serving_contract,
                                            seed_training_contract,
                                            set_meshsan)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rec(axis, op="all_reduce", nbytes=1 << 20, wpe=4.0, group=4):
    """One synthetic collectives.analyze_hlo record."""
    return {"op": op, "hlo_op": op.replace("_", "-"), "bytes": nbytes,
            "elements": int(nbytes / wpe) if wpe else 0,
            "wire_bytes_per_el": wpe, "group_size": group, "axis": axis}


class _FakeEntry:
    """Duck-typed ExecutableEntry: name/signature/collectives."""

    def __init__(self, name, records, signature=("sig",)):
        self.name = name
        self.signature = signature
        self.collectives = records


# ---------------------------------------------------------------------
# contract checks (seeded faults)
# ---------------------------------------------------------------------

def test_undeclared_axis_traffic_is_a_named_finding():
    """ISSUE 15 acceptance: a synthetic ledger entry with traffic on
    an undeclared axis produces a finding naming executable, axis, op
    and bytes."""
    san = MeshSanitizer(mode="raise")
    san.declare("compiled_step",
                TrafficContract(axes={"dp", "fsdp"}))
    with pytest.raises(MeshSanError) as e:
        san.check_records("compiled_step",
                          [_rec("ep", op="all_to_all", nbytes=123456)])
    msg = str(e.value)
    assert "compiled_step" in msg and "'ep'" in msg
    assert "all_to_all" in msg and "123456" in msg
    assert "UNDECLARED" in msg
    assert san.counters["violations"] == 1


def test_warn_mode_counts_and_returns_without_raising():
    san = MeshSanitizer(mode="warn")
    san.declare("compiled_step", TrafficContract(axes={"dp"}))
    msgs = san.check_records(
        "compiled_step",
        [_rec("tp"), _rec("dp"), _rec("sp", op="all_gather")])
    assert len(msgs) == 2       # tp and sp; dp is declared
    assert san.counters["violations"] == 2
    assert len(san.violation_log) == 2


def test_wire_downgrade_fp32_on_int8_axis():
    """ISSUE 15 acceptance: fp32 bytes on an axis configured for an
    int8 wire is a finding naming executable, axis, op and bytes —
    and tiny control collectives below min_bytes never trip it."""
    san = MeshSanitizer(mode="warn")
    san.declare("compiled_step", TrafficContract(
        axes={"fsdp", "zps"},
        all_to_all_axes={"fsdp", "zps"},
        wire_bytes_per_el={"fsdp": 2.0},
        min_bytes=65536))
    # quantized wire (int8 payload + fp32 scales ~1.06 B/el): clean
    assert san.check_records(
        "compiled_step",
        [_rec("fsdp", op="all_to_all", nbytes=1 << 20, wpe=1.06)]) == []
    # fp32 wire on the same axis: downgrade finding with all four facts
    msgs = san.check_records(
        "compiled_step",
        [_rec("fsdp", op="all_to_all", nbytes=1 << 20, wpe=4.0)])
    assert len(msgs) == 1
    assert "compiled_step" in msgs[0] and "'fsdp'" in msgs[0]
    assert "all_to_all" in msgs[0] and str(1 << 20) in msgs[0]
    assert "wire downgrade" in msgs[0]
    # a 4 KiB fp32 loss-mean on the same axis is not wire traffic
    assert san.check_records(
        "compiled_step", [_rec("fsdp", nbytes=4096, wpe=4.0)]) == []


def test_unexpected_all_to_all_is_the_reshard_signature():
    """A serving executable with tp-only traffic declared: an
    all-to-all showing up means GSPMD inserted a reshard exchange."""
    san = MeshSanitizer(mode="warn")
    san.declare("v2/fused_dispatch", seed_serving_contract(tp=2))
    assert san.check_records("v2/fused_dispatch",
                             [_rec("tp", op="all_reduce")]) == []
    msgs = san.check_records(
        "v2/fused_dispatch", [_rec("tp", op="all_to_all")])
    assert len(msgs) == 1 and "silent-reshard" in msgs[0]
    msgs = san.check_records(
        "v2/fused_dispatch", [_rec("tp", op="ppermute")])
    assert len(msgs) == 1
    # a kilobyte-scale reshard shuffle is normal GSPMD behavior (the
    # partitioner inserts them even in clean programs) — only
    # substantial exchanges are the signature
    assert san.check_records(
        "v2/fused_dispatch",
        [_rec("tp", op="all_to_all", nbytes=3072)]) == []


# recorded from the v5e:2x2 AOT compile of the four-chip cell's ZeRO-3
# step (Mistral-7B widths, fsdp=4, sequence 8192; PR 28), trimmed: the
# parent's tensor-parallel re-partitioning of the MLP backward, and the two
# forms in which the TPU compiler runs the plan's own gathers and scatters
# as collective-permutes
_A2A = (
    '  %all-to-all.11 = bf16[4,1,8192,3584]{2,3,1,0:T(8,128)(2,1)} '
    'all-to-all(%fusion.789), channel_id=79, replica_groups=[1,4]<=[4], '
    'dimensions={0}, metadata={op_name="jit(train_step)/transpose(jvp('
    'ds.layers))/while/body/closed_call/checkpoint/ds.mlp/jit(silu)/mul" '
    'stack_frame_id=120}')
_RING = (
    '  %collective-permute-start.24 = (bf16[4096,1,3584]{2,0,1:T(8,128)'
    '(2,1)}, bf16[4096,1,3584]{2,0,1:T(8,128)(2,1)}, u32[]{:S(2)}, '
    'u32[]{:S(2)}) collective-permute-start(%dynamic-slice_bitcast_fusion'
    '.18), channel_id=71, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}, '
    'metadata={op_name="jit(train_step)/transpose(jvp(ds.layers))/while/'
    'body/closed_call/checkpoint/ds.mlp/LEAF" stack_frame_id=169}')
_HALO = '''
  %fusion.5 = bf16[8064,4096]{1,0:T(8,128)(2,1)} fusion(%fusion.2), kind=kCustom, calls=%all-reduce-scatter, metadata={op_name="jit(train_step)/transpose(jvp(ds.embed))/jit(_take)/scatter-add" stack_frame_id=8}
  %slice.215 = bf16[192,4096]{1,0:T(8,128)(2,1)} slice(%fusion.5), slice={[7872:8064], [0:4096]}
  %collective-permute-start.76 = (bf16[192,4096]{1,0:T(8,128)(2,1)}, bf16[192,4096]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%slice.215), channel_id=196, source_target_pairs={{0,1},{1,2},{2,3}}
  %collective-permute-done.76 = bf16[192,4096]{1,0:T(8,128)(2,1)} collective-permute-done(%collective-permute-start.76)
'''


@pytest.mark.parametrize("hlo,implements,finding", [
    (_A2A, None, ("all_to_all", f"{224 << 20} B", "ds.mlp/jit(silu)/mul")),
    (_RING.replace("LEAF", "dot_general"), "collective_matmul", None),
    (_RING.replace("LEAF", "mul"), None,
     ("ppermute", f"{4096 * 3584 * 2} B", "ds.mlp/mul")),
    (_HALO, "reduce_scatter", None),
], ids=["mlp_backward_all_to_all", "collective_matmul_ring",
        "bare_permute", "reduce_scatter_halo"])
def test_zero3_contract_on_recorded_tpu_hlo(hlo, implements, finding,
                                            devices8):
    """The detector that would have caught PR 28's fault catches it: the
    parent's HLO line through the ledger's walk and the seeded ZeRO-3
    training contract is an ``unexpected-op`` finding naming the axis,
    the op, its 224 MiB and its owner. The compiler's own ring and halo
    permutes are held to the gather or scatter they implement; a permute
    that is neither is still a finding."""
    from deepspeed_tpu.parallel.mesh import MeshTopology, TopologyConfig
    from deepspeed_tpu.telemetry.collectives import analyze_hlo
    topo = MeshTopology(TopologyConfig(fsdp=4), devices=devices8[:4])
    (rec,) = analyze_hlo(hlo, topo.mesh)
    assert rec["axis"] == "fsdp" and rec.get("implements") == implements
    san = MeshSanitizer(mode="warn")
    san.declare("compiled_step", seed_training_contract(topo.sizes))
    msgs = san.check_records("compiled_step", [rec])
    if finding is None:
        assert msgs == []
        return
    (msg,) = msgs
    assert "unexpected" in msg and "'fsdp'" in msg
    assert all(part in msg for part in finding)


def test_combined_axis_labels_check_by_component():
    """collectives.analyze_hlo labels multi-axis groups "fsdp+zps";
    declared iff every component is."""
    san = MeshSanitizer(mode="warn")
    san.declare("compiled_step",
                TrafficContract(axes={"fsdp", "zps"}))
    assert san.check_records("compiled_step",
                             [_rec("fsdp+zps")]) == []
    msgs = san.check_records("compiled_step", [_rec("fsdp+tp")])
    assert len(msgs) == 1 and "fsdp+tp" in msgs[0]


def test_world_and_unattributed_labels():
    """"world" (full-mesh loss reductions) is allowed by default and
    gated by allow_world; "n<k>" labels carry no axis name to hold a
    contract against and are skipped."""
    san = MeshSanitizer(mode="warn")
    san.declare("a", TrafficContract(axes={"dp"}))
    san.declare("b", TrafficContract(axes={"dp"}, allow_world=False))
    assert san.check_records("a", [_rec("world"), _rec("n8")]) == []
    assert len(san.check_records("b", [_rec("world")])) == 1


def test_undeclared_executable_records_but_never_fails():
    """No contract declared for a name: records are kept for stall
    attribution, nothing is checked."""
    san = MeshSanitizer(mode="raise")
    assert san.check_records("warmup_probe", [_rec("ep")]) == []
    assert san.records_by_name["warmup_probe"]


def test_observe_entry_checks_once_per_executable():
    san = MeshSanitizer(mode="warn")
    san.declare("compiled_step", TrafficContract(axes={"dp"}))
    entry = _FakeEntry("compiled_step", [_rec("tp")])
    assert len(san.observe_entry(entry)) == 1
    # same (name, signature): the per-dispatch path is a set lookup
    assert san.observe_entry(entry) == []
    assert san.counters["violations"] == 1
    # a NEW signature of the same name is a new executable
    other = _FakeEntry("compiled_step", [_rec("tp")],
                       signature=("sig2",))
    assert len(san.observe_entry(other)) == 1
    assert san.observe_entry(None) == []


# ---------------------------------------------------------------------
# contract seeding (the engine/serve-loop call sites)
# ---------------------------------------------------------------------

def test_seed_training_contract_follows_mesh_and_wire_flags():
    sizes = {"pp": 1, "dp": 1, "fsdp": 4, "zps": 2, "ep": 1,
             "sp": 1, "tp": 1}
    plain = seed_training_contract(sizes)
    assert plain.axes == {"fsdp", "zps"}
    assert plain.all_to_all_axes == frozenset()      # no qgZ, no sp/ep
    assert plain.wire_bytes_per_el == {}
    qgz = seed_training_contract(sizes, quantized_gradients=True)
    assert qgz.all_to_all_axes == {"fsdp", "zps"}    # the qgZ exchange
    assert qgz.wire_limit("fsdp", "all_to_all") == 2.0
    assert qgz.wire_limit("zps", "reduce_scatter") == 2.0
    # sp/ep/pp axes pull in their expected op classes
    moe = seed_training_contract({"dp": 2, "ep": 4, "sp": 2, "pp": 2})
    assert moe.all_to_all_axes == {"sp", "ep"}
    assert moe.permute_axes == {"pp", "sp"}


def test_wire_ceiling_is_per_quantized_direction():
    """Each ZeRO++ flag quantizes ONE traffic direction: qgZ-only must
    tolerate the legitimately-fp32 weight all_gather (and vice versa)
    while still catching a disengaged quantized path in its own
    direction — including the plain fp32 reduce_scatter/all_reduce
    shape a disengaged qgZ degrades into."""
    sizes = {"fsdp": 4, "zps": 2}
    qgz = seed_training_contract(sizes, quantized_gradients=True)
    san = MeshSanitizer(mode="warn")
    san.declare("compiled_step", qgz)
    # fp32 weight all-gather is the CORRECT wire for qgZ-only
    assert san.check_records(
        "compiled_step",
        [_rec("fsdp", op="all_gather", nbytes=1 << 22, wpe=4.0)]) == []
    # a disengaged qgZ shows up as fp32 gradient exchange: caught
    for op in ("all_to_all", "reduce_scatter", "all_reduce"):
        assert san.check_records(
            "compiled_step",
            [_rec("fsdp", op=op, nbytes=1 << 22, wpe=4.0)]), op
    # symmetric: qwZ-only limits the gather, not the gradient wire
    qwz = seed_training_contract(sizes, quantized_weights=True)
    san2 = MeshSanitizer(mode="warn")
    san2.declare("compiled_step", qwz)
    assert san2.check_records(
        "compiled_step",
        [_rec("fsdp", op="reduce_scatter", nbytes=1 << 22,
              wpe=4.0)]) == []
    assert san2.check_records(
        "compiled_step",
        [_rec("fsdp", op="all_gather", nbytes=1 << 22, wpe=4.0)])


def test_seed_serving_contract():
    assert seed_serving_contract(tp=2).axes == {"tp"}
    assert seed_serving_contract(tp=1).axes == frozenset()
    assert seed_serving_contract(tp=2).all_to_all_axes == frozenset()


# ---------------------------------------------------------------------
# stall attribution + hang-dump ride-along
# ---------------------------------------------------------------------

def test_stall_attribution_names_the_collective():
    """The attributor joins the recorder's last dispatch heartbeat
    against the stalled executable's collective content, largest
    payload first."""
    san = MeshSanitizer(mode="warn")
    san.check_records("compiled_step",
                      [_rec("fsdp", op="reduce_scatter", nbytes=1 << 24),
                       _rec("dp", op="all_reduce", nbytes=1 << 10)])
    events = [
        {"slot": 0, "kind": "progress", "name": "train_batch",
         "meta": {"step": 3}},
        {"slot": 1, "kind": "progress", "name": "irrelevant"},
    ]
    attr = san.stall_attribution(events)
    assert attr is not None
    assert attr["executable"] == "compiled_step"
    assert attr["collectives"][0]["axis"] == "fsdp"
    assert attr["collectives"][0]["op"] == "reduce_scatter"
    assert attr["collectives"][0]["bytes"] == 1 << 24
    # v2 heartbeats carry the span name in meta
    san.check_records("v2/fused_dispatch", [_rec("tp")])
    attr = san.stall_attribution(
        [{"slot": 0, "kind": "progress", "name": "v2_dispatch",
          "meta": {"span": "v2/fused_dispatch"}}])
    assert attr["executable"] == "v2/fused_dispatch"
    # nothing attributable recorded
    assert san.stall_attribution([]) is None
    assert san.stall_attribution(
        [{"slot": 0, "kind": "progress", "name": "unknown"}]) is None


def test_hang_dump_embeds_meshsan_and_stall(tmp_path):
    """ISSUE 15: a wedged run's watchdog dump names the collective and
    axis it died in, not just the thread stacks."""
    from deepspeed_tpu.telemetry.flightrec import (FlightRecorder,
                                                   dump_state)
    san = MeshSanitizer(mode="warn")
    san.declare("compiled_step",
                TrafficContract(axes={"dp", "fsdp"}))
    san.check_records("compiled_step",
                      [_rec("fsdp", op="reduce_scatter", nbytes=1 << 22)])
    rec = FlightRecorder(capacity=32)
    rec.progress("train_batch", step=7)
    set_meshsan(san)
    try:
        path = dump_state("unit-test stall", str(tmp_path),
                          recorder=rec)
        assert path
        with open(path) as f:
            doc = json.load(f)
        assert doc["meshsan"]["contracts"]["compiled_step"]["axes"] == \
            ["dp", "fsdp"]
        stall = doc["collective_stall"]
        assert stall["executable"] == "compiled_step"
        assert stall["collectives"][0]["axis"] == "fsdp"
        assert stall["collectives"][0]["op"] == "reduce_scatter"
    finally:
        set_meshsan(None)
    assert get_meshsan() is None


def test_snapshot_shape():
    san = MeshSanitizer(mode="warn")
    san.declare("compiled_step", TrafficContract(axes={"dp"}))
    san.check_records("compiled_step", [_rec("tp")])
    snap = san.snapshot()
    assert snap["mode"] == "warn"
    assert snap["counters"]["violations"] == 1
    assert snap["violations"] and "tp" in snap["violations"][0]
    assert snap["executables"] == {"compiled_step": 1}


# ---------------------------------------------------------------------
# telemetry counter + report surfacing
# ---------------------------------------------------------------------

def test_violation_counter_reaches_telemetry_report():
    """Warn-mode violations bump ds_meshsan_violations_total{kind} in
    the live registry, and telemetry_report's serving summary surfaces
    the series (the graftsan pattern)."""
    from deepspeed_tpu import telemetry
    telemetry.shutdown()
    telemetry.configure()
    try:
        san = MeshSanitizer(mode="warn")
        san.declare("compiled_step", TrafficContract(axes={"dp"}))
        san.check_records("compiled_step", [_rec("ep")])
        reg = telemetry.get_registry()
        assert reg.counter("ds_meshsan_violations_total").value(
            kind="undeclared-axis") == 1
        spec = importlib.util.spec_from_file_location(
            "telemetry_report",
            os.path.join(REPO, "tools", "telemetry_report.py"))
        tr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tr)
        summary = tr.serving_summary(
            {"ds_meshsan_violations_total/kind=undeclared-axis": 1.0,
             "ds_unrelated": 5.0})
        assert summary == {
            "ds_meshsan_violations_total/kind=undeclared-axis": 1.0}
    finally:
        telemetry.shutdown()


# ---------------------------------------------------------------------
# config wiring
# ---------------------------------------------------------------------

def test_env_knob_parsing(monkeypatch):
    from deepspeed_tpu.analysis.meshsan import env_enabled
    monkeypatch.delenv("DS_MESHSAN", raising=False)
    assert env_enabled() is False
    monkeypatch.setenv("DS_MESHSAN", "0")
    assert env_enabled() is False
    monkeypatch.setenv("DS_MESHSAN", "1")
    assert env_enabled() is True


def test_config_blocks_default_off_and_validate():
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceMeshsanConfig, RaggedInferenceEngineConfig)
    from deepspeed_tpu.runtime.config import DeepSpeedConfig, MeshsanConfig
    assert DeepSpeedConfig().meshsan.enabled is False
    assert RaggedInferenceEngineConfig().meshsan.enabled is False
    cfg = MeshsanConfig(enabled=True, mode="warn",
                        axes=["dp", "fsdp"], wire_min_bytes=0)
    assert cfg.axes == ["dp", "fsdp"]
    with pytest.raises(Exception):
        MeshsanConfig(mode="explode")
    with pytest.raises(Exception):
        InferenceMeshsanConfig(mode="explode")
    with pytest.raises(ValueError):
        MeshSanitizer(mode="explode")


def test_engine_seeded_meshsan_contract_matches_training_traffic(
        tmp_path, devices8):
    """Engine-backed acceptance (ISSUE 15): a real sharded-DP train
    step under meshsan raise-mode passes its own seeded contract (the
    ledger's HLO walk attributes every collective to declared axes),
    and a deliberately over-narrow contract catches the same step's
    real traffic as an undeclared-axis finding."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models import GPT2
    telemetry.shutdown()
    try:
        engine, _, _, _ = ds.initialize(
            model=GPT2(size="tiny"), config={
                "train_batch_size": 16,
                "optimizer": {"type": "AdamW",
                              "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2},
                "mesh": {"fsdp": 8},
                "telemetry": {"enabled": True,
                              "executable_ledger": True},
                "meshsan": {"enabled": True, "mode": "raise"}})
        assert engine._meshsan is not None
        tokens = jax.random.randint(jax.random.PRNGKey(0), (16, 17),
                                    0, 512)
        batch = (tokens[:, :-1], tokens[:, 1:])
        engine.train_batch(batch)
        engine.train_batch(batch)
        san = engine._meshsan
        assert san.counters["checked_executables"] >= 1
        assert san.counters["violations"] == 0
        # the same step against a contract that forgot fsdp: the REAL
        # traffic becomes the seeded fault
        narrow = MeshSanitizer(mode="warn")
        narrow.declare("compiled_step", TrafficContract(axes={"tp"}))
        led = telemetry.get_ledger()
        entries = [e for e in led.entries()
                   if e.name == "compiled_step" and e.collectives]
        assert entries, "ledger recorded no compiled_step collectives"
        msgs = narrow.check_records("compiled_step",
                                    entries[0].collectives)
        assert msgs and any("UNDECLARED" in m for m in msgs)
    finally:
        set_meshsan(None)
        telemetry.shutdown()
