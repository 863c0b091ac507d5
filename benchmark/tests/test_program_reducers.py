"""Tests of the readers PR 24 added (``reducers/program.py``): device
scopes, the clock bracket, set-up phases.
By hand, with the others:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from architectures import mistral                   # noqa: E402
from kinds import train_job                         # noqa: E402
from lib import files, peaks, reducers, telemetry   # noqa: E402
from lib import trace as T                          # noqa: E402
from reducers import program as rp                  # noqa: E402

STEP = "^jit_train_step"
SPANS = files.load_traffic("pretrain-s8k")["span_pattern"]
MISTRAL = dict(hidden_size=4096, head_dim=128, num_attention_heads=32,
               num_key_value_heads=8, intermediate_size=14336,
               vocab_size=32000, num_hidden_layers=2, sliding_window=4096)


def _ctx(fixture, scopes=None):
    return {"trace": T.Trace.from_file(os.path.join(HERE, "data", fixture)),
            "arch": mistral, "model": MISTRAL, "seq_len": 8192, "chips": 1, "sequences": 1,
            "peaks": peaks.peak("TPU v5 lite"), "tokens_per_s": 31672.0,
            "memory_peak_bytes": 10 ** 10,
            "ledger_entry": train_job.LEDGER_ENTRY,
            "op_scopes_path": scopes and os.path.join(HERE, "data", scopes)}


def _metric(ctx, name):
    red = files.load_layer_metric(name)["reducer"]
    return reducers.find(red["name"])(ctx, red.get("args", {}))


@pytest.fixture(scope="module")
def old():
    """PR 23's fixture: recorded before the program had scopes."""
    return _ctx("train_1chip_3steps.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    """First four runs of the train step of chip call 2 of PR 24 (one v5e
    chip, train-s8k-1chip traced, the program with its ``ds.`` scopes):
    chip 0 and the host's program spans and launch events, HLO texts cut
    to ``%name = opcode(...)``, no stats; and the exported scope map cut
    to the instructions in it. Three complete steps."""
    return _ctx("train_1chip_3steps_scoped.xplane.pb",
                "train_1chip_3steps_scoped.op_scopes.json")


# ---- nothing that was there moved ------------------------------------------
@pytest.mark.parametrize("name,value", [
    ("host_gap_ms.train", 2.8115949999999446),
    ("device_step_ms.train", 256.02368500000046),
    ("device_idle.train", 0.8009174534002295),
    ("collective_ms.train", 0.0),
    ("exposed_collective_ms.train", 0.0),
])
def test_old_fixture_gives_every_existing_metric_its_value(old, name, value):
    assert _metric(old, name) == pytest.approx(value, rel=0, abs=1e-12)


def test_old_fixture_breakdown_keys_and_values(old):
    t = old["trace"]
    assert T.top_ops(t, 3) == [
        ["closed_call.35 [tpu_custom_call]", 0.06514366100000002],
        ["fusion.372", 0.054137520999999696],
        ["bitcast_dynamic-update-slice_fusion.42", 0.04741576400000008]]
    gaps = dict(T.idle_gaps_by_span(t, SPANS))
    assert gaps == {"no span": 0.006599721000001037,
                    "batch_to_device": 0.0016410859999999353,
                    "train_batch": 2.933999999993331e-05,
                    "compiled_step": 3.6000000043067554e-08}


def test_a_program_without_scopes_or_spans_leaves_the_metrics_out(old):
    """What a parent from before PR 24 gives: None, never an error."""
    for name in ("layers_fwd_ms.train", "layers_bwd_ms.train",
                 "loss_head_ms.train", "optimizer_ms.train",
                 "flash_fwd_ms.train", "flash_bwd_ms.train",
                 "flash_ms.train", "flash_roofline.train",
                 "unscoped_ms.train", "setup_import_s.train",
                 "setup_init_s.train", "setup_compile_s.train"):
        assert _metric(old, name) is None, name
    assert rp.device_scopes(old) == []
    empty = {"trace": None}
    assert _metric(empty, "clock_bracket_us.train") is None
    assert _metric(empty, "h2d_ms.train") is None
    assert rp.idle_gaps_aligned(empty, SPANS) == []
    assert train_job.report_lines(empty, {}) == []


# ---- device scopes on a trace recorded with them ---------------------------
def test_scoped_parts_telescope_to_the_device_step(scoped):
    step = _metric(scoped, "device_step_ms.train")
    assert T.step_count(scoped["trace"], STEP) == 3
    parts = {n: _metric(scoped, n) for n in (
        "layers_fwd_ms.train", "layers_bwd_ms.train", "loss_head_ms.train",
        "optimizer_ms.train", "unscoped_ms.train")}
    parts["embed"] = rp.scope_ms_per_step(
        scoped, {"pattern": r"^((fwd|bwd):)?ds\.embed\b", "module": STEP})
    assert abs(parts["layers_fwd_ms.train"] - 56.0357) < 1e-3
    assert abs(parts["layers_bwd_ms.train"] - 107.1207) < 1e-3
    assert abs(parts["loss_head_ms.train"] - 57.7190) < 1e-3
    assert abs(parts["optimizer_ms.train"] - 30.8519) < 1e-3
    assert abs(parts["embed"] - 2.8955) < 1e-3
    assert abs(parts["unscoped_ms.train"] - 1.3931) < 1e-3
    assert abs(sum(parts.values()) - step) < 0.01 * step
    assert parts["unscoped_ms.train"] < 0.02 * step
    # the two scopes together ARE flash_ms.train since PR 63; this trace's
    # only Mosaic calls are the flash pair, so the old pattern (every
    # ``tpu_custom_call``) still reads the same time here
    flash = (_metric(scoped, "flash_fwd_ms.train")
             + _metric(scoped, "flash_bwd_ms.train"))
    assert flash == pytest.approx(_metric(scoped, "flash_ms.train"),
                                  rel=1e-9)
    mosaic = reducers.device_op_ms_per_step(scoped, {
        "pattern": 'custom_call_target="tpu_custom_call"', "module": STEP})
    assert abs(flash - mosaic) < 0.01 * mosaic
    top = rp.device_scopes(scoped, 3)
    assert [s for s, _ in top] == ["bwd:ds.layers/ds.mlp",
                                   "bwd:ds.loss_head",
                                   "fwd:ds.layers/ds.mlp"]
    # the kernels have names now, and the old pattern still finds them
    assert T.top_ops(scoped["trace"], 1)[0][0] == \
        "ds_flash_bwd.10 [tpu_custom_call]"


def test_a_kernels_roofline_by_scope_reads_the_flash_kernels_share(scoped,
                                                                   old):
    """``kernel_roofline_pct`` finds its kernel by the program's scope and
    its cost by name in the architecture module: for the two flash scopes
    it IS ``flash_roofline.train`` since PR 63 (whose file took every
    Mosaic call until then, and so PR 62's rotation kernels). A second
    Pallas kernel under another scope does not move it, and the least time
    over ``flash_ms.train`` is the same number."""
    args = {"scope": r"ds\.flash_(fwd|bwd)\b", "cost": "flash_call_cost",
            "per": "layer", "module": STEP}
    read = reducers.find("kernel_roofline_pct")
    assert read(scoped, args) == pytest.approx(
        _metric(scoped, "flash_roofline.train"), rel=1e-9)
    assert abs(read(scoped, args) - 57.6905) < 1e-3
    assert read(scoped, args) == pytest.approx(
        100.0 * reducers.least_ms_per_step(scoped, "flash_call_cost", "layer")
        / _metric(scoped, "flash_ms.train"), rel=1e-9)
    # forward alone against both calls' least time: another number
    assert read(scoped, {**args, "scope": r"ds\.flash_fwd\b"}) > 100.0
    # once a step and not once a layer: the least time of one layer
    assert read(scoped, {**args, "per": "step"}) == pytest.approx(
        read(scoped, args) / MISTRAL["num_hidden_layers"])
    with pytest.raises(files.BenchmarkFileError, match="'layer' or"):
        read(scoped, {**args, "per": "call"})
    # no such scope, or a program without scopes: nothing to read
    assert read(scoped, {**args, "scope": r"ds\.moe_gmm\b"}) is None
    assert read(old, args) is None


def test_clock_bracket_and_aligned_gaps_on_the_recorded_trace(scoped):
    width = _metric(scoped, "clock_bracket_us.train")
    br = scoped["clock_bracket"]
    assert br["steps"] == 3 and br["lower"] < br["upper"] < 0
    assert abs(width - 1227.641) < 1e-2
    assert abs(1e6 * br["midpoint"] + 1369.85) < 1e-1
    plain = dict(T.idle_gaps_by_span(scoped["trace"], SPANS))
    moved = dict(rp.idle_gaps_aligned(scoped, SPANS))
    # the same idle seconds, attributed on one clock
    assert abs(sum(plain.values()) - sum(moved.values())) < 1e-9
    assert moved["no span"] < plain["no span"]
    assert "step_boundary" in moved
    assert abs(_metric(scoped, "h2d_ms.train") - 1.19811) < 1e-4


@pytest.mark.parametrize("offset_us", [-1300.0, 0.0, 800.0])
def test_a_known_clock_offset_is_recovered(offset_us):
    """A made-up trace: three steps of 100 ms; the device starts 200 us
    after the launch begins and the next train_batch begins 300 us after
    the device ends, so the bracket is 500 us wide and its midpoint lies
    50 us under the true offset."""
    ms, off = 1e-3, offset_us * 1e-6
    host = []
    t_dev_end = None
    h0 = 1.0
    mods, ops = [], []
    for k in range(4):
        if t_dev_end is not None:
            h0 = t_dev_end + 300e-6
        launch = h0 + 1 * ms
        d0 = launch + 200e-6
        t_dev_end = d0 + 100 * ms
        host += [("train_batch", h0, h0 + 2 * ms),
                 ("compiled_step", h0 + 0.9 * ms, h0 + 1.5 * ms),
                 ("TpuLoadedExecutable::ExecuteLaunch", launch,
                  launch + 0.1 * ms)]
        mods.append(("jit_train_step(1)", d0 + off, t_dev_end + off))
        ops.append(("%fusion.1 = fusion(...)", d0 + off, t_dev_end + off))
    t = T.Trace({0: {T.MODULES_LINE: mods, T.OPS_LINE: ops}},
                {"python": sorted(host, key=lambda e: e[1])})
    br = rp.clock_bracket(t, STEP, "^TpuLoadedExecutable::ExecuteLaunch$")
    assert br["steps"] == 3
    assert abs(br["upper"] - (off + 200e-6)) < 1e-9
    assert abs(br["lower"] - (off - 300e-6)) < 1e-9
    assert abs(br["midpoint"] - (off - 50e-6)) < 1e-9
    ctx = {"trace": t}
    assert abs(rp.clock_bracket_us(ctx, {
        "module": STEP, "launch": "^TpuLoadedExecutable::ExecuteLaunch$"})
        - 500.0) < 1e-6
    # aligned, the 1.5 ms gap between steps falls under train_batch and
    # its children, none of it under no span
    moved = dict(rp.idle_gaps_aligned(ctx, "^(train_batch|compiled_step)"))
    assert moved.get("no span", 0.0) < 0.4 * ms * 3


def test_a_caller_that_does_not_block_drops_the_lower_limit():
    ms = 1e-3
    host, mods = [], []
    for k in range(4):
        h0 = 1.0 + k * 10 * ms          # the host runs ahead of the device
        host += [("train_batch", h0, h0 + 2 * ms),
                 ("compiled_step", h0 + 1 * ms, h0 + 1.5 * ms)]
        mods.append(("jit_train_step(1)", h0 + 1.2 * ms + k * 20 * ms,
                     h0 + 31.2 * ms + k * 20 * ms))
    t = T.Trace({0: {T.MODULES_LINE: mods, T.OPS_LINE: mods}},
                {"python": host})
    br = rp.clock_bracket(t, STEP, "^never$")
    assert br["lower"] is None and br["midpoint"] is None
    assert rp.clock_bracket_us({"trace": t}, {"module": STEP,
                                              "launch": "^never$"}) is None
    assert any("did not block" in ln for ln in train_job.report_lines(
        {"clock_bracket": br}, {}))


# ---- set-up phases ---------------------------------------------------------
def test_setup_phases_partition_the_setup():
    ctx = {"setup_s": 20.0, "pre_build_s": 6.0,
           "program": {"import_s": 0.5,
                       "spans": {"init/topology": [0.1, 1],
                                 "init/state": [0.8, 1],
                                 "init/build_step": [0.1, 1],
                                 "first_step": [0.01, 1]},
                       "compile_s": {"jaxpr_trace": 9.0,
                                     "jaxpr_to_mlir_module": 1.5,
                                     "backend_compile": 6.0,
                                     "cache_load": 0.8}},
           "program_at_build": {"compile_s": {"jaxpr_to_mlir_module": 0.2,
                                              "backend_compile": 0.6}}}
    got = {n: _metric(ctx, n) for n in (
        "setup_import_s.train", "setup_init_s.train",
        "setup_compile_s.train")}
    assert got["setup_import_s.train"] == 0.5
    assert got["setup_init_s.train"] == pytest.approx(1.0)
    # after the engine was built; tracing and cache_load are not summed
    assert got["setup_compile_s.train"] == pytest.approx(6.7)
    # a later cell's names end otherwise: the line reads them by their stem
    for ending in (".train", ".moe"):
        metrics = {k.replace(".train", ending): {"value": v}
                   for k, v in got.items()}
        line = train_job.report_lines(ctx, metrics)[0]
        assert "the rest 11.800" in line and "5.500 before the engine" in line


def test_program_state_without_telemetry_is_only_the_import_seconds():
    state = telemetry.program_state()
    assert set(state) <= {"import_s"}
    assert telemetry.step_rows(5) == []
    assert telemetry.export("unused", "x") == {}
