"""The benchmark's own tests. Run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import math
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from architectures import mistral                  # noqa: E402
from architectures import mistral as flops         # noqa: E402
from lib import files, peaks, reducers, traffic    # noqa: E402
from lib import trace as T                         # noqa: E402

B = files.benchmark_json()
CELLS = [w["name"] for w in B["workloads"]]
ALL_CELL_FILES = sorted(p[:-5] for p in os.listdir(
    os.path.join(BENCH, "cells")) if p.endswith(".json"))

MISTRAL = dict(hidden_size=4096, head_dim=128, num_attention_heads=32,
               num_key_value_heads=8, intermediate_size=14336,
               vocab_size=32000, num_hidden_layers=2, sliding_window=4096)


# ---- required FLOPs, roofline, peaks --------------------------------------
def test_flops_match_the_hand_count():
    """ISSUE 23's hand count at sequence 8192, depth 2: 436 M a layer,
    262 M the head, about 100 M of causal+window attention, x 3."""
    f = flops.forward_flops_per_token(MISTRAL, 8192)
    assert f["layer_matmul"] == 2 * (4096 * 4096 * 2 + 2 * 4096 * 1024
                                     + 3 * 4096 * 14336) == 436207616
    assert f["head"] == 2 * 4096 * 32000 == 262144000
    # visible keys: 4096*4097/2 below the window, 4096 each above it
    assert flops.visible_keys_total(8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096
    assert flops.visible_keys_total(8192, None) == 8192 * 8193 // 2
    assert abs(2 * f["attention_layer"] / 1e6 - 100.7) < 0.1
    assert abs(flops.train_flops_per_token(MISTRAL, 8192) / 1e9 - 3.706) < 1e-3


def test_flash_roofline_cost():
    fwd = flops.flash_call_cost(MISTRAL, 1, 8192, backward=False)
    bwd = flops.flash_call_cost(MISTRAL, 1, 8192, backward=True)
    pairs = 32 * flops.visible_keys_total(8192, 4096)
    assert fwd["flops"] == 2 * 2 * 128 * pairs
    assert bwd["flops"] == 5 * 2 * 128 * pairs
    # q and o at 32 heads, k and v at 8, bf16, plus the f32 lse row
    assert fwd["bytes"] == 2 * 8192 * 32 * 128 * 2 + 2 * 8192 * 8 * 128 * 2 \
        + 8192 * 32 * 4
    t, bound = flops.least_seconds(fwd, peaks.peak("TPU v5 lite"))
    assert bound == "compute" and abs(t * 1e3 - 2.094) < 0.01


def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert "source" in peaks.peak("TPU v5 lite")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("cpu")


# ---- the trace reduction, on a trace recorded on the chip -----------------
FIXTURE = os.path.join(HERE, "data", "train_1chip_3steps.xplane.pb")
STEP = "^jit_train_step"
MOSAIC = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def recorded():
    """First four runs of the train step of the first traced chip run of
    train-s8k-1chip (PR 23, one v5e chip), cut with event names
    shortened; three complete steps."""
    return T.Trace.from_file(FIXTURE)


def test_recorded_trace_busy_idle_and_steps(recorded):
    lo, hi = T.window(recorded)
    assert T.step_count(recorded, STEP) == 3
    assert abs((hi - lo) - 1.032589) < 1e-5
    assert abs(T.busy_seconds(recorded) - 1.024319) < 1e-5
    ctx = {"trace": recorded}
    assert abs(reducers.idle_share_pct(ctx, {}) - 0.8009) < 1e-3
    assert abs(reducers.busy_ms_per_step(ctx, {"module": STEP})
               - 256.0237) < 1e-3
    assert abs(reducers.step_gap_ms_median(ctx, {"module": STEP})
               - 2.8116) < 1e-3


def test_recorded_trace_mosaic_time_and_roofline(recorded):
    ctx = {"trace": recorded, "arch": mistral, "model": MISTRAL,
           "seq_len": 8192, "chips": 1, "sequences": 1, "peaks": peaks.peak("TPU v5 lite")}
    args = {"pattern": MOSAIC, "module": STEP}
    # flash forward + one-pass backward of two layers
    assert abs(reducers.device_op_ms_per_step(ctx, args) - 25.3967) < 1e-3
    # the least time of those calls over it (what flash_roofline.train
    # reads by scope on a trace that has scopes)
    assert abs(100.0 * reducers.least_ms_per_step(
        ctx, "flash_call_cost", "layer")
        / reducers.device_op_ms_per_step(ctx, args) - 57.693) < 1e-2
    top = T.top_ops(recorded, 3)
    assert top[0][0] == "closed_call.35 [tpu_custom_call]"
    assert abs(top[0][1] - 0.0651437) < 1e-6


def test_recorded_trace_gap_attribution(recorded):
    gaps = dict(T.idle_gaps_by_span(
        recorded, "^(train_batch|compiled_step|batch_to_device)"))
    assert abs(gaps["no span"] - 0.0065997) < 1e-6
    assert abs(gaps["batch_to_device"] - 0.0016411) < 1e-6
    lo, hi = T.window(recorded)
    assert abs(sum(gaps.values())
               - ((hi - lo) - T.busy_seconds(recorded))) < 1e-9


def test_exposed_collective_time_on_a_made_up_trace():
    """No four-chip trace is recorded here; the interval arithmetic is
    checked on a hand-made one. Chip 0: compute 0-10 and 14-20 ms inside
    a while op 0-20; an all-gather from start to done 8-16 ms (async
    line) -> 8 ms of collective; exposed: the start op's own 0.1 ms on
    the TensorCore plus 10-14 = 4.1 ms."""
    ms = 1e-3
    dev = {0: {
        T.MODULES_LINE: [("jit_train_step(1)", 0, 21 * ms),
                         ("jit_train_step(1)", 22 * ms, 43 * ms)],
        T.OPS_LINE: [("%while.1 = ...", 0, 20 * ms),
                     ("%fusion.1 = ...", 0, 8 * ms),
                     ("%all-gather-start.1 = ...", 8 * ms, 8.1 * ms),
                     ("%fusion.3 = ...", 8.1 * ms, 10 * ms),
                     ("%fusion.2 = ...", 14 * ms, 20 * ms),
                     ("%fusion.1 = ...", 22 * ms, 42 * ms)],
        T.ASYNC_LINE: [("%all-gather-start.1 = ...", 8 * ms, 16 * ms)]}}
    tr = T.Trace(dev, {"python": [("batch_to_device", 20 * ms, 21.5 * ms)]})
    ctx = {"trace": tr}
    pat = "^%(all-gather|all-reduce)"
    args = {"pattern": pat, "module": STEP,
            "lines": [T.OPS_LINE, T.ASYNC_LINE]}
    assert abs(reducers.device_op_ms_per_step(ctx, args) - 8.0) < 1e-9
    assert abs(reducers.exposed_op_ms_per_step(ctx, args) - 4.1) < 1e-9
    # the idle gap 20-22 ms: 1.5 ms under the span, 0.5 under none
    gaps = dict(T.idle_gaps_by_span(tr, "^batch_to_"))
    assert abs(gaps["batch_to_device"] - 1.5 * ms) < 1e-12
    assert abs(gaps["no span"] - 0.5 * ms) < 1e-12
    assert [e[0] for e in T.leaf_events(dev[0][T.OPS_LINE])][:2] == \
        ["%fusion.1 = ...", "%all-gather-start.1 = ..."]


# ---- traffic: the same work for every seed --------------------------------
def test_train_batches_come_from_the_seed():
    tj = files.load_traffic("pretrain-s8k")
    pool = traffic.train_batches(tj, 7, 4, 32000)
    assert len(pool) == 8 and pool[0].shape == (4, 8193)
    assert (pool[0] != pool[1]).any()
    assert (traffic.train_batches(tj, 7, 4, 32000)[3] == pool[3]).all()
    big = traffic.train_batches(tj, 2 ** 31 + 11, 1, 32000)
    assert big[0].shape == (1, 8193) and (big[0] != pool[0][:1]).any()
    assert 0 <= big[0].min() and big[0].max() < 32000


@pytest.mark.parametrize("ahead_s, step_s, traced, want", [
    (6.0, 0.576, False, 11),    # 6 s of steps, rounded up
    (6.0, 1.5, False, 4),
    (6.0, 30.0, False, 1),      # a step longer than the span: one ahead
    (6.0, 0.01, False, 64),     # never more in flight than MAX_AHEAD
    (6.0, float("inf"), False, 1),      # no warm-up step was timed
    (6.0, 0.576, True, 0),      # a traced run waits for every step
    (None, 0.576, False, 0),    # a traffic file without the key
])
def test_steps_sent_ahead(ahead_s, step_s, traced, want):
    from kinds import train_job
    tr = {} if ahead_s is None else {"dispatch_ahead_seconds": ahead_s}
    assert train_job.steps_ahead(tr, step_s, traced) == want


def test_a_traffic_that_sends_ahead_sends_four_to_eight_seconds():
    """A stall of the host shorter than that leaves the chip fed, and the
    last wait has an end; a file without the key waits for every step."""
    ahead = {name: files.load_traffic(name).get("dispatch_ahead_seconds")
             for name in sorted({w["traffic"] for w in B["workloads"]})}
    assert all(v is None or 4.0 <= v <= 8.0 for v in ahead.values()), ahead
    assert ahead["pretrain-s16k"] == 6.0


# ---- BENCHMARK.json and the files agree -----------------------------------
def test_no_cell_reads_one_thing_under_two_names():
    """One entry a definition (PR 49 folded 103 copies into 23, PR 63 the
    sixty that PRs 54, 56 and 60 brought into the entries they copied). What an
    adding PR can still do, because it may edit no file that is there, is
    bring COPIES of the step readers under a suffix of its own, for its
    own cells (README, "Adding things", point 4); the next ``benchmark``
    PR folds them, which it can exactly because two entries of one
    definition never share a cell."""
    seen = {}
    for entry in B["per_layer"]:
        spec = files.load_layer_metric(entry["name"])
        key = json.dumps([spec[k] for k in (
            "layer", "unit", "source", "moves", "reducer")], sort_keys=True)
        for other, cells in seen.setdefault(key, []):
            assert not set(cells) & set(entry["workloads"]), (
                entry["name"], other)
        seen[key].append((entry["name"], entry["workloads"]))


def test_benchmark_json_agrees_with_the_files():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    per_layer = {m["name"]: m for m in B["per_layer"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert 1 <= B["run_seconds"] <= 51
    four = [w for w in B["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(B["workloads"]) // 4)
    cfgs = {c["name"]: c for c in B["configs"]}
    for w in B["workloads"]:
        cell = files.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) \
            == (w["config"], w["traffic"], w["chips"], w["why"])
        assert len(w["why"]) <= 200
        c = cfgs[w["config"]]
        assert os.path.isfile(os.path.join(CHECKOUT, c["file"]))
        assert sorted(c["reduced"]) == sorted(cell["config_file"]["reduced"])
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) > 1
        for m in cell["end_to_end"]:
            assert w["name"] in e2e[m].get("workloads", CELLS), (m, w["name"])
        assert cell["per_layer"]
        # a cell reports exactly what the contract owes it
        assert set(cell["per_layer"]) == {
            m["name"] for m in B["per_layer"] if w["name"] in m["workloads"]}
        for m in cell["per_layer"]:
            spec = files.load_layer_metric(m)
            entry = per_layer[m]
            assert (spec["unit"], spec["better"], spec["source"],
                    spec["layer"], spec["moves"]) == (
                entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"])
            # every cell that reports a metric reports the one it moves
            assert spec["moves"] in cell["end_to_end"], (m, w["name"])
            assert callable(reducers.find(spec["reducer"]["name"]))
    for m in B["end_to_end"]:
        for wn in m.get("workloads", CELLS):
            assert m["name"] in files.load_cell(wn)["end_to_end"]
    used = {c for w in B["workloads"] for c in [w["config"]]}
    assert used == set(cfgs)


def test_every_name_a_file_gives_resolves():
    """No run needed: each configuration's architecture is a module with
    the three parts, each traffic mix's kind a module with ``run``, and
    each metric file (a cell lists it or not) names cells that list it,
    a reducer of the one table and a ``BENCHMARK.json`` entry."""
    for path in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        cfg = files.load_config(path[:-5])
        arch = files.load_module("architectures", cfg["architecture"], path)
        assert isinstance(arch.WIDTHS, dict)
        assert callable(arch.reference) and callable(
            arch.train_flops_per_token)
        missing = set(arch.WIDTHS) - set(cfg) - set(arch.OPTIONAL)
        assert not missing, (path, missing)
    for path in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        kind = files.load_traffic(path[:-5])["kind"]
        assert callable(files.load_module("kinds", kind, path).run)
    per_layer = {m["name"]: m for m in B["per_layer"]}
    for path in sorted(os.listdir(os.path.join(BENCH, "layer_metrics"))):
        spec = files.load_layer_metric(path[:-5])
        assert callable(reducers.find(spec["reducer"]["name"]))
        # a list on both sides, the same list (tier-1 holds the same)
        assert sorted(per_layer[spec["name"]]["workloads"]) == sorted(
            spec["cells"])
        for cell in spec["cells"]:
            assert spec["name"] in files.load_cell(cell)["per_layer"]
    for stem in files.known_modules("architectures"):
        with open(os.path.join(BENCH, "architectures", stem + ".py")) as f:
            assert not re.search(r"^\s*(import|from)\s+deepspeed_tpu",
                                 f.read(), re.M), stem


# ---- every cell, end to end, on the CPU at the tiny preset ----------------
def _run_rig(cell, trace="0", seconds="2"):
    chips = files.load_cell(cell)["chips"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   CHECKOUT, ".bench_trace", "test_jax_cache"))
    p = subprocess.run([sys.executable, os.path.join(HERE, "cpu_rig.py"),
                        cell, trace, seconds], cwd=CHECKOUT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


@pytest.mark.parametrize("cell", ALL_CELL_FILES)
def test_cell_end_to_end_on_cpu(cell):
    """Control flow and the contract's final line only: a CPU run gives
    no device number, and says so in its ``device`` block."""
    spec = files.load_cell(cell)
    line, out = _run_rig(cell, "0", "3")
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == spec["chips"]
    assert "memory_peak_bytes" in line["device"]
    assert set(line["metrics"]) == set(spec["end_to_end"])
    units = {m["name"]: m["unit"] for m in B["end_to_end"]}
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name]
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["correct"] is True, out[-3000:]
    # untraced: steps were sent ahead, and every step sent was waited for
    # and counted (a loss that is not finite would have failed the run)
    train = [ln for ln in out.splitlines() if ln.startswith("train: ")][-1]
    sends = "dispatch_ahead_seconds" in spec["traffic_file"]
    assert (int(train.split("ahead=")[1].split()[0]) >= 1) == sends
    assert int(train.split("steps=")[1].split()[0]) == line["attempted"]


def test_traced_run_reports_per_layer_metrics_on_cpu():
    """Control flow only: a CPU trace has no TPU plane, so the device
    readers return nothing; the host clock and the set-up phases read."""
    line, out = _run_rig("train-s8k-1chip", "1", "3")
    assert line["correct"] is True and line["failed"] == 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    got = set(line["metrics"])
    assert {"mfu.train", "setup_import_s.train", "setup_init_s.train",
            "setup_compile_s.train"} <= got
    assert not got & {"device_idle.train", "flash_ms.train",
                      "layers_fwd_ms.train"}
    assert got <= set(files.load_cell("train-s8k-1chip")["per_layer"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps",
                                      "device_scopes", "idle_gaps_aligned"}
    assert all(len(v) <= 10 for v in line["breakdown"].values())
    setup = [ln for ln in out.splitlines() if ln.startswith("setup:")]
    assert len(setup) == 1
    parts = sum(line["metrics"][k]["value"] for k in (
        "setup_import_s.train", "setup_init_s.train",
        "setup_compile_s.train"))
    assert 0 < parts < float(setup[0].split("setup_s=")[1].split()[0])
    assert any(ln.startswith("steptrace over") for ln in out.splitlines())


def test_untraced_run_turns_no_telemetry_on():
    """``--trace 0``: no spans, no executable ledger, no program account."""
    _, out = _run_rig("train-s8k-1chip", "0", "2")
    assert not any(ln.startswith(("setup:", "clock:", "steptrace"))
                   for ln in out.splitlines())
    code = ("import sys; sys.path[:0] = [%r, %r]; import cpu_rig, run; "
            "run.main(['--workload', 'train-s8k-1chip', '--seed', '5', "
            "'--seconds', '1', '--trace', '0'], rig=cpu_rig.RIG); "
            "assert 'deepspeed_tpu.telemetry' not in sys.modules" % (HERE, BENCH))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   CHECKOUT, ".bench_trace", "test_jax_cache"))
    p = subprocess.run([sys.executable, "-c", code], cwd=CHECKOUT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]


def _control(cell, stand_in):
    chips = files.load_cell(cell)["chips"]
    code = ("import sys, json; sys.path[:0] = [%r, %r]; "
            "import cpu_rig, control; print(json.dumps("
            "control.control(%r, 3000000019, cpu_rig.RIG, %r)))"
            % (HERE, BENCH, cell, stand_in))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   CHECKOUT, ".bench_trace", "test_jax_cache"))
    p = subprocess.run([sys.executable, "-c", code], cwd=CHECKOUT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["control"] == stand_in and got["rounded_matmuls"] > 0
    return got


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct_on_cpu(cell):
    """``tests/control.py`` at the tiny preset: the reference with fp8
    operands in the program's place fails the check the program passes
    (on the chip at the cells' own size: ``PERF.md`` section 2). A cell's
    limits are set from chip readings at its own size, and may lie above
    what fp8 reads at the tiny widths (the Mellum cell's ``logits_err_rms``:
    limit 0.055, fp8 0.0377 here, 0.2242 at least on the chip). Which case
    a cell is in is read off the run, not off its name: where fp8 stays
    under the limit, the CPU holds what it can, that the control reads an
    order of magnitude over the bfloat16 stand-in, which is what a right
    program looks like; NOT correct is then held on the chip alone."""
    got = _control(cell, "float8_e4m3fn")
    if got["got"]["logits_err_rms"] > got["limits"]["logits_err_rms"]:
        assert got["correct"] is False
        return
    right = _control(cell, "bfloat16")
    assert right["correct"] is True
    for key in ("logits_err_rms", "logits_err_max"):
        assert got["got"][key] > 10 * right["got"][key], key


def test_no_tpu_no_result():
    """At the real size, without a TPU: non-zero exit, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "train-s8k-1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
