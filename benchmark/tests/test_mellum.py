"""Architecture ``mellum`` (PR 38): its counts against a hand count, the
configuration file against the catalog's published numbers, the cell end to
end on the CPU at the tiny preset, traced and untraced, and the control of
the two attention kinds. Run by hand with the rest of the benchmark's tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from architectures import mellum as arch           # noqa: E402
from lib import files, peaks                       # noqa: E402
from test_benchmark import _run_rig                # noqa: E402

CELL = "train-moe-s16k-1chip"
NAME = "mellum2-12b-ep4-zero3-1chip"
CFG = files.load_config(NAME)
M = {k: CFG[k] for k in arch.WIDTHS}
SEQ = 16384


def test_flops_match_the_hand_count():
    """ISSUE 38's parts, per token forward: four layers' projections (32 q
    and 4 kv heads of 128 on 2304), the live pairs of three window layers
    (16,253,440 a head of 134,225,920) and of the full one, the router's
    64 outputs, a token's held share of its 8 experts of 896 (2 of them),
    the head over 24576 rows."""
    assert arch.live_pairs(M, SEQ, "swa") == 16253440
    assert arch.live_pairs(M, SEQ, "full") == 134225920
    f = arch.forward_flops_per_token(M, SEQ)
    assert f["projections"] == 4 * 2 * 21233664
    assert f["swa_attention"] == 3 * 4 * 128 * 32 * 16253440 / SEQ
    assert f["full_attention"] == 4 * 128 * 32 * 134225920 / SEQ
    assert f["router"] == 4 * 2 * 2304 * 64
    assert f["held_experts"] == 4 * 2 * 6193152 * 2
    assert f["head"] == 2 * 2304 * 24576
    assert f["total"] == sum(v for k, v in f.items() if k != "total")
    per_step = arch.train_flops_per_token(M, SEQ) * SEQ
    # projections 8.35, experts 4.87, full 6.60, window 2.40, head 5.57,
    # router 0.06 T (ISSUE 38's 29 T counts the flash backward's rerun)
    assert abs(per_step / 1e12 - 27.84) < 0.01


def test_kernel_costs_match_the_hand_count():
    v5e = peaks.peak("TPU v5 lite")
    rows = SEQ * 32
    q_like, kv_like = rows * 128 * 2, SEQ * 4 * 128 * 2
    for kind, cost, layers, pairs in (
            ("swa", arch.swa_flash_call_cost, 3, 16253440),
            ("full", arch.full_flash_call_cost, 1, 134225920)):
        fwd = cost(M, 1, SEQ, backward=False)
        bwd = cost(M, 1, SEQ, backward=True)
        assert fwd["flops"] == layers * 4 * 128 * 32 * pairs, kind
        assert bwd["flops"] == layers * 10 * 128 * 32 * pairs, kind
        assert fwd["bytes"] == layers * (2 * q_like + 2 * kv_like + rows * 4)
        assert bwd["bytes"] == layers * (4 * q_like + 4 * kv_like + rows * 4)
    full = [arch.least_seconds(arch.full_flash_call_cost(
        M, 1, SEQ, backward=b), v5e) for b in (False, True)]
    assert [bound for _, bound in full] == ["compute", "compute"]
    assert abs(1e3 * sum(t for t, _ in full) - 39.07) < 0.05    # ms a step
    swa = [arch.least_seconds(arch.swa_flash_call_cost(
        M, 1, SEQ, backward=b), v5e)[0] for b in (False, True)]
    assert abs(1e3 * sum(swa) - 14.19) < 0.05
    moe = arch.moe_call_cost(M, 1, SEQ, backward=False)
    assert moe["flops"] == 4 * 32768 * 6 * 2304 * 896
    assert moe["bytes"] == 4 * (16 * 6193152 * 2 + 2 * 32768 * 2304 * 2)
    counted = arch.moe_call_cost(M, 1, SEQ, backward=True, rows=1000.0)
    assert counted["flops"] == 4 * 2 * 1000 * 6 * 2304 * 896


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key,
    but for the keys ``reduced`` names; no width among them; the floors."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "mlp_layer_types",
         "num_experts", "vocab_size", "max_position_embeddings"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    # one whole period at the published 3 : 1, a quarter of the experts
    # and of the vocabulary; the router keeps its width
    assert CFG["layer_types"] == row["config"]["layer_types"][:4]
    assert CFG["num_hidden_layers"] == len(CFG["layer_types"]) == 4
    assert CFG["num_experts"] >= 8
    assert CFG["num_routed_experts"] == row["config"]["num_experts"] == 64
    assert CFG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    # the engine of the other routed cell, at a rate under which the pool
    # of 8 batches is not learnt by heart inside a window (``assumed``)
    mine = json.loads(json.dumps(CFG["program"]["ds_config"]))
    assert mine["optimizer"]["params"].pop("lr") == 2e-5
    theirs = files.load_config(
        "kimi-linear-48b-ep32-zero3-1chip")["program"]["ds_config"]
    theirs["optimizer"]["params"].pop("lr")
    assert mine == theirs
    assert set(arch.CHECK_KEYS) <= set(CFG["check"])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_runs_on_cpu(trace):
    """Control flow only: the device readers find no TPU plane; the host
    clock's metrics and the program's counters read, and nothing compiles
    inside the window."""
    line, out = _run_rig(CELL, trace, "3")
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert "compiles_in_window=0" in out
    got = set(line["metrics"])
    if trace == "0":
        assert got == {"train_tokens_per_s", "setup_s"}
        return
    assert {"mfu.train", "h2d_ms.train", "setup_import_s.train",
            "held_expert_tokens.routed", "moe_pad_share.routed"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    assert 0.0 < line["metrics"]["moe_pad_share.routed"]["value"] < 100.0


def test_the_attention_kinds_control_sees_each_planted_fault():
    """``tests/attention_kinds_control.py`` at the tiny widths (which need
    a larger boost than the cell's to peak the softmax): the program passes
    the configuration's ``check``, no planted departure does."""
    import attention_kinds_control as control
    import cpu_rig
    rig = dict(cpu_rig.RIG, attention_boost={"wq": 4.0, "wk": 4.0,
                                             "wv": 8.0, "wo": 8.0})
    out = control.attention_kinds_control(CELL, 3800000019, rig)
    assert out["program"]["correct"] is True, out
    for name in ("no_window", "no_attention_factor", "tables_swapped"):
        assert out[name]["correct"] is False, (name, out[name])
    assert out["ok"] is True
