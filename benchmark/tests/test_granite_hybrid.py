"""Architecture ``granite_hybrid`` (PR 34): its counts against a hand
count, the configuration file against the catalog's published numbers, and
the cell end to end on the CPU at the tiny preset, traced. Run by hand with
the rest of the benchmark's tests:

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

from architectures import granite_hybrid as arch   # noqa: E402
from lib import files, peaks                       # noqa: E402
from test_benchmark import _run_rig                # noqa: E402

CELL = "train-ssm-s8k-1chip"
NAME = "granite-4.0-h-micro-zero3-1chip"
CFG = files.load_config(NAME)
M = {k: CFG[k] for k in arch.WIDTHS}
SEQ = 8192


def test_flops_match_the_hand_count():
    """ISSUE 34's parts, per token forward: a Mamba layer's projections
    (2048 x 8512 in, 4096 x 2048 out) and its convolution, the recurrence's
    write and read of a [64, 128] state a head, the attention layer at 32
    / 8 heads of 64, ten SwiGLUs of 8192, the tied 12544-row head."""
    f = arch.forward_flops_per_token(M, SEQ)
    mamba = 2 * (2048 * 8512 + 4096 * 2048) + 2 * 4 * 4352
    assert f["mamba_projections"] == 9 * mamba
    assert f["ssd_state"] == 9 * 4 * 64 * 64 * 128
    assert f["attention_projections"] == 2 * (2 * 2048 * 2048
                                              + 2 * 2048 * 512)
    assert f["attention"] == 4 * 64 * 32 * (SEQ + 1) / 2
    assert f["ffn"] == 10 * 2 * 3 * 2048 * 8192
    assert f["head"] == 2 * 2048 * 12544
    assert f["total"] == sum(v for k, v in f.items() if k != "total")
    per_step = arch.train_flops_per_token(M, SEQ) * SEQ
    assert abs(per_step / 1e12 - 39.24) < 0.01


def test_kernel_costs_match_the_hand_count():
    v5e = peaks.peak("TPU v5 lite")
    fwd = arch.ssd_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.ssd_call_cost(M, 1, SEQ, backward=True)
    tokens = SEQ * 64
    assert fwd["flops"] == 9 * 4 * 64 * 128 * tokens
    assert fwd["bytes"] == 9 * (tokens * (2 * 64 * 2 + 4) + SEQ * 512)
    assert bwd["flops"] == 2 * fwd["flops"]
    assert bwd["bytes"] == 9 * (tokens * (3 * 64 * 2 + 8) + SEQ * 1024)
    least = [arch.least_seconds(c, v5e) for c in (fwd, bwd)]
    assert [bound for _, bound in least] == ["memory", "memory"]
    assert abs(1e3 * sum(t for t, _ in least) - 3.89) < 0.02    # ms a step
    flash = [arch.gqa_flash_call_cost(M, 1, SEQ, backward=b)
             for b in (False, True)]
    pairs = 32 * SEQ * (SEQ + 1) // 2
    assert flash[0]["flops"] == 4 * 64 * pairs
    assert flash[1]["flops"] == 10 * 64 * pairs
    assert [arch.least_seconds(c, v5e)[1] for c in flash] == [
        "compute", "compute"]


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key,
    but for the keys ``reduced`` names; no width among them; the floors."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "vocab_size",
         "max_position_embeddings"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    # one whole period at the published 9 : 1, an eighth of the vocabulary
    assert CFG["layer_types"] == row["config"]["layer_types"][:10]
    assert CFG["num_hidden_layers"] == len(CFG["layer_types"]) == 10
    assert CFG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert CFG["program"]["ds_config"] == files.load_config(
        "mistral-7b-zero3-1chip")["program"]["ds_config"]


def test_traced_run_reports_on_cpu():
    """Control flow only: the device readers find no TPU plane; the host
    clock's metrics read, and nothing compiles inside the window."""
    line, out = _run_rig(CELL, "1", "3")
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    got = set(line["metrics"])
    assert {"mfu.train", "h2d_ms.train", "setup_import_s.train"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    assert "compiles_in_window=0" in out


def test_the_attention_control_sees_a_planted_softmax_scale():
    """``tests/attention_control.py`` at the tiny widths (which need a
    larger boost than the cell's to peak the softmax): the program passes
    the configuration's ``check``, the planted scale does not."""
    import attention_control
    import cpu_rig
    rig = dict(cpu_rig.RIG, attention_boost={"wq": 16.0, "wk": 16.0,
                                             "wv": 8.0, "wo": 8.0})
    out = attention_control.attention_control(CELL, 3400000019, rig)
    assert out["program"]["correct"] is True, out
    assert out["planted_scale"]["correct"] is False, out
    assert out["ok"] is True
