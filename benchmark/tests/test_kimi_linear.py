"""Architecture ``kimi_linear`` (PR 31): its counts against a hand count,
the configuration file against the catalog's published numbers, and the
cell end to end on the CPU at the tiny preset, traced. Run by hand with
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

from architectures import kimi_linear as arch      # noqa: E402
from lib import files, peaks                       # noqa: E402
from test_benchmark import _run_rig                # noqa: E402

CELL = "train-kda-s16k-1chip"
CFG = files.load_config("kimi-linear-48b-ep32-zero3-1chip")
M = {k: CFG[k] for k in arch.WIDTHS if k in CFG}
SEQ = 16384


def test_flops_match_the_hand_count():
    """ISSUE 31's parts, per token forward: KDA projections 39.5 M
    parameters less the vectors, MLA 29.1 M, the dense FFN 63.7 M, a
    routed layer's router, shared expert and a quarter of an expert, the
    20480-row head; attention at 192 + 128 a visible pair."""
    f = arch.forward_flops_per_token(M, SEQ)
    inner = 32 * 128
    kda = 2 * (3 * 2304 * inner + 2 * (2304 * 128 + 128 * inner)
               + 2304 * 32 + inner * 2304) + 2 * 3 * 4 * inner
    assert f["kda_projections"] == 4 * kda
    assert abs(kda / 2 / 1e6 - 39.5) < 0.1
    assert f["kda_state"] == 4 * 6 * 32 * 128 * 128
    mla = 2 * (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256
               + 32 * 128 * 2304)
    assert f["mla_projections"] == mla and abs(mla / 2 / 1e6 - 29.1) < 0.1
    assert f["mla_attention"] == 2 * 320 * 32 * (SEQ + 1) / 2
    assert f["dense_ffn"] == 2 * 3 * 2304 * 9216
    expert = 2 * 3 * 2304 * 1024
    assert arch.held_share(M) == 8 * 8 / 256 == 0.25
    assert f["routed_layers"] == 4 * (2 * 2304 * 256 + expert
                                      + 0.25 * expert)
    assert f["head"] == 2 * 2304 * 20480
    assert f["total"] == sum(v for k, v in f.items() if k != "total")
    per_step = arch.train_flops_per_token(M, SEQ) * SEQ
    assert abs(per_step / 1e12 - 41.875) < 0.01
    assert arch.layer_kinds(M) == [("kda", "dense"), ("kda", "moe"),
                                   ("kda", "moe"), ("mla", "moe"),
                                   ("kda", "moe")]


def test_kernel_costs_match_the_hand_count():
    pk = peaks.peak("TPU v5 lite")
    rows = SEQ * 32                         # one sequence, 32 heads
    # KDA, 4 layers: q, k, v bf16 and g float32 at 128 channels, beta,
    # o out; memory-bound, about 1 ms a layer forward
    fwd = arch.kda_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.kda_call_cost(M, 1, SEQ, backward=True)
    reads = rows * (3 * 128 * 2 + 128 * 4 + 4)
    assert fwd == {"flops": 4 * 6 * 128 * 128 * rows,
                   "bytes": 4 * (reads + rows * 128 * 2)}
    assert bwd == {"flops": 2 * fwd["flops"],
                   "bytes": 4 * (2 * reads + rows * 128 * 2)}
    t, bound = arch.least_seconds(fwd, pk)
    assert bound == "memory" and abs(t * 1e3 / 4 - 0.986) < 0.01
    # MLA flash, 1 layer, full causal: 2 matmuls forward at 192 and 128,
    # backward 3 at 192 and 2 at 128
    pairs = 32 * SEQ * (SEQ + 1) // 2
    fwd = arch.mla_flash_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.mla_flash_call_cost(M, 1, SEQ, backward=True)
    assert fwd["flops"] == 2 * pairs * (192 + 128)
    assert bwd["flops"] == 2 * pairs * (3 * 192 + 2 * 128)
    assert fwd["bytes"] == rows * (2 * (192 + 192 + 128 + 128) + 4)
    assert bwd["bytes"] == rows * (2 * 2 * (192 + 192 + 128 + 128) + 4)
    assert arch.least_seconds(fwd, pk)[1] == "compute"
    assert abs(sum(arch.least_seconds(c, pk)[0] for c in (fwd, bwd)) * 1e3
               - 50.23) < 0.05
    # held experts, 4 layers: 16384 x 8 x 8 / 256 = 4096 rows a layer
    fwd = arch.moe_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.moe_call_cost(M, 1, SEQ, backward=True)
    weights = 8 * 3 * 2304 * 1024
    assert fwd == {"flops": 4 * 4096 * 2 * 3 * 2304 * 1024,
                   "bytes": 4 * (weights * 2 + 2 * 4096 * 2304 * 2)}
    assert bwd["flops"] == 2 * fwd["flops"]
    assert bwd["bytes"] == fwd["bytes"] + 4 * (weights * 4
                                               + 4096 * 2304 * 2)
    # at the rows the program counted: 8 experts x 400 in place of 512
    less = arch.moe_call_cost(M, 1, SEQ, backward=False, rows=3200)
    assert less == {"flops": 4 * 3200 * 2 * 3 * 2304 * 1024,
                    "bytes": 4 * (weights * 2 + 2 * 3200 * 2304 * 2)}


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key,
    but for the keys ``reduced`` names; no width among them."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == "kimi-linear-48b-ep32-zero3-1chip")
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    la, pub = CFG["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {k: la[k] for k in ("head_dim", "num_heads",
                               "short_conv_kernel_size")} == {
        k: pub[k] for k in ("head_dim", "num_heads",
                            "short_conv_kernel_size")}
    assert CFG["num_routed_experts"] == row["config"]["num_experts"]
    # the floors: a whole period after the leading dense layer, 8 experts,
    # an eighth of the vocabulary
    assert CFG["num_hidden_layers"] >= 5 and CFG["num_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= row["config"]["vocab_size"]


def test_traced_run_reports_the_counters_on_cpu():
    """Control flow only: the device readers find no TPU plane; the host
    clock's and the program counter's metrics read."""
    line, out = _run_rig(CELL, "1", "3")
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    got = set(line["metrics"])
    assert {"mfu.train", "held_expert_tokens.routed"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    # 128 tokens x top-8 of 256: 4 rows a held expert under a balanced
    # router, nothing dropped (or the metric would be missing)
    assert 1 < line["metrics"]["held_expert_tokens.routed"]["value"] < 12
    assert "compiles_in_window=0" in out
