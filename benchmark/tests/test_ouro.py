"""Architecture ``ouro`` (PR 42): its counts against a hand count, the
configuration file against the catalog's published numbers, the cell end to
end on the CPU at the tiny preset, traced and untraced, and the control of
the loop. Run by hand with the rest of the benchmark's tests:

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

from architectures import ouro as arch             # noqa: E402
from lib import files, peaks                       # noqa: E402
from test_benchmark import _run_rig                # noqa: E402

CELL = "train-loop-s8k-1chip"
NAME = "ouro-2.6b-pp6-zero3-1chip"
CFG = files.load_config(NAME)
M = {k: CFG[k] for k in arch.WIDTHS}
SEQ = 8192


def test_flops_match_the_hand_count():
    """ISSUE 42's parts: 4 x 8 layer applications of 51,380,224 matmul
    parameters (4 x 2048^2 + 3 x 2048 x 5632) and 33,558,528 causal pairs
    a head at 8192, 4 head products over 49152 rows, 4 gates."""
    assert arch.layer_matmul_params(M) == 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert arch.visible_keys_total(SEQ) == 33558528
    f = arch.forward_flops_per_token(M, SEQ)
    assert f["layer_matmuls"] == 32 * 2 * 51380224
    assert f["attention"] == 32 * 4 * 128 * 16 * 33558528 / SEQ
    assert f["heads"] == 4 * 2 * 2048 * 49152
    assert f["gates"] == 4 * 2 * 2048
    assert f["total"] == sum(v for k, v in f.items() if k != "total")
    per_token = arch.train_flops_per_token(M, SEQ)
    # layers 9.87, attention 3.22, heads 2.42 GFLOP a token: 15.50
    assert abs(per_token / 1e9 - 15.50) < 0.01
    # 127.0 TFLOP a step, 0.645 s at the chip's peak
    v5e = peaks.peak("TPU v5 lite")
    assert abs(per_token * SEQ / v5e["bf16_flops_per_s"] - 0.6447) < 0.001


def test_kernel_costs_match_the_hand_count():
    v5e = peaks.peak("TPU v5 lite")
    rows = SEQ * 16
    q_like = rows * 128 * 2
    fwd = arch.mha_flash_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.mha_flash_call_cost(M, 1, SEQ, backward=True)
    assert fwd["flops"] == 32 * 4 * 128 * 16 * 33558528
    assert bwd["flops"] == 32 * 10 * 128 * 16 * 33558528
    # 16 key heads: k and v are as wide as q
    assert fwd["bytes"] == 32 * (4 * q_like + rows * 4)
    assert bwd["bytes"] == 32 * (8 * q_like + rows * 4)
    least = [arch.least_seconds(c, v5e) for c in (fwd, bwd)]
    assert [bound for _, bound in least] == ["compute", "compute"]
    # 44.65 + 111.63 ms a step
    assert abs(1e3 * sum(t for t, _ in least) - 156.28) < 0.05


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key, but
    for the three keys ``reduced`` names; no width among them; the loop's
    count whole; the engine of the Mistral cells."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "max_position_embeddings"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    assert CFG["total_ut_steps"] == 4 and CFG["vocab_size"] == 49152
    assert CFG["num_hidden_layers"] == len(CFG["layer_types"]) == 8
    assert CFG["layer_types"] == row["config"]["layer_types"][:8]
    assert set(arch.WIDTHS) - set(row["config"]) == {"exit_entropy_beta"}
    assert "exit_entropy_beta" in CFG["assumed"]["loss"]
    for said in ("sandwich_norm", "state_between_passes", "exit_gate",
                 "loss", "not_built", "kept_and_not_read", "weights",
                 "optimizer"):
        assert CFG["assumed"][said]
    assert "6 pipeline stages of 8 layers" in CFG["deployment"]
    mine = CFG["program"]["ds_config"]
    theirs = files.load_config("mistral-7b-zero3-1chip")["program"][
        "ds_config"]
    assert mine == theirs
    # the traffic is the Mistral cells' in every key but the traced seconds
    tr = files.load_traffic("pretrain-s8k-trace12")
    base = files.load_traffic("pretrain-s8k")
    assert {k for k in base if base[k] != tr[k]} == {"trace_seconds", "why"}
    assert tr["trace_seconds"] == 12.0


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_runs_on_cpu(trace):
    """Control flow only: the device readers find no TPU plane; the host
    clock's metrics and the program's gauges read, and nothing compiles
    inside the window."""
    line, out = _run_rig(CELL, trace, "3")
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert "compiles_in_window=0" in out
    got = set(line["metrics"])
    if trace == "0":
        assert got == {"train_tokens_per_s", "setup_s"}
        return
    assert {"mfu.train", "h2d_ms.train", "setup_import_s.train",
            "expected_exit_pass.ouro"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    assert 1.0 <= line["metrics"]["expected_exit_pass.ouro"]["value"] <= 4.0


def test_the_new_readers_read_nothing_from_a_program_without_the_loop():
    """On the parent commit, or in another family's cell, there is no
    ``ds.loop`` scope and no exit gauge: each reader returns None and does
    not raise."""
    from lib import reducers
    for name, args in (
            ("scope_less_ms_per_step",
             {"pattern": "ds\\.loop\\b", "less": "ds\\.(attn|mlp)\\b",
              "module": "^jit_train_step"}),
            ("expected_exit_pass", {})):
        assert reducers.find(name)({"trace": None}, args) is None


def test_the_loop_control_sees_each_planted_fault():
    """``tests/loop_control.py`` at the tiny widths: the program passes the
    configuration's ``check``, no planted departure does."""
    import cpu_rig
    import loop_control as control
    out = control.loop_control(CELL, 3800000019, dict(cpu_rig.RIG))
    assert out["program"]["correct"] is True, out
    for name in ("passes_3", "no_output_norms", "unnormed_carry",
                 "uniform_exit", "beta_0"):
        assert out[name]["correct"] is False, (name, out[name])
    assert out["ok"] is True
