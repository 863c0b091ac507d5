"""Architecture ``laguna`` (PR 60): its counts against a hand count, the
reference against itself in blocks and whole, the configuration file
against the catalog's published numbers, the cell end to end on the CPU at
the tiny preset, traced and untraced, and the control of what the family
adds. Run by hand with the rest of the benchmark's tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from architectures import laguna as arch           # noqa: E402
from lib import files, peaks                       # noqa: E402
from test_benchmark import _run_rig                # noqa: E402

CELL = "train-lag-s8k-1chip"
NAME = "laguna-s-2.1-ep32-zero3-1chip"
CFG = files.load_config(NAME)
M = {k: CFG[k] for k in arch.WIDTHS}
SEQ = 8192


def test_flops_match_the_hand_count():
    """ISSUE 60's parts, per token forward: the projections at THE KIND'S
    head count (72 in the three window layers, 48 in the full one, over 8
    key heads of 128 on 3072) with the gate's narrow column block, the
    live pairs of both kinds, the router's 256 outputs, the shared expert
    whole and 10 x 8 / 256 of a held expert; x 3 for training: 19.8 T a
    step of 8192 tokens."""
    parts = arch.forward_flops_per_token(M, SEQ)
    assert arch.kind_heads(M) == {"sliding_attention": 72,
                                  "full_attention": 48}
    assert parts["projections"] == sum(
        2 * (2 * 3072 * nh * 128 + 2 * 3072 * 1024 + 3072 * nh)
        for nh in (72, 72, 72, 48))
    assert arch.live_pairs(M, SEQ, "swa") == 4063488
    assert arch.live_pairs(M, SEQ, "full") == 33558528
    assert parts["swa_attention"] == 3 * 4 * 128 * 72 * 4063488 / SEQ
    assert parts["full_attention"] == 4 * 128 * 48 * 33558528 / SEQ
    assert arch.held_share(M) == 10 * 8 / 256
    assert parts["routed_layers"] == 4 * (
        2 * 3072 * 256 + 6 * 3072 * 1024 * (1 + 0.3125))
    assert parts["dense_ffn"] == 0 and parts["head"] == 2 * 3072 * 12544
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    step = SEQ * arch.train_flops_per_token(M, SEQ)
    assert 19.7e12 < step < 19.9e12


def test_kernel_costs_match_the_hand_count():
    """The two flash costs at their own head counts (live pairs only, each
    operand read once), the held experts' at the units that run (3 forward,
    8 backward a row), and which bound holds on a v5e."""
    pk = peaks.peak("TPU v5 lite")
    swa_f = arch.swa_flash_call_cost(M, 1, SEQ, backward=False)
    swa_b = arch.swa_flash_call_cost(M, 1, SEQ, backward=True)
    full_f = arch.full_flash_call_cost(M, 1, SEQ, backward=False)
    assert swa_f["flops"] == 3 * 4 * 128 * 72 * 4063488
    assert swa_b["flops"] == 3 * 10 * 128 * 72 * 4063488
    assert full_f["flops"] == 4 * 128 * 48 * 33558528
    q_like, kv_like = SEQ * 72 * 128 * 2, SEQ * 8 * 128 * 2
    assert swa_f["bytes"] == 3 * (2 * q_like + 2 * kv_like + SEQ * 72 * 4)
    assert swa_b["bytes"] == 3 * (4 * q_like + 4 * kv_like + SEQ * 72 * 4)
    assert arch.least_seconds(full_f, pk)[1] == "compute"
    # a window layer's forward: 0.45 T of live pairs' work on 0.33 GB
    assert arch.least_seconds(swa_f, pk)[1] == "compute"
    moe_f = arch.moe_call_cost(M, 1, SEQ, backward=False)
    moe_b = arch.moe_call_cost(M, 1, SEQ, backward=True)
    rows = SEQ * 0.3125
    assert moe_f["flops"] == 4 * rows * 3 * 2 * 3072 * 1024
    assert moe_b["flops"] == 4 * rows * 8 * 2 * 3072 * 1024
    weights = 8 * 3 * 3072 * 1024
    assert moe_f["bytes"] == 4 * (weights * 2 + 2 * rows * 3072 * 2)
    assert moe_b["bytes"] == 4 * (weights * 6 + 3 * rows * 3072 * 2)
    assert arch.moe_call_cost(M, 1, SEQ, backward=False, rows=100)[
        "flops"] == 4 * 100 * 6 * 3072 * 1024


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """The attention by blocks of query rows and the feed-forwards by
    blocks of rows are memory bounds only: at block sizes that cut a
    256-token sequence into eight the reference's loss, tail logits and
    mask are the whole sequence's."""
    import jax
    import jax.numpy as jnp
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    rng = np.random.default_rng(0)
    m = dict(M, hidden_size=32, head_dim=16, num_key_value_heads=2,
             num_attention_heads_per_layer=[6, 4], sliding_window=48,
             layer_types=["sliding_attention", "full_attention"],
             mlp_layer_types=["dense", "sparse"], num_experts=8,
             num_routed_experts=16, num_experts_per_tok=3, vocab_size=64,
             routing_margin=0.02)
    d, hd, f = 32, 16, 24
    w = lambda *s, scale=0.3: jnp.asarray(  # noqa: E731
        rng.normal(size=s) * scale, jnp.float32)

    def attn(nh):
        return {"wq": w(d, nh * hd), "wk": w(d, 2 * hd), "wv": w(d, 2 * hd),
                "wg": w(d, nh), "wo": w(nh * hd, d)}

    def ffn(*lead):
        return {"w_gate": w(*lead, d, f), "w_up": w(*lead, d, f),
                "w_down": w(*lead, f, d)}

    norms = {"ln1_scale": jnp.ones(d), "ln2_scale": jnp.ones(d)}
    params = {
        "embed": {"tokens": w(64, d, scale=1.0)},
        "layers": {"lead": {"0": {**norms, "swa": attn(6), "mlp": ffn()}},
                   "period": {},
                   "tail": {"0": {**norms, "full": attn(4), "moe": {
                       "router": w(d, 16), "experts": ffn(8),
                       "shared": ffn()}}}},
        "final_norm": {"scale": jnp.ones(d)}, "lm_head": w(d, 64)}
    tok = jnp.asarray(rng.integers(0, 64, (2, 257)))

    def run():
        jax.clear_caches()
        with jax.default_matmul_precision("highest"):
            return arch.reference(params, tok[:, :-1], tok[:, 1:], m, 64)

    whole = run()
    monkeypatch.setattr(arch.mellum, "Q_BLOCK", 32)
    monkeypatch.setattr(sys.modules["architectures.lfm2_moe"], "ROW_BLOCK",
                        64)
    blocks = run()
    assert abs(whole[0] - blocks[0]) < 1e-5 * abs(whole[0])
    np.testing.assert_allclose(whole[1], blocks[1], atol=2e-5)
    np.testing.assert_array_equal(whole[2], blocks[2])
    assert 0 < int(np.sum(np.asarray(whole[2]))) <= whole[2].size


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key,
    but for the keys ``reduced`` names; no width among them; the floors."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "mlp_layer_types",
         "num_attention_heads_per_layer", "gating_types", "mlp_only_layers",
         "num_experts", "vocab_size", "max_position_embeddings"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    # layers 1 to 4: one whole period at the published 3 : 1 behind the
    # leading dense layer, the lists cut alike; the floors of experts and
    # vocabulary; the router keeps its width
    published = row["config"]
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert CFG[key] == published[key][1:5], key
    assert CFG["num_hidden_layers"] == 4 and CFG["mlp_only_layers"] == []
    assert CFG["num_experts"] == 8
    assert CFG["num_routed_experts"] == published["num_experts"] == 256
    assert CFG["vocab_size"] * 8 == published["vocab_size"]
    assert set(arch.CHECK_KEYS) <= set(CFG["check"])
    for name in ("router score", "shared expert", "the gate", "rotation",
                 "weights", "optimizer", "router gradient"):
        assert name in CFG["assumed"], name


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_runs_on_cpu(trace):
    """Control flow only: the device readers find no TPU plane; the host
    clock's metric and the program's counter read, and nothing compiles
    inside the window (the cut lists of the file keep the tiny preset's 6
    and 4 heads: the head count is the kind's, not the file's)."""
    line, out = _run_rig(CELL, trace, "3")
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert "compiles_in_window=0" in out
    got = set(line["metrics"])
    if trace == "0":
        assert got == {"train_tokens_per_s", "setup_s"}
        return
    assert {"mfu.train", "held_expert_tokens.routed",
            "setup_init_s.train"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    assert len(files.load_cell(CELL)["per_layer"]) == 26
    assert 3.0 < line["metrics"]["held_expert_tokens.routed"]["value"] < 7.0


def test_the_control_judges_the_program_and_each_planted_fault():
    """``tests/laguna_control.py`` at the tiny widths: the program passes
    the configuration's ``check`` and every fault is planted and judged
    (at the init's own scale a layer of hidden 64 adds a hundredth of the
    embedding, so a fault is not SEEN here: ``tests/test_laguna.py`` of the
    program's own tests plants all seven under boosted weights, where
    every one is refused; on the chip, at the cell's own size, the control
    sees each)."""
    import cpu_rig
    import laguna_control as control
    out = control.laguna_control(CELL, 6000000019, cpu_rig.RIG)
    assert out["program"]["correct"] is True, out
    assert set(control.FAULTS) <= set(out)
    # the gate left out is the one departure the tiny widths show
    assert out["gate_left_out"]["got"]["logits_err_rms"] > 1.2 * out[
        "program"]["got"]["logits_err_rms"]
