"""Architecture ``deepseek_v3`` (PR 64): its reference's rotation and
blocked attention against independent forms in numpy, its counts against
a hand count, the configuration file against the catalog's published
numbers, the cell end to end on the CPU at the tiny preset, traced and
untraced, the control of what the family adds, and that what the PR adds
to the benchmark is files beside the accepted ones, none of which changed.
Run by hand with the rest of the benchmark's tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from architectures import deepseek_v3 as arch       # noqa: E402
from lib import files, peaks                        # noqa: E402
from test_benchmark import _run_rig                 # noqa: E402

CELL = "train-mla-s32k-1chip"
NAME = "kanana-2-30b-ep8-zero3-1chip"
CFG = files.load_config(NAME)
M = {k: CFG[k] for k in arch.WIDTHS if k in CFG}
M["num_experts"] = CFG["n_routed_experts"]
SEQ = 32768
# the commit this PR was written on: what `benchmark/` held before it
PARENT = "79a9c8d8fe35f76b82adeef53fc0a8621566a344"


def test_the_rotation_and_the_blocked_attention_in_numpy():
    """``rotate_pairs`` against each neighbouring pair turned by its own
    angle in float64, and ``causal_attention`` (one body over blocks of
    query rows against every key) against the whole masked softmax, at a
    key of 24 and a value of 16."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    b, s, h, r = 1, 48, 3, 8
    x = rng.normal(size=(b, s, h, r))
    got = np.asarray(arch.rotate_pairs(jnp.asarray(x, jnp.float32), 1e6))
    for i in range(r // 2):
        ang = np.arange(s) * 1e6 ** (-2 * i / r)
        c, sn = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        even, odd = x[..., 2 * i], x[..., 2 * i + 1]
        assert np.allclose(got[..., 2 * i], even * c - odd * sn, atol=2e-5)
        assert np.allclose(got[..., 2 * i + 1], odd * c + even * sn,
                           atol=2e-5)
    q, k = rng.normal(size=(2, b, 512, h, 24))
    v = rng.normal(size=(b, 512, h, 16))
    got = np.asarray(arch.causal_attention(*(
        jnp.asarray(t, jnp.float32) for t in (q, k, v))))
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(24)
    scores = np.where(np.tril(np.ones((512, 512), bool)), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)
    assert arch.Q_BLOCK < 512 and np.allclose(got, want, atol=2e-5)


def test_flops_match_the_hand_count():
    """ISSUE 64's count at sequence 32768 for layers 1 to 4: 11.0 TFLOP of
    attention pairs a layer forward, 132 a step with the backward, over
    three quarters of the step's 170."""
    parts = arch.forward_flops_per_token(M, SEQ)
    d, nh = 2048, 32
    assert parts["mla_projections"] == 4 * 2 * (
        d * nh * 192 + d * 576 + 512 * nh * 256 + nh * 128 * d)
    assert parts["mla_attention"] == 4 * 2 * 320 * nh * (SEQ + 1) / 2
    assert parts["routed_layers"] == 4 * (
        2 * d * 128 + 6 * d * 768 * (2 + 6 * 16 / 128))
    assert parts["dense_ffn"] == 0 and parts["head"] == 2 * d * 16032
    assert 10.9e12 < parts["mla_attention"] * SEQ / 4 < 11.1e12
    step = arch.train_flops_per_token(M, SEQ) * SEQ
    assert 169e12 < step < 171e12
    assert 131e12 < 3 * parts["mla_attention"] * SEQ < 133e12


def test_kernel_costs_match_the_hand_count():
    """The flash calls at LIVE pairs, key 192 and value 128, the same
    count whatever sweeps them (one row or two spans); the held experts at
    NINE matmul units a row; both bound by their products on a v5e."""
    v5e = peaks.PEAKS["TPU v5 lite"]
    nh = 32
    pairs = nh * SEQ * (SEQ + 1) // 2
    fwd = arch.mla_flash_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.mla_flash_call_cost(M, 1, SEQ, backward=True)
    assert fwd["flops"] == 4 * 2 * pairs * (192 + 128)
    assert bwd["flops"] == 4 * 2 * pairs * (3 * 192 + 2 * 128)
    assert fwd["bytes"] == 4 * SEQ * nh * ((2 * 192 + 2 * 128) * 2 + 4)
    assert bwd["bytes"] == 4 * SEQ * nh * ((4 * 192 + 4 * 128) * 2 + 4)
    half = SEQ // 2
    assert arch.live_pairs(SEQ) == 2 * arch.live_pairs(half) + half * half
    for cost in (fwd, bwd):
        assert arch.least_seconds(cost, v5e)[1] == "compute"
    # 0.80 s of a step at the chip's peak: the floor of the cell's step
    least = sum(arch.least_seconds(c, v5e)[0] for c in (fwd, bwd))
    assert 0.80 < least < 0.81
    rows = 4096
    f = arch.moe_call_cost(M, 1, SEQ, backward=False, rows=rows)
    b = arch.moe_call_cost(M, 1, SEQ, backward=True, rows=rows)
    assert f["flops"] == 4 * rows * 2 * 3 * 2048 * 768
    assert b["flops"] == 2 * f["flops"]         # 3 + 6 = nine units
    assert arch.held_share(M) == 0.75 and SEQ * 0.75 / 16 == 1536


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key,
    but for the keys ``reduced`` names; no width among them and NOT the
    context; the floors."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
         "vocab_size"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
        else:
            assert CFG["reduced"][key]["published"] == value, key
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "qk_head_dim", "v_head_dim", "head_dim", "num_experts_per_tok",
              "max_position_embeddings")
    assert not set(widths) & set(entry["reduced"])
    assert CFG["max_position_embeddings"] == row["context_length"] == SEQ
    # layers 1 to 4: four routed layers, an eighth of the experts and of
    # the vocabulary
    assert CFG["num_hidden_layers"] == 4 >= 4
    assert CFG["first_k_dense_replace"] == 0
    assert CFG["n_routed_experts"] * 8 == row["config"]["n_routed_experts"]
    assert CFG["num_routed_experts"] == row["config"]["n_routed_experts"]
    assert CFG["vocab_size"] * 8 == row["config"]["vocab_size"]
    tr = files.load_traffic("pretrain-s32k")
    assert (tr["seq_len"], tr["sequences_per_chip"]) == (SEQ, 1)
    assert tr["span_pattern"] == files.load_traffic(
        "pretrain-s16k")["span_pattern"]
    assert set(arch.CHECK_KEYS) <= set(CFG["check"])
    assert all(key in CFG or key in arch.OPTIONAL for key in arch.WIDTHS)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_runs_on_cpu(trace):
    """Control flow only: the device readers find no TPU plane; the host
    clock's metrics and the program's counters and gauge read, and nothing
    compiles inside the window."""
    line, out = _run_rig(CELL, trace, "3")
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert "compiles_in_window=0" in out
    got = set(line["metrics"])
    if trace == "0":
        assert got == {"train_tokens_per_s", "setup_s"}
        return
    assert {"mfu.kan", "held_expert_tokens.kan", "flash_segments.kan",
            "setup_init_s.kan"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    # 128 tokens x top-6 of 128: 6 rows a held expert if balanced; the
    # tiny rows are held whole
    assert 2 < line["metrics"]["held_expert_tokens.kan"]["value"] < 12
    assert line["metrics"]["flash_segments.kan"]["value"] == 1


def test_the_control_judges_the_program_and_each_planted_fault():
    """``tests/kanana_control.py`` at the tiny widths: the program passes
    the configuration's ``check`` (``tests/test_deepseek_v3.py`` of the
    program's own tests plants all seven under boosted weights, where
    every one is seen; at the init's own scale the tiny model cannot show
    them all)."""
    import cpu_rig
    import kanana_control as control
    out = control.kanana_control(CELL, 6400000019, cpu_rig.RIG)
    assert out["program"]["correct"] is True, out
    assert set(control.FAULTS) <= set(out)


def test_no_accepted_benchmark_file_changed():
    """What an adding PR may do (README, "Adding things"): every file
    ``benchmark/`` held at the parent commit still has the parent's bytes,
    and ``BENCHMARK.json`` differs from the parent's only by entries put at
    the end of its lists and the cell's name at the end of
    ``train_tokens_per_s``'s ``workloads``."""
    root = os.path.dirname(BENCH)

    def git(*args):
        return subprocess.run(["git", "-C", root, *args], check=True,
                              capture_output=True).stdout

    try:
        listed = git("ls-tree", "-r", "--name-only", PARENT,
                     "benchmark").decode().split()
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history here")
    assert len(listed) > 100
    for path in listed:
        with open(os.path.join(root, path), "rb") as f:
            now = hashlib.sha256(f.read()).hexdigest()
        assert now == hashlib.sha256(
            git("show", f"{PARENT}:{path}")).hexdigest(), path
    before = json.loads(git("show", f"{PARENT}:BENCHMARK.json"))
    after = files.benchmark_json()
    for key in ("command", "paths", "run_seconds"):
        assert after[key] == before[key]
    for key, added in (("configs", 1), ("workloads", 1), ("per_layer", 26)):
        assert after[key][:len(before[key])] == before[key], key
        assert len(after[key]) == len(before[key]) + added, key
    rate, setup = after["end_to_end"]
    assert setup == before["end_to_end"][1]
    assert rate["workloads"] == before["end_to_end"][0]["workloads"] + [CELL]
    assert {k: v for k, v in rate.items() if k != "workloads"} == {
        k: v for k, v in before["end_to_end"][0].items() if k != "workloads"}
    assert all(m["workloads"] == [CELL] and m["name"].endswith(".kan")
               for m in after["per_layer"][len(before["per_layer"]):])
