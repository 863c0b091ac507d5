"""Architecture ``xing4`` (PR 56): its reference's hyper-connections
against an independent form a token at a time, its counts against a hand
count, the configuration file against the catalog's published numbers,
the cell end to end on the CPU at the tiny preset, traced and untraced,
and the control of what the family adds. Run by hand with the rest of the
benchmark's tests:

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

from architectures import xing4 as arch             # noqa: E402
from lib import files, peaks                        # noqa: E402
from test_benchmark import _run_rig                 # noqa: E402

CELL = "train-mhc-s8k-1chip"
NAME = "xing4.0-29b-ep8-zero3-1chip"
CFG = files.load_config(NAME)
M = {k: CFG[k] for k in arch.WIDTHS if k in CFG}
M["num_experts"] = CFG["n_routed_experts"]
SEQ = 8192


def test_a_sublayer_is_the_equations_a_token_at_a_time():
    """``hyper_sublayer`` against ISSUE 56's equations written again for
    ONE token in numpy float64 and looped: the norm without gain, the
    three groups of coefficients with their own alpha, the clamp before
    ``exp``, rows then columns twenty times, ``u`` and ``X'``; ``H_res``
    comes out doubly stochastic."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    t, n, c = 24, 4, 16
    x = rng.normal(size=(1, t, n, c))
    hc = {"phi": rng.normal(size=(n * c, n * (n + 2))) * (n * c) ** -0.5,
          "b": rng.normal(size=(n * (n + 2),)), "alpha": np.array(
              [0.9, 0.6, 1.2])}
    w = rng.normal(size=(c, c)) * c ** -0.5
    hyper = dict(eps=1e-6, clamp=(-30.0, 30.0), iters=20)
    f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
    got, = arch.hyper_sublayer(
        f32(x), {k: f32(v) for k, v in hc.items()},
        lambda u: (jnp.tanh(u @ f32(w)),), **hyper)
    sig = lambda v: 1 / (1 + np.exp(-v))  # noqa: E731
    for tok in range(t):
        X = x[0, tok]
        vec = X.reshape(-1)
        z = (vec / np.sqrt(np.mean(vec ** 2) + 1e-6)) @ hc["phi"]
        a, b = hc["alpha"], hc["b"]
        h_pre = sig(a[0] * z[:n] + b[:n])
        h_post = 2 * sig(a[1] * z[n:2 * n] + b[n:2 * n])
        m = np.exp(np.clip(a[2] * z[2 * n:] + b[2 * n:], -30, 30)
                   ).reshape(n, n)
        for _ in range(20):
            m = m / (m.sum(axis=1, keepdims=True) + 1e-6)
            m = m / (m.sum(axis=0, keepdims=True) + 1e-6)
        assert np.abs(m.sum(axis=0) - 1).max() < 1e-5
        assert np.abs(m.sum(axis=1) - 1).max() < 5e-3
        y = np.tanh((h_pre @ X) @ w)
        np.testing.assert_allclose(
            got[0, tok], m @ X + h_post[:, None] * y[None], atol=2e-5)


def test_flops_match_the_hand_count():
    """ISSUE 56's count at sequence 8192 for layers 2 to 5: 20.2 TFLOP a
    step, 8.2 of attention pairs."""
    parts = arch.forward_flops_per_token(M, SEQ)
    d, n, nh = 3584, 4, 32
    assert parts["mla_projections"] == 4 * 2 * (
        d * 768 + 768 * nh * 192 + d * 576 + 512 * nh * 256 + nh * 128 * d)
    assert parts["mla_attention"] == 4 * 2 * 320 * nh * 8193 / 2
    assert parts["hyper_connections"] == 8 * (2 * n * d * 24 + 2 * 24 * d)
    assert parts["routed_layers"] == 4 * (
        2 * d * 64 + 6 * d * 1024 * (1 + 4 * 8 / 64))
    assert parts["dense_ffn"] == 0 and parts["head"] == 2 * d * 16384
    step = arch.train_flops_per_token(M, SEQ) * SEQ
    assert 20.0e12 < step < 20.4e12
    assert 8.2e12 < 3 * parts["mla_attention"] * SEQ < 8.3e12


def test_kernel_costs_match_the_hand_count():
    """One forward and one backward a sublayer, eight sublayers: X read
    once a pass; both stream passes are bound by their bytes, the flash
    kernels and the held experts by their products."""
    v5e = peaks.PEAKS["TPU v5 lite"]
    t, d = SEQ, 3584
    pre_f = arch.mhc_pre_call_cost(M, 1, SEQ, backward=False)
    pre_b = arch.mhc_pre_call_cost(M, 1, SEQ, backward=True)
    post_f = arch.mhc_post_call_cost(M, 1, SEQ, backward=False)
    post_b = arch.mhc_post_call_cost(M, 1, SEQ, backward=True)
    assert pre_f["bytes"] == 8 * (t * 5 * d * 2 + t * 24 * 4 + 4 * d * 24 * 2)
    assert pre_b["bytes"] == 8 * (t * 9 * d * 2 + t * 24 * 4
                                  + 4 * d * 24 * 6)
    assert post_f["bytes"] == 8 * (t * 9 * d * 2 + t * 20 * 4)
    assert post_b["bytes"] == 8 * (t * 14 * d * 2 + 2 * t * 20 * 4)
    for cost in (pre_f, pre_b, post_f, post_b):
        assert arch.least_seconds(cost, v5e)[1] == "memory"
    least = lambda fn: sum(arch.least_seconds(  # noqa: E731
        fn(M, 1, SEQ, backward=b), v5e)[0] for b in (False, True))
    assert 8.0e-3 < least(arch.mhc_pre_call_cost) < 8.2e-3
    assert 13.1e-3 < least(arch.mhc_post_call_cost) < 13.3e-3
    for fn in (arch.mla_flash_call_cost, arch.moe_call_cost):
        assert arch.least_seconds(fn(M, 1, SEQ, backward=True), v5e)[
            1] == "compute"
    assert arch.moe_call_cost(M, 1, SEQ, backward=False, rows=4096)[
        "flops"] == 4 * 4096 * 6 * d * 1024


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key,
    but for the keys ``reduced`` names; no width among them; the floors."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
         "vocab_size", "max_position_embeddings",
         "num_nextn_predict_layers"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
              "hc_mult")
    assert not set(widths) & set(entry["reduced"])
    # layers 2 to 5: four routed layers, an eighth of the experts and of
    # the vocabulary
    assert CFG["num_hidden_layers"] == 4 >= 4
    assert CFG["first_k_dense_replace"] == 0
    assert CFG["n_routed_experts"] == 8 >= 8
    assert CFG["num_routed_experts"] == row["config"]["n_routed_experts"]
    assert CFG["vocab_size"] * 8 == row["config"]["vocab_size"]
    tr = files.load_traffic("pretrain-s8k")
    assert (tr["seq_len"], tr["sequences_per_chip"]) == (SEQ, 1)
    # the engine of the other routed cells, at the LFM2 file's rate
    mine = CFG["program"]["ds_config"]
    assert mine == files.load_config(
        "lfm2-24b-ep8-zero3-1chip")["program"]["ds_config"]
    assert set(arch.CHECK_KEYS) <= set(CFG["check"])
    assert all(key in CFG or key in arch.OPTIONAL for key in arch.WIDTHS)


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
    assert {"mfu.train", "held_expert_tokens.routed",
            "mhc_sinkhorn_residual.mhc", "setup_init_s.train"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    # 128 tokens x top-4 of 64: 8 rows a held expert if balanced
    assert 3 < line["metrics"]["held_expert_tokens.routed"]["value"] < 16
    assert 0 < line["metrics"]["mhc_sinkhorn_residual.mhc"]["value"] < 1e-2


def test_the_control_judges_the_program_and_each_planted_fault():
    """``tests/mhc_control.py`` at the tiny widths: the program passes the
    configuration's ``check`` with and without a res logit planted past
    the clamp, the dropped clamp does not (``tests/test_xing4_limits.py``
    of the program's own tests plants all eight under boosted weights,
    where every one is seen)."""
    import cpu_rig
    import mhc_control as control
    out = control.mhc_control(CELL, 5600000019, cpu_rig.RIG)
    assert out["program"]["correct"] is True, out
    assert out["program_with_a_logit_past_the_clamp"]["correct"] is True
    assert out["clamp_dropped"]["correct"] is False
    assert set(control.FAULTS) <= set(out)
