"""Architecture ``lfm2_moe`` (PR 54): its reference against an independent
token-by-token form, its counts against a hand count, the configuration
file against the catalog's published numbers, the cell end to end on the
CPU at the tiny preset, traced and untraced, and the control of what the
family adds. Run by hand with the rest of the benchmark's tests:

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

from architectures import lfm2_moe as arch          # noqa: E402
from lib import files, peaks                        # noqa: E402
from test_benchmark import _run_rig                 # noqa: E402

CELL = "train-conv-s8k-1chip"
NAME = "lfm2-24b-ep8-zero3-1chip"
CFG = files.load_config(NAME)
M = {k: CFG[k] for k in arch.WIDTHS}
SEQ, BATCH = 8192, 2


def test_the_mixers_are_the_equations_token_by_token():
    """The reference's parts against forms written again from the
    equations with loops over tokens, heads and channels in numpy
    float64: the gated short convolution (three column runs in the order
    B, Cg, X, three taps with zeros before the start, no activation), the
    attention layer (plain-w QK-norm, the whole head rotated by pairs
    (i, i + D / 2) after the norm, causal softmax, 2 query heads a key
    head) and the routed layer (sigmoid scores, top 2 of scores + bias,
    the scores over their sum + 1e-6, the held experts alone)."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.asarray(x, jnp.float32), t)
    s, c, n = 9, 6, 3
    p = {"w_in": rng.normal(size=(c, 3 * c)),
         "taps": rng.uniform(-0.5, 0.5, size=(n, c)),
         "w_out": rng.normal(size=(c, c))}
    h = rng.normal(size=(1, s, c))
    bcx = h[0] @ p["w_in"]
    want = np.zeros((s, c))
    for t in range(s):
        for ch in range(c):
            conv = sum(p["taps"][i, ch] * bcx[t - (n - 1) + i, ch]
                       * bcx[t - (n - 1) + i, 2 * c + ch]
                       for i in range(n) if t - (n - 1) + i >= 0)
            want[t, ch] = bcx[t, c + ch] * conv
    with jax.default_matmul_precision("highest"):
        got = arch.gated_conv(f32(p), f32(h))
    np.testing.assert_allclose(got[0], want @ p["w_out"], rtol=2e-4,
                               atol=2e-4)

    d, heads, kv, theta = 16, 4, 2, 100.0
    hd = d // heads
    a = {"wq": rng.normal(size=(d, heads * hd)),
         "wk": rng.normal(size=(d, kv * hd)),
         "wv": rng.normal(size=(d, kv * hd)),
         "q_norm": rng.normal(size=hd) * 0.3 + 1,
         "k_norm": rng.normal(size=hd) * 0.3 + 1,
         "wo": rng.normal(size=(heads * hd, d))}
    h = rng.normal(size=(1, s, d))

    def normed(x, w):
        return x / np.sqrt((x * x).mean() + 1e-5) * w

    def rotated(x, t):
        y = x.copy()
        for i in range(hd // 2):
            ang = t * theta ** (-2 * i / hd)
            y[i] = x[i] * np.cos(ang) - x[i + hd // 2] * np.sin(ang)
            y[i + hd // 2] = x[i + hd // 2] * np.cos(ang) + x[i] * np.sin(ang)
        return y

    q_all, k_all, v_all = h[0] @ a["wq"], h[0] @ a["wk"], h[0] @ a["wv"]
    want = np.zeros((s, heads * hd))
    for head in range(heads):
        j = head // (heads // kv)
        for t in range(s):
            q = rotated(normed(q_all[t, head * hd:(head + 1) * hd],
                               a["q_norm"]), t)
            keys = np.stack([rotated(normed(
                k_all[u, j * hd:(j + 1) * hd], a["k_norm"]), u)
                for u in range(t + 1)])
            scores = keys @ q / np.sqrt(hd)
            w = np.exp(scores - scores.max())
            want[t, head * hd:(head + 1) * hd] = (
                w / w.sum()) @ v_all[:t + 1, j * hd:(j + 1) * hd]
    with jax.default_matmul_precision("highest"):
        got = arch.attention_mixer(f32(a), f32(h), heads=heads, kv_heads=kv,
                                   theta=theta, eps=1e-5)
    np.testing.assert_allclose(got[0], want @ a["wo"], rtol=2e-4, atol=2e-4)

    e, held, k, f = 8, 3, 2, 5
    r = {"router": rng.normal(size=(d, e)), "router_bias": rng.normal(
            size=e) * 0.2,
         "experts": {"w_gate": rng.normal(size=(held, d, f)),
                     "w_up": rng.normal(size=(held, d, f)),
                     "w_down": rng.normal(size=(held, f, d))}}
    x = rng.normal(size=(s, d))
    want = np.zeros((s, d))
    silu = lambda v: v / (1 + np.exp(-v))   # noqa: E731
    for t in range(s):
        scores = 1 / (1 + np.exp(-(x[t] @ r["router"])))
        chosen = np.argsort(-(scores + r["router_bias"]))[:k]
        total = scores[chosen].sum() + 1e-6
        for j in chosen:
            if j < held:
                ex = r["experts"]
                want[t] += scores[j] / total * (
                    (silu(x[t] @ ex["w_gate"][j]) * (x[t] @ ex["w_up"][j]))
                    @ ex["w_down"][j])
    with jax.default_matmul_precision("highest"):
        got, dist, rms = arch.routed(f32(r), f32(x), top_k=k, first=0,
                                     renormalise=True, scaling=1.0)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert dist.shape == (s,) and float(rms) > 0


def test_flops_match_the_hand_count():
    """ISSUE 54's parts, per token forward: four conv mixers' projections
    (2048 -> 6144, 2048 -> 2048) and their eight products a channel, the
    attention layer's projections and its live pairs at 32 heads of 64,
    the leading layer's SwiGLU of 11776, the four routers' 64 outputs, a
    token's held share of its 4 experts of 1536 (0.5 of one), the tied
    head over 8192 rows."""
    f = arch.forward_flops_per_token(M, SEQ)
    assert arch.layer_kinds(M) == [("conv", "dense"), ("attn", "moe")] + [
        ("conv", "moe")] * 3
    assert f["conv_projections"] == 4 * 2 * (2048 * 6144 + 2048 * 2048)
    assert f["conv_mix"] == 4 * 8 * 2048
    assert f["attn_projections"] == 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    assert f["attention"] == 4 * 64 * 32 * (SEQ + 1) / 2
    assert f["dense_ffn"] == 2 * 3 * 2048 * 11776
    assert f["router"] == 4 * 2 * 2048 * 64
    assert arch.held_share(M) == 4 * 8 / 64 == 0.5
    assert f["held_experts"] == 4 * 2 * 9437184 * 0.5
    assert f["head"] == 2 * 2048 * 8192
    assert f["total"] == sum(v for k, v in f.items() if k != "total")
    per_step = arch.train_flops_per_token(M, SEQ) * BATCH * SEQ
    # conv projections 6.60, dense 7.11, held experts 1.86, pairs 1.65,
    # head 1.65, attention projections 1.03, router 0.05 T
    assert abs(per_step / 1e12 - 19.95) < 0.01


def test_kernel_costs_match_the_hand_count():
    v5e = peaks.peak("TPU v5 lite")
    tokens = BATCH * SEQ
    # the gated convolution, 4 layers: B, Cg, X in and y out forward (16
    # KiB a token), those and dy in and three gradients out backward (28)
    fwd = arch.gated_conv_call_cost(M, BATCH, SEQ, backward=False)
    bwd = arch.gated_conv_call_cost(M, BATCH, SEQ, backward=True)
    assert fwd == {"flops": 4 * tokens * 2048 * 8,
                   "bytes": 4 * (tokens * 16384 + 3 * 2048 * 4)}
    assert bwd == {"flops": 4 * tokens * 2048 * 26,
                   "bytes": 4 * (tokens * 28672 + 2 * 3 * 2048 * 4)}
    both = [arch.least_seconds(c, v5e) for c in (fwd, bwd)]
    assert [b for _, b in both] == ["memory", "memory"]
    assert abs(1e3 * both[0][0] / 4 - 0.328) < 0.002    # ms a layer
    assert abs(1e3 * both[1][0] / 4 - 0.574) < 0.002
    # flash at 64, 1 layer, 32 query heads on 8 key heads, two sequences
    pairs = BATCH * 32 * SEQ * (SEQ + 1) // 2
    fwd = arch.flash_call_cost(M, BATCH, SEQ, backward=False)
    bwd = arch.flash_call_cost(M, BATCH, SEQ, backward=True)
    q_like, kv_like = tokens * 32 * 64 * 2, tokens * 8 * 64 * 2
    assert fwd == {"flops": 4 * 64 * pairs,
                   "bytes": 2 * q_like + 2 * kv_like + tokens * 32 * 4}
    assert bwd == {"flops": 10 * 64 * pairs,
                   "bytes": 4 * q_like + 4 * kv_like + tokens * 32 * 4}
    both = [arch.least_seconds(c, v5e) for c in (fwd, bwd)]
    assert [b for _, b in both] == ["compute", "compute"]
    assert abs(1e3 * sum(t for t, _ in both) - 9.77) < 0.02
    # held experts, 4 layers: 16384 x 4 x 8 / 64 = 8192 rows a layer; nine
    # matmul units a row (3 forward, 6 backward: the backward rule's rerun
    # of gate and up is not counted; one count in every routed module
    # since PR 63)
    fwd = arch.moe_call_cost(M, BATCH, SEQ, backward=False)
    bwd = arch.moe_call_cost(M, BATCH, SEQ, backward=True)
    weights, unit = 8 * 9437184, 8192 * 2 * 2048 * 1536
    assert fwd == {"flops": 4 * 3 * unit,
                   "bytes": 4 * (weights * 2 + 2 * 8192 * 2048 * 2)}
    assert bwd["flops"] == 4 * 6 * unit
    assert bwd["bytes"] == fwd["bytes"] + 4 * (weights * 4
                                               + 8192 * 2048 * 2)
    assert arch.least_seconds(fwd, v5e)[1] == "compute"
    counted = arch.moe_call_cost(M, BATCH, SEQ, backward=False, rows=9000.0)
    assert counted["flops"] == 4 * 9000 * 6 * 2048 * 1536


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key,
    but for the keys ``reduced`` names; no width among them; the floors."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "num_dense_layers",
         "num_experts", "vocab_size", "max_position_embeddings"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    # layers 1 to 5 of the published list: one leading dense layer and a
    # whole period at 1 : 3; an eighth of the experts and of the vocabulary
    assert CFG["layer_types"] == row["config"]["layer_types"][1:6]
    assert CFG["num_hidden_layers"] == 5 and CFG["num_dense_layers"] == 1
    assert CFG["num_experts"] == 8 >= 8
    assert CFG["num_routed_experts"] == row["config"]["num_experts"] == 64
    assert CFG["vocab_size"] * 8 == row["config"]["vocab_size"]
    tr = files.load_traffic("pretrain-s8k-b2")
    assert (tr["seq_len"], tr["sequences_per_chip"]) == (SEQ, BATCH)
    assert CFG["program"]["sequences_per_chip"] == BATCH
    # the engine of the other routed cells, at a tenth of the Mellum file's
    # rate (at 2e-5 this stack learns the pool of 8 batches by heart
    # inside a window: ``assumed.optimizer``)
    mine = json.loads(json.dumps(CFG["program"]["ds_config"]))
    theirs = files.load_config(
        "mellum2-12b-ep4-zero3-1chip")["program"]["ds_config"]
    assert mine["optimizer"]["params"].pop("lr") == 2e-6
    assert theirs["optimizer"]["params"].pop("lr") == 2e-5
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
    assert {"mfu.train", "held_expert_tokens.routed", "moe_pad_share.routed",
            "setup_init_s.train"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    # 2 x 128 tokens x top-4 of 64: 16 rows a held expert if balanced
    assert 6 < line["metrics"]["held_expert_tokens.routed"]["value"] < 32
    assert 0.0 < line["metrics"]["moe_pad_share.routed"]["value"] < 100.0


def test_the_control_sees_each_planted_fault():
    """``tests/lfm_control.py`` at the tiny widths: the program passes the
    configuration's ``check`` (``tests/test_lfm2_moe.py`` of the program's
    own tests plants all nine under boosted weights, where every one is
    seen: at the tiny preset's own init a layer of hidden 64 adds a
    hundredth of the embedding to the hidden state)."""
    import cpu_rig
    import lfm_control as control
    out = control.lfm_control(CELL, 5400000019, cpu_rig.RIG)
    assert out["program"]["correct"] is True, out
    assert set(control.FAULTS) <= set(out)
    assert set(out["margins"]) == {str(m) for m in control.MARGINS}
