"""Architecture ``qwen3_next`` (PR 46): its reference against an
independent token-by-token form, its counts against a hand count, the
configuration file against the catalog's published numbers, the cell end
to end on the CPU at the tiny preset, traced and untraced, and the control
of what the family adds. Run by hand with the rest of the benchmark's tests:

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

from architectures import qwen3_next as arch       # noqa: E402
from lib import files, peaks                       # noqa: E402
from test_benchmark import _run_rig                # noqa: E402

CELL = "train-gdn-s16k-1chip"
NAME = "qwen3-next-80b-ep16-zero3-1chip"
CFG = files.load_config(NAME)
M = {k: CFG[k] for k in arch.WIDTHS}
SEQ = 16384


def test_the_mixers_are_the_equations_token_by_token():
    """The reference's two mixers against forms written again from the
    equations with loops over tokens and heads in numpy float64: the
    Gated DeltaNet layer (convolution, l2 norms, a key head serving two
    value heads, the state's decay, correction and read, the gated norm)
    and the gated attention layer (a head's query and gate halves, the
    (1 + w) norm, a quarter of the head rotated by pairs (i, i + r / 2),
    causal softmax, 2 query heads a key head, the sigmoid gate)."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    s, d, hk, hv, dk, dv = 12, 16, 2, 4, 4, 4
    kw, vw = hk * dk, hv * dv
    p = {"w_qkvz": rng.normal(size=(d, 2 * kw + 2 * vw)),
         "w_ba": rng.normal(size=(d, 2 * hv)),
         "conv": rng.uniform(-0.5, 0.5, size=(4, 2 * kw + vw)),
         "A_log": np.log(rng.uniform(0.1, 4, size=hv)),
         "dt_bias": np.ones(hv), "o_norm": rng.normal(size=dv) + 1,
         "wo": rng.normal(size=(vw, d))}
    h = rng.normal(size=(1, s, d))
    silu = lambda x: x / (1 + np.exp(-x))   # noqa: E731
    qkvz = h[0] @ p["w_qkvz"]
    pad = np.concatenate([np.zeros((3, 2 * kw + vw)), qkvz[:, :2 * kw + vw]])
    qkv = silu(sum(pad[i:i + s] * p["conv"][i] for i in range(4)))
    ba = h[0] @ p["w_ba"]
    want = np.zeros((s, vw))
    for head in range(hv):
        j = head // (hv // hk)
        state = np.zeros((dk, dv))
        for t in range(s):
            q = qkv[t, j * dk:(j + 1) * dk]
            k = qkv[t, kw + j * dk:kw + (j + 1) * dk]
            q = q / np.sqrt((q * q).sum() + 1e-6) / np.sqrt(dk)
            k = k / np.sqrt((k * k).sum() + 1e-6)
            v = qkv[t, 2 * kw + head * dv:2 * kw + (head + 1) * dv]
            z = qkvz[t, 2 * kw + vw + head * dv:2 * kw + vw + (head + 1) * dv]
            beta = 1 / (1 + np.exp(-ba[t, head]))
            g = -np.exp(p["A_log"][head]) * np.log1p(
                np.exp(ba[t, hv + head] + 1.0))
            state = np.exp(g) * state
            state = state + beta * np.outer(k, v - state.T @ k)
            o = state.T @ q
            o = o / np.sqrt((o * o).mean() + 1e-6) * p["o_norm"]
            want[t, head * dv:(head + 1) * dv] = o * silu(z)
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.asarray(x, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        got = arch.gdn_mixer(f32(p), f32(h), hk=hk, hv=hv, dk=dk, dv=dv,
                             eps=1e-6)
    np.testing.assert_allclose(got[0], want @ p["wo"], rtol=2e-4, atol=2e-4)

    heads, kv, hd, rot, theta = 4, 2, 8, 4, 100.0
    a = {"wq": rng.normal(size=(d, heads * 2 * hd)),
         "wk": rng.normal(size=(d, kv * hd)),
         "wv": rng.normal(size=(d, kv * hd)),
         "q_norm": rng.normal(size=hd) * 0.3,
         "k_norm": rng.normal(size=hd) * 0.3,
         "wo": rng.normal(size=(heads * hd, d))}

    def normed(x, w):
        return x / np.sqrt((x * x).mean() + 1e-6) * (1 + w)

    def rotated(x, t):
        y = x.copy()
        for i in range(rot // 2):
            ang = t * theta ** (-2 * i / rot)
            y[i] = x[i] * np.cos(ang) - x[i + rot // 2] * np.sin(ang)
            y[i + rot // 2] = (x[i + rot // 2] * np.cos(ang)
                               + x[i] * np.sin(ang))
        return y

    qg, k_all, v_all = h[0] @ a["wq"], h[0] @ a["wk"], h[0] @ a["wv"]
    want = np.zeros((s, heads * hd))
    for head in range(heads):
        j = head // (heads // kv)
        for t in range(s):
            q = rotated(normed(qg[t, head * 2 * hd:head * 2 * hd + hd],
                               a["q_norm"]), t)
            gate = qg[t, head * 2 * hd + hd:(head + 1) * 2 * hd]
            keys = np.stack([rotated(normed(
                k_all[u, j * hd:(j + 1) * hd], a["k_norm"]), u)
                for u in range(t + 1)])
            scores = keys @ q / np.sqrt(hd)
            w = np.exp(scores - scores.max())
            w = w / w.sum()
            out = w @ v_all[:t + 1, j * hd:(j + 1) * hd]
            want[t, head * hd:(head + 1) * hd] = out / (1 + np.exp(-gate))
    with jax.default_matmul_precision("highest"):
        got = arch.gated_attention(f32(a), f32(h), heads=heads, kv_heads=kv,
                                   hd=hd, rot=rot, theta=theta, eps=1e-6)
    np.testing.assert_allclose(got[0], want @ a["wo"], rtol=2e-4, atol=2e-4)


def test_flops_match_the_hand_count():
    """ISSUE 46's parts, per token forward: three Gated DeltaNet layers'
    projections (2048 -> 12288 + 64, 4096 -> 2048) and convolution, the
    recurrence's three products a value head, the attention layer's
    projections (a query twice as wide) and its live pairs at 16 heads of
    256, the router's 512 outputs, the gated shared expert, a token's
    held share of its 10 experts of 512 (0.625 of one), the head over
    18992 rows."""
    f = arch.forward_flops_per_token(M, SEQ)
    assert arch.layer_kinds(M) == ["linear_attention"] * 3 + [
        "full_attention"]
    assert f["gdn_projections"] == 3 * (
        2 * (2048 * 12288 + 2048 * 64 + 4096 * 2048) + 2 * 4 * 8192)
    assert f["gdn_state"] == 3 * 6 * 32 * 128 * 128
    assert f["attn_projections"] == 2 * (2048 * 8192 + 2 * 2048 * 512
                                         + 4096 * 2048)
    assert f["attention"] == 4 * 256 * 16 * (SEQ + 1) / 2
    assert f["router"] == 4 * 2 * 2048 * 512
    assert f["shared_expert"] == 4 * (2 * 3 * 2048 * 512 + 2 * 2048)
    assert arch.held_share(M) == 10 * 32 / 512 == 0.625
    assert f["held_experts"] == 4 * 2 * 3145728 * 0.625
    assert f["head"] == 2 * 2048 * 18992
    assert f["total"] == sum(v for k, v in f.items() if k != "total")
    per_step = arch.train_flops_per_token(M, SEQ) * SEQ
    # GDN projections 9.91, state 0.46, attention projections 2.68, pairs
    # 6.60, router 0.41, shared 1.24, held experts 0.77, head 3.82 T
    assert abs(per_step / 1e12 - 25.93) < 0.01


def test_kernel_costs_match_the_hand_count():
    v5e = peaks.peak("TPU v5 lite")
    rows = SEQ * 32                         # one sequence, 32 value heads
    # the scan, 3 layers: q, k, v bf16 at 128, the gate and beta a number
    # a head and token, o out; memory-bound, 0.66 ms a layer forward
    fwd = arch.gdn_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.gdn_call_cost(M, 1, SEQ, backward=True)
    reads = rows * (3 * 128 * 2 + 4 + 4)
    assert fwd == {"flops": 3 * 6 * 128 * 128 * rows,
                   "bytes": 3 * (reads + rows * 128 * 2)}
    assert bwd == {"flops": 2 * fwd["flops"],
                   "bytes": 3 * (2 * reads + rows * 128 * 2)}
    t, bound = arch.least_seconds(fwd, v5e)
    assert bound == "memory" and abs(t * 1e3 / 3 - 0.661) < 0.005
    both = sum(arch.least_seconds(c, v5e)[0] for c in (fwd, bwd))
    assert abs(both * 1e3 - 5.45) < 0.02            # ms a step
    # flash at 256, 1 layer, 16 query heads on 2 key heads, full causal
    pairs = 16 * SEQ * (SEQ + 1) // 2
    fwd = arch.gattn_flash_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.gattn_flash_call_cost(M, 1, SEQ, backward=True)
    q_like, kv_like = SEQ * 16 * 256 * 2, SEQ * 2 * 256 * 2
    assert fwd == {"flops": 4 * 256 * pairs,
                   "bytes": 2 * q_like + 2 * kv_like + SEQ * 16 * 4}
    assert bwd == {"flops": 10 * 256 * pairs,
                   "bytes": 4 * q_like + 4 * kv_like + SEQ * 16 * 4}
    both = [arch.least_seconds(c, v5e) for c in (fwd, bwd)]
    assert [b for _, b in both] == ["compute", "compute"]
    assert abs(1e3 * sum(t for t, _ in both) - 39.07) < 0.05
    # held experts, 4 layers: 16384 x 10 x 32 / 512 = 10240 rows a layer
    fwd = arch.moe_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.moe_call_cost(M, 1, SEQ, backward=True)
    weights = 32 * 3145728
    assert fwd == {"flops": 4 * 10240 * 6 * 2048 * 512,
                   "bytes": 4 * (weights * 2 + 2 * 10240 * 2048 * 2)}
    assert bwd["flops"] == 2 * fwd["flops"]
    assert bwd["bytes"] == fwd["bytes"] + 4 * (weights * 4
                                               + 10240 * 2048 * 2)
    assert arch.least_seconds(fwd, v5e)[1] == "memory"
    counted = arch.moe_call_cost(M, 1, SEQ, backward=False, rows=9000.0)
    assert counted["flops"] == 4 * 9000 * 6 * 2048 * 512


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key,
    but for the keys ``reduced`` names; no width among them; the floors."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size",
         "max_position_embeddings"])
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
    # one whole period at the published 3 : 1, a sixteenth of the experts,
    # an eighth of the vocabulary; the router keeps its width
    assert CFG["num_hidden_layers"] == CFG["full_attention_interval"] == 4
    assert CFG["num_experts"] == 32 >= 8
    assert CFG["num_routed_experts"] == row["config"]["num_experts"] == 512
    assert CFG["vocab_size"] * 8 == row["config"]["vocab_size"]
    # the engine of the other routed cells, at the Mellum file's rate
    mine = CFG["program"]["ds_config"]
    assert mine == files.load_config(
        "mellum2-12b-ep4-zero3-1chip")["program"]["ds_config"]
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
    assert {"mfu.train", "held_expert_tokens.routed",
            "moe_pad_share.routed"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    # 128 tokens x top-10 of 512: 2.5 rows a held expert if balanced
    assert 0.5 < line["metrics"]["held_expert_tokens.routed"]["value"] < 8
    assert 0.0 < line["metrics"]["moe_pad_share.routed"]["value"] < 100.0


def test_the_control_sees_each_planted_fault():
    """``tests/gdn_control.py`` at the tiny widths: the program passes the
    configuration's ``check``; the faults the tiny widths let the tail
    logits see do not (``tests/test_qwen3_next.py`` of the program's own
    tests plants all five under boosted weights)."""
    import cpu_rig
    import gdn_control as control
    out = control.gdn_control(CELL, 4600000019, cpu_rig.RIG)
    assert out["program"]["correct"] is True, out
    assert set(control.FAULTS) <= set(out)
