"""Architecture ``nemotron_h`` (PR 66): its reference's grouped Mamba-2
mixer and latent routed layer against independent forms in numpy, its
counts against a hand count, the configuration file against the catalog's
published numbers, the cell end to end on the CPU at the tiny preset,
traced and untraced, the control of what the family adds, and that what
the PR adds to the benchmark is files beside the accepted ones, none of
which changed. Run by hand with the rest of the benchmark's tests:

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

from architectures import nemotron_h as arch        # noqa: E402
from lib import files, peaks                        # noqa: E402
from test_benchmark import _run_rig                 # noqa: E402

CELL = "train-lmoe-s8k-1chip"
NAME = "nemotron-3-super-120b-ep64-zero3-1chip"
CFG = files.load_config(NAME)
M = {k: CFG[k] for k in arch.WIDTHS if k in CFG}
M["num_experts"] = CFG["n_routed_experts"]
SEQ = 8192
# the commit this PR was written on: what `benchmark/` held before it
PARENT = "fb089fdc07305837ca282282c27517dc649eabe6"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "mamba_num_heads",
           "n_groups", "num_attention_heads", "num_key_value_heads",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers",
           "max_position_embeddings"]


def test_the_grouped_mixer_and_the_latent_layer_in_numpy():
    """``mamba_mixer`` at two groups against the recurrence written out in
    float64 (a head reads ITS group's B and C; the gated norm takes its
    mean of squares a group), and ``routed`` against every token's own sum
    over its chosen held experts, through the latent and back."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    b, s, d, nh, hd, g, n = 1, 24, 16, 4, 4, 2, 8
    inner, gn = nh * hd, g * n
    p = {"w_in": rng.normal(size=(d, 2 * inner + 2 * gn + nh)) * 0.3,
         "conv_w": rng.normal(size=(4, inner + 2 * gn)) * 0.4,
         "conv_b": rng.normal(size=(inner + 2 * gn,)) * 0.2,
         "dt_bias": rng.normal(size=(nh,)), "D": rng.normal(size=(nh,)),
         "A_log": np.log(rng.uniform(1, 4, nh)),
         "norm": 1 + 0.3 * rng.normal(size=(inner,)),
         "w_out": rng.normal(size=(inner, d)) * 0.2}
    h = rng.normal(size=(b, s, d))
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda v: jnp.asarray(v, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(arch.mamba_mixer(
            f32(p), f32(h), heads=nh, head_dim=hd, groups=g, state=n,
            eps=1e-5))
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    proj = h @ p["w_in"]
    z, xbc, dt = np.split(proj, [inner, 2 * inner + 2 * gn], axis=-1)
    pad = np.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    xbc = silu(sum(pad[:, i:i + s] * p["conv_w"][i] for i in range(4))
               + p["conv_b"])
    x = xbc[..., :inner].reshape(b, s, nh, hd)
    B = xbc[..., inner:inner + gn].reshape(b, s, g, n)
    C = xbc[..., inner + gn:].reshape(b, s, g, n)
    dt = np.log1p(np.exp(dt + p["dt_bias"]))
    y = np.zeros((b, s, nh, hd))
    for head in range(nh):
        grp, state = head // (nh // g), np.zeros((hd, n))
        for t in range(s):
            step = dt[0, t, head]
            state = (np.exp(-step * np.exp(p["A_log"][head])) * state
                     + step * np.outer(x[0, t, head], B[0, t, grp]))
            y[0, t, head] = state @ C[0, t, grp] + p["D"][head] * x[0, t, head]
    y = y.reshape(b, s, inner) * silu(z)
    y = y.reshape(b, s, g, inner // g)
    y = y / np.sqrt((y ** 2).mean(-1, keepdims=True) + 1e-5)
    want = (y.reshape(b, s, inner) * p["norm"]) @ p["w_out"]
    assert np.allclose(got, want, atol=2e-5), np.abs(got - want).max()

    t, d, lat, f, e, k, first, held = 40, 16, 8, 12, 32, 5, 8, 4
    q = {"router": rng.normal(size=(d, e)), "router_bias":
         rng.normal(size=(e,)) * 0.1,
         "latent": {"w_dn": rng.normal(size=(d, lat)) * 0.4,
                    "w_up": rng.normal(size=(lat, d)) * 0.4},
         "experts": {"w_up": rng.normal(size=(held, lat, f)) * 0.4,
                     "w_down": rng.normal(size=(held, f, lat)) * 0.4},
         "shared": {"w_up": rng.normal(size=(d, 2 * f)) * 0.3,
                    "w_down": rng.normal(size=(2 * f, d)) * 0.3}}
    x = rng.normal(size=(t, d))
    with jax.default_matmul_precision("highest"):
        got, dist, rms = arch.routed(f32(q), f32(x), top_k=k, first=first,
                                     renormalise=True, scaling=5)
    relu2 = lambda v: np.maximum(v, 0) ** 2  # noqa: E731
    scores = 1 / (1 + np.exp(-(x @ q["router"])))
    select = scores + q["router_bias"]
    want = relu2(x @ q["shared"]["w_up"]) @ q["shared"]["w_down"]
    for tok in range(t):
        chosen = np.argsort(-select[tok])[:k]
        total = scores[tok, chosen].sum() + 1e-20
        u, r = x[tok] @ q["latent"]["w_dn"], np.zeros(lat)
        for j in chosen:
            if first <= j < first + held:
                r = r + 5 * scores[tok, j] / total * (
                    relu2(u @ q["experts"]["w_up"][j - first])
                    @ q["experts"]["w_down"][j - first])
        want[tok] += r @ q["latent"]["w_up"]
    assert np.allclose(np.asarray(got), want, atol=5e-5)
    assert np.isclose(float(rms), np.sqrt((select ** 2).mean()), rtol=1e-5)
    assert dist.shape == (t,) and float(dist.min()) >= 0


def test_flops_match_the_hand_count():
    """ISSUE 66's count at sequence 8192 for layers 26 to 36: about 1.0
    GFLOP a token forward, the shared experts 44% of it, the held experts
    2%; a rematted step about 33 TFLOP."""
    parts = arch.forward_flops_per_token(M, SEQ)
    d = 4096
    assert parts["mamba_projections"] == 5 * (
        2 * (d * 4640 + 2048 * d) + 2 * 4 * 2560)
    assert parts["ssd_state"] == 5 * 4 * 32 * 64 * 128
    assert parts["attention_projections"] == 2 * (2 * d * 1024 + 2 * d * 128)
    assert parts["attention"] == 4 * 128 * 8 * (SEQ + 1) / 2
    assert parts["router_and_latent"] == 5 * 2 * (d * 512 + 2 * d * 1024)
    assert parts["shared_experts"] == 5 * 4 * d * 5376
    assert parts["held_experts"] == 5 * 4 * 1024 * 2688 * 22 * 8 / 512
    assert parts["head"] == 2 * d * 16384
    total = parts["total"]
    assert 1.00e9 < total < 1.03e9
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert shares["shared_experts"] == 43 and shares["head"] == 13
    assert shares["held_experts"] == 2 and shares["router_and_latent"] == 10
    assert 32e12 < 4 * total * SEQ < 34e12          # with remat's rerun
    assert arch.train_flops_per_token(M, SEQ) == 3 * total


def test_kernel_costs_match_the_hand_count():
    """The held experts at SIX matmul units a row of 1024 x 2688 (memory
    bound at a balanced router's 352 rows an expert: the weights and their
    float32 gradients outweigh the rows); the scan at 32 heads in 2 groups
    (memory bound); the flash calls at 8 query heads on one key-value head
    of 128 (compute bound)."""
    v5e = peaks.PEAKS["TPU v5 lite"]
    rows = 2816
    f = arch.moe_call_cost(M, 1, SEQ, backward=False, rows=rows)
    b = arch.moe_call_cost(M, 1, SEQ, backward=True, rows=rows)
    assert f["flops"] == 5 * rows * 2 * 2 * 1024 * 2688
    assert b["flops"] == 2 * f["flops"]             # 2 + 4 = six units
    weights = 8 * 2 * 1024 * 2688
    assert f["bytes"] == 5 * (weights * 2 + 2 * rows * 1024 * 2)
    assert b["bytes"] == 5 * (weights * 6 + 3 * rows * 1024 * 2)
    assert arch.held_share(M) == 22 * 8 / 512 and SEQ * 22 / 512 == 352
    assert arch.moe_call_cost(M, 1, SEQ, backward=False)["flops"] == f["flops"]
    assert arch.least_seconds(b, v5e)[1] == "memory"
    ssd = arch.ssd_call_cost(M, 1, SEQ, backward=False)
    assert ssd["flops"] == 5 * 4 * 32 * 64 * 128 * SEQ
    assert ssd["bytes"] == 5 * SEQ * (32 * (2 * 64 * 2 + 4) + 2 * 2 * 128 * 2)
    assert arch.least_seconds(ssd, v5e)[1] == "memory"
    pairs = 8 * SEQ * (SEQ + 1) // 2
    fwd = arch.flash_call_cost(M, 1, SEQ, backward=False)
    bwd = arch.flash_call_cost(M, 1, SEQ, backward=True)
    assert fwd["flops"] == 2 * 2 * 128 * pairs
    assert bwd["flops"] == 5 * 2 * 128 * pairs
    assert fwd["bytes"] == SEQ * ((2 * 8 + 2 * 1) * 128 * 2 + 8 * 4)
    for cost in (fwd, bwd):
        assert arch.least_seconds(cost, v5e)[1] == "compute"


def test_configuration_holds_the_published_numbers():
    """Every number of the catalog row's ``config`` under its own key,
    but for the keys ``reduced`` names; no width among them; the floors: a
    whole period, 8 experts a routed layer, an eighth of the vocabulary."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert CFG["source"] == row["source_url"]
    entry = next(c for c in files.benchmark_json()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == sorted(
        REDUCED)
    for key, value in row["config"].items():
        assert key in CFG, key
        if key not in entry["reduced"]:
            assert CFG[key] == value, key
        else:
            assert CFG["reduced"][key]["published"] == value, key
            assert CFG["reduced"][key]["here"] == CFG[key], key
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "moe_latent_size", "moe_shared_expert_intermediate_size",
              "mamba_head_dim", "ssm_state_size", "head_dim", "expand",
              "num_experts_per_tok", "conv_kernel", "chunk_size")
    assert not set(widths) & set(entry["reduced"])
    published = row["config"]["hybrid_override_pattern"]
    assert published[26:37] == CFG["hybrid_override_pattern"]
    assert len(CFG["hybrid_override_pattern"]) == CFG["num_hidden_layers"]
    # one period holds the three kinds in the published 5 : 5 : 1
    assert [published.count(ch) for ch in "ME*"] == [40, 40, 8]
    assert [CFG["hybrid_override_pattern"].count(ch) for ch in "ME*"] == [
        5, 5, 1]
    # a quarter of each mixer's heads, with the groups and the key-value
    # head they read
    assert CFG["mamba_num_heads"] * 4 == row["config"]["mamba_num_heads"]
    assert CFG["n_groups"] * 4 == row["config"]["n_groups"]
    assert CFG["num_attention_heads"] * 4 == row["config"][
        "num_attention_heads"]
    assert CFG["num_key_value_heads"] == 1
    assert CFG["n_routed_experts"] == 8
    assert CFG["num_routed_experts"] == row["config"]["n_routed_experts"]
    assert CFG["vocab_size"] * 8 == row["config"]["vocab_size"]
    tr = files.load_traffic("pretrain-s8k")
    assert (tr["seq_len"], tr["sequences_per_chip"]) == (SEQ, 1)
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
    assert {"mfu.nem", "held_expert_tokens.nem", "moe_pad_share.nem",
            "setup_init_s.nem"} <= got
    assert got <= set(files.load_cell(CELL)["per_layer"])
    # 128 tokens x top-22 of 512: 5.5 rows a held expert if balanced
    assert 2 < line["metrics"]["held_expert_tokens.nem"]["value"] < 12


def test_the_control_judges_the_program_and_each_planted_fault():
    """``tests/nemotron_control.py`` at the tiny widths: the program passes
    the configuration's ``check`` at the seed's weights and at each rule of
    larger ones (``tests/test_nemotron_h.py`` of the program's own tests
    plants all nine under boosted weights, where every one is seen; at the
    init's own scale the tiny model cannot show them all)."""
    import cpu_rig
    import nemotron_control as control
    out = control.nemotron_control(CELL, 6600000019, cpu_rig.RIG)
    rights = [k for k in out if k.startswith("program")
              and "margins" not in k]
    assert len(rights) == 3 and all(out[k]["correct"] for k in rights), out
    assert set(control.FAULTS) <= set(out)


def test_no_accepted_benchmark_file_changed():
    """What an adding PR may do (README, "Adding things"): every file
    ``benchmark/`` held at the parent commit still has the parent's bytes,
    and ``BENCHMARK.json`` holds the parent's entries where they were, the
    cell's name behind them in ``train_tokens_per_s``'s ``workloads``, and
    this PR's one configuration, one cell and 28 ``.nem`` metrics behind
    them (what a later PR adds behind those is its own)."""
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
    for key, added in (("configs", 1), ("workloads", 1), ("per_layer", 28)):
        assert after[key][:len(before[key])] == before[key], key
        assert len(after[key]) >= len(before[key]) + added, key
    mine = after["per_layer"][len(before["per_layer"]):][:28]
    assert all(m["workloads"] == [CELL] and m["name"].endswith(".nem")
               for m in mine)
    assert [m["name"] for m in mine] == files.load_cell(CELL)["per_layer"]
    assert after["configs"][len(before["configs"])]["name"] == NAME
    assert after["workloads"][len(before["workloads"])] == {
        "name": CELL, "config": NAME, "traffic": "pretrain-s8k", "chips": 1,
        "why": files.load_cell(CELL)["why"]}
    rate, setup = after["end_to_end"]
    assert setup == before["end_to_end"][1]
    was = before["end_to_end"][0]["workloads"]
    assert rate["workloads"][:len(was) + 1] == was + [CELL]
    assert {k: v for k, v in rate.items() if k != "workloads"} == {
        k: v for k, v in before["end_to_end"][0].items() if k != "workloads"}
    assert len(after["per_layer"]) <= 128
