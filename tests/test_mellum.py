"""Mellum 2 (ISSUE 38): window and full attention layers mixed, each with
its own rotary table (plain, YaRN), an explicit head width, and in every
layer a held share of softmax-routed experts, checked on the CPU at tiny
sizes against the plain float32 reference the benchmark keeps
(``benchmark/architectures/mellum.py``, which imports nothing from the
program). The whole model's loss and gradients against that reference are
``tests/test_mellum_reference.py``'s (PR 50: a file is one worker's under
``--dist loadfile``), the other families' train steps
``tests/test_step_pins.py``'s. A CPU run shows results and counts, never a
time."""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import Mellum, ModelConfig
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import (_held_layout, held_block,
                                           held_experts_ffn, moe_ffn_held,
                                           sigmoid_top_k, softmax_top_k,
                                           top_k_gating)
from deepspeed_tpu.ops import layers as L

from helpers import families
from helpers.families import config_of, tiny
from helpers.families import (_batch, _err,  # noqa: F401
                               _telemetry_isolation)
from architectures import mellum as arch  # noqa: E402  (benchmark/, on
#                                           sys.path by families)
from kinds import train_job  # noqa: E402
from lib import modelspec  # noqa: E402

CONFIG = config_of("mellum")
_tiny = functools.partial(tiny, "mellum")

PUBLISHED_YARN = CONFIG["rope_parameters"]["full_attention"]


@pytest.fixture(scope="module")
def right():
    return families.right("mellum", 16, loss_chunk=64)


# ---- the rotary tables -----------------------------------------------------
def test_yarn_table_is_the_closed_form_at_the_published_sizes():
    """``low`` 18, ``high`` 35 and ``attention_factor`` 0.1 ln 16 + 1 from
    the published section; the program's table against the benchmark's
    own closed form, at the published and at the tiny sizes; a window
    layer's table is the plain one."""
    inv, low, high = L.yarn_inv_freq(
        128, 500000, factor=16, original_max_position_embeddings=8192,
        beta_fast=32, beta_slow=1)
    assert (low, high) == (18, 35) == arch.yarn_ramp_ends(128, PUBLISHED_YARN)
    assert PUBLISHED_YARN["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1, rel=1e-12)
    i = np.arange(64)
    plain = 500000.0 ** (-2 * i / 128)
    ramp = np.clip((i - 18) / 17, 0, 1)
    np.testing.assert_allclose(inv, plain * (1 - ramp) + plain / 16 * ramp,
                               rtol=1e-12)
    assert inv[18] == plain[18] and inv[35] == plain[35] / 16
    tiny = _tiny().config.rope_parameters
    for hd, sections, n in ((128, CONFIG["rope_parameters"], 512),
                            (32, tiny, 128)):
        for kind, section in sections.items():
            cos, sin = L.rotary_embedding(n, hd, scaling=section)
            freq, factor = arch.inv_freq(hd, section)
            ang = np.arange(n)[:, None] * np.asarray(freq, np.float64)
            np.testing.assert_allclose(cos, np.cos(ang) * factor, atol=2e-4)
            np.testing.assert_allclose(sin, np.sin(ang) * factor, atol=2e-4)
            assert factor == (1.0 if kind == "sliding_attention"
                              else section["attention_factor"])
    # where the section gives no factor it is 0.1 ln(factor) + 1
    bare = {k: v for k, v in PUBLISHED_YARN.items()
            if k != "attention_factor"}
    np.testing.assert_allclose(
        L.rotary_embedding(4, 128, scaling=bare)[0][0],
        PUBLISHED_YARN["attention_factor"], rtol=1e-6)
    # the plain call is the table every other family had
    cos, _ = L.rotary_embedding(16, 8, 1e4)
    np.testing.assert_array_equal(cos, np.cos(np.outer(
        np.arange(16), 1.0 / 1e4 ** (np.arange(0, 8, 2) / 8))).astype(
            np.float32))
    with pytest.raises(NotImplementedError, match="rope_type"):
        L.rotary_embedding(4, 8, scaling={"rope_type": "llama3"})


def test_an_explicit_head_width_and_what_else_the_config_refuses():
    c = _tiny().config
    assert c.head_dim == 32 != c.hidden_size // c.num_heads
    assert ModelConfig(hidden_size=64, num_heads=4).head_dim == 16
    assert Mellum(size="12b-a2.5b").config.head_dim == 128
    with pytest.raises(ValueError, match="layer_types"):
        _tiny(layer_types=["sliding_attention", "mamba"] * 2)
    with pytest.raises(ValueError, match="rope_parameters"):
        _tiny(rope_parameters={"sliding_attention": {}})
    with pytest.raises(NotImplementedError, match="softmax router"):
        _tiny(moe_router_activation="sigmoid")
    model = _tiny()
    for entry in (model.block, model.decode, model.init_cache):
        with pytest.raises(NotImplementedError, match="apply/loss only"):
            entry()
    with pytest.raises(NotImplementedError, match="window a call"):
        model._mixers(lambda q, k, v, **kw: q, None)    # a wrapper without


# ---- the softmax router in front of the held dispatch ----------------------
E, K, D, F = 64, 8, 16, 8


def _full_layer():
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    w = lambda *shape: 0.5 * jax.random.normal(next(ks), shape)  # noqa: E731
    params = {"router": w(D, E),
              "experts": {"w_gate": w(E, D, F), "w_up": w(E, D, F),
                          "w_down": w(E, F, D)}}
    return params, jax.random.normal(jax.random.PRNGKey(1), (2, 48, D))


def test_softmax_router_by_hand_and_the_capacity_path_shares_it():
    logits = jnp.log(jnp.asarray([[0.5, 0.1, 0.3, 0.1]]))
    idx, w, probs, _ = softmax_top_k(logits, 2)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    np.testing.assert_allclose(np.asarray(probs[0]), [.5, .1, .3, .1],
                               rtol=1e-6)
    np.testing.assert_allclose(sorted(np.asarray(w[0])), [.375, .625],
                               rtol=1e-6)
    _, raw, _, _ = softmax_top_k(logits, 2, renormalise=False)
    np.testing.assert_allclose(sorted(np.asarray(raw[0])), [.3, .5],
                               rtol=1e-6)
    # top_k_gating's combine weights are these weights
    combine, *_ = top_k_gating(logits, 2, drop_tokens=False)
    np.testing.assert_allclose(np.asarray(jnp.sum(combine, -1)[0]),
                               [.625, 0, .375, 0], rtol=1e-6)
    with pytest.raises(ValueError, match="selection bias"):
        params, x = _full_layer()
        moe_ffn_held(x, params["router"], jnp.zeros(E), params["experts"],
                     None, k=K, router="softmax")


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-15, 16-31, 32-47 and 48-63 of one layer, each share run
    as its chip runs it, sum to the reference's layer with every expert
    held; there is no shared expert to count once, and every share counts
    the same load over all 64."""
    params, x = _full_layer()
    xt = x.reshape(-1, D)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = arch.routed(params, xt, top_k=K, first=0,
                                  renormalise=True)
        total, loads = 0, []
        for chip in range(4):
            mine = {n: w[16 * chip:16 * chip + 16]
                    for n, w in params["experts"].items()}
            out, counts = moe_ffn_held(
                x, params["router"], None, mine, None, k=K,
                first_expert=16 * chip, router="softmax", block=16)
            ref, _, _ = arch.routed(dict(params, experts=mine), xt, top_k=K,
                                    first=16 * chip, renormalise=True)
            assert _err(out.reshape(-1, D), ref) < 1e-5, chip
            sent = counts["load"][16 * chip:16 * chip + 16]
            assert int(counts["done"]) == int(jnp.sum(sent))
            loads.append(np.asarray(counts["load"]))
            total = total + out.reshape(-1, D)
    assert _err(total, whole) < 1e-5
    assert all(np.array_equal(loads[0], l) for l in loads)
    assert int(loads[0].sum()) == xt.shape[0] * K


@pytest.mark.parametrize("skew", ["balanced", "all_to_one_held_expert",
                                  "none_held"])
def test_no_row_is_dropped_under_a_skewed_softmax_router(skew):
    """Every token to held expert 3 (96 rows in blocks of 16: six blocks of
    one expert), or none to any held expert: the rows computed are the
    rows routed, the result is the dense sum, and the blocks counted are
    the sweep's own trip count."""
    params, x = _full_layer()
    held = {n: w[:16] for n, w in params["experts"].items()}
    xt = x.reshape(-1, D)
    plant = {"balanced": 0.0,
             "all_to_one_held_expert": 30.0 * jax.nn.one_hot(3, E),
             "none_held": jnp.where(jnp.arange(E) < 16, -30.0, 0.0)}[skew]
    idx, w, _, _ = softmax_top_k(xt @ params["router"] + plant, K)
    idx = idx.astype(jnp.int32)
    out, swept = held_experts_ffn(xt, idx, w, held, 0, 16)
    want_rows = int(jnp.sum(idx < 16))
    assert int(swept["done"]) == want_rows
    if skew == "all_to_one_held_expert":
        assert int(jnp.sum(idx == 3)) == xt.shape[0]
    if skew == "none_held":
        assert want_rows == 0 and not np.any(np.asarray(out))
    else:
        dense = sum(jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
                    * arch._swiglu({n: v[e] for n, v in held.items()}, xt)
                    for e in range(16))
        assert _err(out, dense) < 1e-5
    # the sweep's own trip count is the blocks the layer counts from the load
    ends = _held_layout(idx, w, 0, 16, 16)[3]
    sent = jnp.bincount(idx.reshape(-1), length=E)[:16]
    assert int(ends[-1]) == int(jnp.sum((sent + 15) // 16)) \
        == int(swept["tiles"])
    if skew == "all_to_one_held_expert":
        assert int(ends[-1]) >= xt.shape[0] // 16


def test_the_block_rule_by_shape():
    """Kimi's shape keeps its 1024 (twice an even load of 512). Where an
    even load is a whole block or more (this cell's 2048) it lies at least
    a quarter block from a multiple of the block: 768, three blocks from
    1537 to 2304 rows. Always 128s between 128 and 1024."""
    assert held_block(16384, 8, 256) == 1024        # the Kimi cell
    assert held_block(16384, 8, 64) == 768          # this cell
    assert held_block(1024, 8, 256) == 128 and held_block(128, 8, 64) == 128
    assert held_block(8 * 128, 8, 64) == 256        # the tiny engine's step
    for n in (256, 4096, 8192, 16384, 24576, 32768, 65536, 2 ** 20):
        block, even = held_block(n, 8, 64), n / 8
        assert block % 128 == 0 and 128 <= block <= 1024
        if even >= 1024:
            assert block / 4 <= even % block <= 3 * block / 4


# ---- planted faults, through the benchmark's own decision ------------------
def _sigmoid_for_softmax(logits, k, *, renormalise=True):
    return sigmoid_top_k(logits, jnp.zeros(logits.shape[-1]), k,
                         renormalise=renormalise)


def _experts_16_to_31(real):
    def shifted(*a, **kw):
        return real(*a, **{**kw, "first_expert": 16})
    return shifted


_TINY_ROPE = _tiny().config.rope_parameters
FAULTS = {
    None: {},
    "window_of_33_in_a_window_layer": dict(sliding_window=33),
    "no_window_in_a_window_layer": dict(sliding_window=4096),
    "yarn_left_out_of_the_full_layer": dict(rope_parameters={
        **_TINY_ROPE, "full_attention": _TINY_ROPE["sliding_attention"]}),
    "attention_factor_left_out": dict(rope_parameters={
        **_TINY_ROPE, "full_attention": {
            **_TINY_ROPE["full_attention"], "attention_factor": 1.0}}),
    "the_two_kinds_tables_swapped": dict(rope_parameters={
        "full_attention": _TINY_ROPE["sliding_attention"],
        "sliding_attention": _TINY_ROPE["full_attention"]}),
    "sigmoid_for_softmax": {},
    "weights_not_renormalised": dict(moe_norm_topk=False),
    "seven_experts_for_eight": dict(moe_top_k=7),
    "held_experts_16_to_31_for_0_to_15": {},
}


@pytest.mark.parametrize("fault", list(FAULTS), ids=lambda f: f or "none")
def test_the_cells_limits_catch_a_planted_fault(fault, right, monkeypatch):
    """The benchmark's own decision (``kinds/train_job.py`` ``decide`` at
    the configuration's ``check``, over the positions the reference's mask
    counts) on the program's tail logits and loss against the
    reference's: the program passes, each planted departure from the
    published equations does not."""
    params, tokens, targets, (want_loss, want_tail, counted), _ = right
    if fault == "sigmoid_for_softmax":
        monkeypatch.setattr(sharded_moe, "softmax_top_k",
                            _sigmoid_for_softmax)
    if fault == "held_experts_16_to_31_for_0_to_15":
        monkeypatch.setattr(sharded_moe, "moe_ffn_held",
                            _experts_16_to_31(sharded_moe.moe_ffn_held))
    model = _tiny(**FAULTS[fault])

    @jax.jit
    def run(params, tokens, targets):
        logits = model.apply(params, tokens)
        return logits[:, -32:], L.cross_entropy_loss(logits, targets)

    with jax.default_matmul_precision("highest"):
        got_tail, got_loss = run(params, tokens, targets)
    got_loss = float(got_loss)
    numbers = train_job.tail_numbers(got_tail, want_tail, counted)
    ok = train_job.decide(numbers, want_loss, got_loss, CONFIG["check"])
    assert ok == (fault is None), numbers
    assert numbers["positions_counted"] >= 16
    if fault is None:
        assert numbers["logits_err_max"] < 1e-4 > numbers["loss_err"]


# ---- the configuration, the counts, the plan -------------------------------
def test_the_configuration_file_builds_the_published_model():
    """``lib/modelspec.py`` holds the model as built to every published
    key of the file (``arch.WIDTHS``: the head width, the kinds, the rotary
    sections, the router); a preset that drifts fails the run; the counts
    are ISSUE 38's arithmetic."""
    model = modelspec.build_model(CONFIG, arch, {})
    c = model.config
    assert c.num_params() == 595153152 == 4 * 120476160 + 113248512
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    assert n == c.num_params()
    assert (model.lead, model.period, model.repeats, model.left) == (
        0, 1, 3, 1)     # three window layers under one scan, the full one
    m = modelspec.reference_model(arch, model)
    assert m["head_dim"] == 128 and m["num_experts"] == 16
    assert m["num_routed_experts"] == 64 and m["norm_topk_prob"] is True
    assert c.flops_per_token(16384) == pytest.approx(
        arch.train_flops_per_token(m, 16384), rel=0.01)
    for key, bad in (("head_dim", 72), ("sliding_window", 4096),
                     ("num_experts_per_tok", 6)):
        drifted = json.loads(json.dumps(CONFIG))
        drifted[key] = bad
        with pytest.raises(ValueError, match=key):
            modelspec.build_model(drifted, arch, {})
    drifted = json.loads(json.dumps(CONFIG))
    drifted["rope_parameters"]["full_attention"]["factor"] = 8
    with pytest.raises(ValueError, match="rope_parameters"):
        modelspec.build_model(drifted, arch, {})
    whole = Mellum(size="12b-a2.5b").config
    assert 12.0e9 < whole.num_params() < 12.3e9             # "12B"
    assert 2.3e9 < whole.num_active_params() < 2.6e9        # "A2.5B"
    assert whole.layer_types.count("full_attention") == 7
    tiny = _tiny()
    assert tiny.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(tiny.init, jax.random.PRNGKey(0))))


@pytest.mark.parametrize("kinds,lead,want", [
    ("sssf", 0, (1, 3, 1)),             # the cell's cut: layers 0 to 3
    ("sssf" * 7, 0, (4, 7, 0)),         # the published 28 layers
    ("sssfsssf", 0, (4, 2, 0)),
])
def test_stack_plan_of_the_mellum_patterns(kinds, lead, want):
    assert stack_plan(list(kinds), lead) == want


def test_required_operations_by_hand():
    m = modelspec.reference_model(arch, modelspec.build_model(
        CONFIG, arch, {}))
    assert arch.live_pairs(m, 16384, "swa") == 16253440
    assert arch.live_pairs(m, 16384, "full") == 134225920
    parts = arch.forward_flops_per_token(m, 16384)
    assert parts["projections"] == 4 * 2 * (2 * 2304 * 4096
                                            + 2 * 2304 * 512)
    assert parts["held_experts"] == 4 * 2 * 3 * 2304 * 896 * 2
    assert parts["head"] == 2 * 2304 * 24576
    assert parts["full_attention"] == 4 * 128 * 32 * 134225920 / 16384
    assert parts["swa_attention"] == 3 * 4 * 128 * 32 * 16253440 / 16384
    # a 16384-token step: 27.85 T (ISSUE 38's 29 T counts the flash
    # backward's second S = QK^T; required FLOPs here do not)
    assert 27.8e12 < 16384 * arch.train_flops_per_token(m, 16384) < 27.9e12
    swa = arch.swa_flash_call_cost(m, 1, 16384, backward=False)
    full = arch.full_flash_call_cost(m, 1, 16384, backward=True)
    assert swa["flops"] == 3 * 4 * 128 * 32 * 16253440
    assert full["flops"] == 10 * 128 * 32 * 134225920
    assert swa["bytes"] == 3 * (2 * 16384 * 32 * 128 * 2
                                + 2 * 16384 * 4 * 128 * 2 + 16384 * 32 * 4)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert arch.least_seconds(full, peaks)[1] == "compute"
    moe = arch.moe_call_cost(m, 1, 16384, backward=False)
    assert moe["flops"] == 4 * 32768 * 6 * 2304 * 896
    assert arch.moe_call_cost(m, 1, 16384, backward=False, rows=100)[
        "flops"] == 4 * 100 * 6 * 2304 * 896
