"""Laguna (ISSUE 60): window layers at 72 and full layers at 48 query heads
over 8 key heads (6 and 4 over 2 at the tiny preset), a sigmoid gate a head,
half the head rotated under YaRN in a full layer, and a held share of 256
softmax-routed experts beside a shared one, checked on the CPU at tiny
sizes against the plain float32 reference the benchmark keeps
(``benchmark/architectures/laguna.py``, which imports nothing from the
program). The whole model's loss and gradients against that reference are
``tests/test_laguna_reference.py``'s, the engine's cases
``tests/test_laguna_engine.py``'s. A CPU run shows results and counts,
never a time."""

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import Laguna
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.moe.sharded_moe import moe_ffn_held
from deepspeed_tpu.ops import layers as L

from helpers import families
from helpers.families import BENCH, config_of, tiny
from helpers.families import _err, _telemetry_isolation  # noqa: F401

if str(BENCH / "tests") not in sys.path:
    sys.path.insert(0, str(BENCH / "tests"))
from architectures import laguna as arch  # noqa: E402  (benchmark/, on
#                                           sys.path by families)
from kinds import train_job  # noqa: E402
from laguna_control import FAULTS, plant  # noqa: E402
from lib import modelspec  # noqa: E402

CONFIG = config_of("laguna")
_tiny = functools.partial(tiny, "laguna")
PUBLISHED_YARN = CONFIG["rope_parameters"]["full_attention"]


# ---- the head count a kind -------------------------------------------------
def test_the_head_count_is_the_kinds():
    """W_q, W_o and W_g take their shapes from the kind's head count (6 in
    a window layer, 4 in a full one, over 2 key heads of 32 on hidden 64);
    ``num_params`` is the tree's size at the tiny and the published
    widths; one count a kind or the config refuses."""
    model = _tiny()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"]
    swa, full = shapes["period"]["0"]["swa"], shapes["tail"]["0"]["full"]
    lead = shapes["lead"]["0"]
    assert swa["wq"].shape == (3, 64, 6 * 32) and swa["wg"].shape == (3, 64, 6)
    assert swa["wo"].shape == (3, 6 * 32, 64)
    assert full["wq"].shape == (64, 4 * 32) and full["wg"].shape == (64, 4)
    assert swa["wk"].shape[1:] == full["wk"].shape == (64, 2 * 32)
    assert lead["full"]["wq"].shape == (64, 4 * 32) and "mlp" in lead
    assert set(shapes["tail"]["0"]["moe"]) == {"router", "experts", "shared"}
    assert model.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    whole = Laguna(size="s-2.1").config
    assert whole.kind_heads == {"full_attention": 48, "sliding_attention": 72}
    assert 117e9 < whole.num_params() < 118e9               # "118B"
    assert whole.lead_layers() == 1 and whole.mlp_only_layers == [0]
    assert whole.gating_types == ["per_head"] * 48
    with pytest.raises(ValueError, match="one count a kind"):
        _tiny(num_attention_heads_per_layer=[4, 6, 6, 5, 4]).config.kind_heads
    with pytest.raises(ValueError, match="key heads"):
        _tiny(num_attention_heads_per_layer=[4, 5, 5, 5, 4])
    with pytest.raises(TypeError, match="gating"):
        _tiny(gating="per-channel")     # one form is built: no option
    with pytest.raises(NotImplementedError, match="softmax router"):
        _tiny(moe_router_activation="sigmoid")
    with pytest.raises(ValueError, match="mlp_layer_types"):
        _tiny(mlp_layer_types=["dense", "sparse"])
    with pytest.raises(ValueError, match="layer_types"):
        _tiny(layer_types=["sliding_attention", "mamba"] * 2 + ["mamba"])


def test_the_configuration_file_builds_the_published_model():
    """``lib/modelspec.py`` holds the model as built to every published
    key of the file (``arch.WIDTHS``: both head counts, the kinds, the
    rotary sections with their rotated share, the router); a preset that
    drifts fails the run; the counts are ISSUE 60's arithmetic."""
    model = modelspec.build_model(CONFIG, arch, {})
    c = model.config
    window = (2 * 3072 * 128 * (72 + 8) + 3072 * 72 + 2 * 3072
              + 3072 * 256 + 3 * 3072 * 1024)
    full = (2 * 3072 * 128 * (48 + 8) + 3072 * 48 + 2 * 3072
            + 3072 * 256 + 3 * 3072 * 1024)
    assert (window, full) == (73365504, 54417408)
    assert c.num_params() == 653577216 == (
        3 * window + full + 4 * 8 * 9437184 + 2 * 12544 * 3072 + 3072)
    assert c.num_params() == sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    # three window layers under one scan, the full one behind them
    assert (model.lead, model.period, model.repeats, model.left) == (
        0, 1, 3, 1)
    m = modelspec.reference_model(arch, model)
    assert m["num_experts"] == 8 and m["num_routed_experts"] == 256
    assert m["num_attention_heads_per_layer"] == [72, 72, 72, 48]
    assert c.flops_per_token(8192) == pytest.approx(
        arch.train_flops_per_token(m, 8192), rel=0.01)
    for key, bad in (("head_dim", 64), ("sliding_window", 1024),
                     ("num_experts_per_tok", 8),
                     ("num_attention_heads_per_layer", [48, 48, 48, 48]),
                     ("moe_routed_scaling_factor", 1.0),
                     ("shared_expert_intermediate_size", 0)):
        drifted = json.loads(json.dumps(CONFIG))
        drifted[key] = bad
        with pytest.raises(ValueError, match=key):
            modelspec.build_model(drifted, arch, {})
    drifted = json.loads(json.dumps(CONFIG))
    drifted["rope_parameters"]["full_attention"]["partial_rotary_factor"] = 1
    with pytest.raises(ValueError, match="rope_parameters"):
        modelspec.build_model(drifted, arch, {})


@pytest.mark.parametrize("kinds,lead,want", [
    (["sd"] + ["ws"] * 3 + ["fs"], 1, (1, 3, 1)),   # the tiny preset
    (["ws"] * 3 + ["fs"], 0, (1, 3, 1)),            # the cell: layers 1 to 4
    (["fd"] + ["ws", "ws", "ws", "fs"] * 11 + ["ws"] * 3, 1, (4, 11, 3)),
])
def test_stack_plan_of_the_laguna_patterns(kinds, lead, want):
    assert stack_plan(kinds, lead) == want


def test_required_operations_by_hand():
    """``train_flops_per_token`` and the three cost functions against a
    hand count at the cell's sizes: the head count is the kind's."""
    m = modelspec.reference_model(arch, modelspec.build_model(
        CONFIG, arch, {}))
    assert arch.live_pairs(m, 8192, "swa") == 512 * 513 // 2 + 7680 * 512
    assert arch.live_pairs(m, 8192, "full") == 8192 * 8193 // 2
    parts = arch.forward_flops_per_token(m, 8192)
    def proj(nh):
        return 2 * (2 * 3072 * nh * 128 + 2 * 3072 * 8 * 128 + 3072 * nh)

    assert parts["projections"] == 3 * proj(72) + proj(48)
    assert parts["swa_attention"] == 3 * 4 * 128 * 72 * 4063488 / 8192
    assert parts["full_attention"] == 4 * 128 * 48 * 33558528 / 8192
    assert parts["dense_ffn"] == 0
    assert parts["routed_layers"] == 4 * (
        2 * 3072 * 256 + 6 * 3072 * 1024 * (1 + 10 * 8 / 256))
    assert parts["head"] == 2 * 3072 * 12544
    # ISSUE 60's count of a step: 19.8 T
    assert 19.7e12 < 8192 * arch.train_flops_per_token(m, 8192) < 19.9e12
    swa = arch.swa_flash_call_cost(m, 1, 8192, backward=False)
    full = arch.full_flash_call_cost(m, 1, 8192, backward=True)
    assert swa["flops"] == 3 * 4 * 128 * 72 * 4063488
    assert full["flops"] == 10 * 128 * 48 * 33558528
    assert swa["bytes"] == 3 * (2 * 8192 * 72 * 128 * 2
                                + 2 * 8192 * 8 * 128 * 2 + 8192 * 72 * 4)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert arch.least_seconds(full, peaks)[1] == "compute"
    moe = arch.moe_call_cost(m, 1, 8192, backward=False)
    assert moe["flops"] == 4 * 2560 * 6 * 3072 * 1024
    back = arch.moe_call_cost(m, 1, 8192, backward=True, rows=100)
    assert back["flops"] == 4 * 100 * 16 * 3072 * 1024
    # a dense lead layer counts its SwiGLU and no router
    lead = dict(m, layer_types=["full_attention"] + m["layer_types"],
                mlp_layer_types=["dense"] + m["mlp_layer_types"],
                num_attention_heads_per_layer=[48] + m[
                    "num_attention_heads_per_layer"])
    more = arch.forward_flops_per_token(lead, 8192)
    assert more["dense_ffn"] == 6 * 3072 * 12288
    assert more["routed_layers"] == parts["routed_layers"]
    assert more["projections"] == parts["projections"] + proj(48)


# ---- the partial rotation --------------------------------------------------
def test_partial_rotation_and_the_yarn_table_on_the_rotated_width():
    """A section's ``partial_rotary_factor`` makes the table as wide as the
    ROTATED channels and YaRN's ramp is reckoned on that width (published:
    64 of 128, low 9, high 18); ``apply_rotary`` rotates the leading
    channels and hands the others on bit for bit; the program's tables are
    the benchmark's closed form at the published and the tiny widths; a
    whole-head table is rotated as it was."""
    cos, sin = L.rotary_embedding(512, 128, scaling=PUBLISHED_YARN)
    assert cos.shape == (512, 32)
    inv, low, high = L.yarn_inv_freq(
        64, 500000, factor=128, original_max_position_embeddings=8192,
        beta_fast=32, beta_slow=1)
    assert (low, high) == (9, 18) == arch.mellum.yarn_ramp_ends(
        64, PUBLISHED_YARN)
    i = np.arange(32)
    plain = 500000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 9) / 9, 0, 1)
    by_hand = plain * (1 - ramp) + plain / 128 * ramp
    np.testing.assert_allclose(inv, by_hand, rtol=1e-12)
    ang = np.arange(512)[:, None] * by_hand
    factor = PUBLISHED_YARN["attention_factor"]
    np.testing.assert_allclose(cos, np.cos(ang) * factor, atol=2e-4)
    np.testing.assert_allclose(sin, np.sin(ang) * factor, atol=2e-4)
    model = _tiny()
    sections = model.config.rope_parameters
    for kind, key, rot in (("full", "full_attention", 16),
                           ("swa", "sliding_attention", 32)):
        cos, sin, _ = model._ropes[kind]
        assert cos.shape == (128, rot // 2)
        freq, factor = arch.mellum.inv_freq(rot, sections[key])
        ang = np.arange(128)[:, None] * np.asarray(freq, np.float64)
        np.testing.assert_allclose(cos, np.cos(ang) * factor, atol=2e-4)
        np.testing.assert_allclose(sin, np.sin(ang) * factor, atol=2e-4)
    assert arch.mellum.yarn_ramp_ends(16, sections["full_attention"]) == (
        0, 3)
    # channels 16 to 31 of a full layer's q pass through; 0 to 15 are the
    # reference's rotation
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 4, 32))
    cos, sin, _ = model._ropes["full"]
    got = L.apply_rotary(x, cos, sin)
    np.testing.assert_array_equal(got[..., 16:], x[..., 16:])
    want = arch.rotate_leading(x, 32, sections["full_attention"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.max(jnp.abs(got[..., :16] - x[..., :16]))) > 0.1
    low_precision = L.apply_rotary(x.astype(jnp.bfloat16), cos, sin)
    assert low_precision.dtype == jnp.bfloat16
    np.testing.assert_array_equal(low_precision[..., 16:],
                                  x.astype(jnp.bfloat16)[..., 16:])
    # decode-time positions index the narrow table the same way
    at = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    np.testing.assert_allclose(L.apply_rotary(x, cos, sin, at), got,
                               atol=1e-6)
    with pytest.raises(ValueError, match="rotated channels"):
        L.apply_rotary(x[..., :8], cos, sin)
    with pytest.raises(ValueError, match="even number"):
        L.rotary_embedding(8, 6, scaling={"partial_rotary_factor": 0.5})


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


def test_a_whole_head_table_lowers_to_the_text_it_lowered_to():
    """``apply_rotary`` with a table as wide as the head, and
    ``rotary_embedding`` without a ``partial_rotary_factor``, are the
    parent's: the rotation lowers to the text of the parent's body (kept
    here), for Mellum's and Xing4's whole-head calls and for the slice
    Qwen3-Next cuts itself, and the tables are the parent's numbers
    (``tests/test_step_pins.py`` holds the three families' whole steps)."""
    def parents(x, cos, sin):
        s = x.shape[1]
        cos = cos[None, :s, None, :]
        sin = sin[None, :s, None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
        return out.astype(x.dtype)

    for shape, width in (((2, 128, 4, 32), 32),      # Mellum: the head
                         ((2, 128, 4, 8), 8),        # Xing4: q_pe, k_pe
                         ((2, 128, 4, 16), 16)):     # Qwen3-Next: its slice
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        table = jax.ShapeDtypeStruct((128, width // 2), jnp.float32)
        assert _lowered(L.apply_rotary, x, table, table) == _lowered(
            parents, x, table, table).replace("parents", "apply_rotary")
    mellum = tiny("mellum").config.rope_parameters
    for section in (*mellum.values(), None):
        cos, sin = L.rotary_embedding(128, 32, 10000.0, scaling=section)
        whole = dict(section or {}, partial_rotary_factor=1)
        np.testing.assert_array_equal(
            cos, L.rotary_embedding(128, 32, 10000.0, scaling=whole)[0])
        assert cos.shape == (128, 16)


# ---- the gate a head -------------------------------------------------------
def _attention_alone(model, kind, wg):
    """``_attention`` of one layer of ``kind`` with the output projection
    the identity: (what goes into W_o, the gate's [sum, count])."""
    c = model.config
    nh = c.kind_heads[{"swa": "sliding_attention",
                       "full": "full_attention"}[kind]]
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    p = {"wq": 0.3 * jax.random.normal(ks[0], (64, nh * 32)),
         "wk": 0.3 * jax.random.normal(ks[1], (64, 2 * 32)),
         "wv": jax.random.normal(ks[2], (64, 2 * 32)),
         "wg": wg, "wo": jnp.eye(nh * 32)}
    h = jax.random.normal(ks[3], (2, 128, 64))
    mixers = model._mixers(L.dot_product_attention, None)
    return model._attention(p, h, kind, mixers[kind])


@pytest.mark.parametrize("kind,nh", [("swa", 6), ("full", 4)])
def test_the_gate_is_one_number_a_head(kind, nh, monkeypatch):
    """A zero W_g halves the attention's output (``sigmoid(0)``); a drawn
    W_g scales a head's 32 channels by ONE number a token, and a head's
    column touches that head's channels alone; the gauge's numbers are the
    gate's sum and count."""
    model = _tiny(remat=False)
    half, gate = _attention_alone(model, kind, jnp.zeros((64, nh)))
    assert half.shape == (2, 128, nh * 32)
    np.testing.assert_allclose(gate, [0.5 * 2 * 128 * nh, 2 * 128 * nh])
    with monkeypatch.context() as patch:
        patch.setattr(jax.nn, "sigmoid", jnp.ones_like)
        ungated, _ = _attention_alone(model, kind, jnp.zeros((64, nh)))
    np.testing.assert_allclose(2 * half, ungated, rtol=1e-6, atol=1e-6)
    assert float(jnp.max(jnp.abs(ungated))) > 0.5
    # drawn columns: one ratio a head over its 32 channels, heads apart
    drawn = 0.125 * jax.random.normal(jax.random.PRNGKey(2), (64, nh))
    open_, _ = _attention_alone(model, kind, drawn)
    ratio = np.asarray(open_ / ungated).reshape(2, 128, nh, 32)
    assert np.allclose(ratio, ratio[..., :1], atol=1e-4)    # one a head
    assert ratio.min() > 0 and ratio.max() < 1
    assert np.std(ratio[..., 0], axis=-1).min() > 0.01      # heads differ
    # another column for head 1 moves head 1's channels and no other's
    other, _ = _attention_alone(model, kind, drawn.at[:, 1].set(-drawn[:, 1]))
    moved = np.asarray(other != open_).reshape(2, 128, nh, 32)
    assert moved[:, :, 1].mean() > 0.99
    assert not moved[:, :, [h for h in range(nh) if h != 1]].any()


# ---- the shares add up -----------------------------------------------------
def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """``moe_ffn_held`` as the family calls it (softmax over 256,
    renormalised top 10 times 2.5, an ungated shared expert): the 32
    shares of 8 experts (``first_expert`` 0, 8, ..., 248), each run as its
    chip runs it and held to the reference's share, sum to the float32
    reference's layer with all 256 held once the shared expert, which
    every chip computes alike, is counted ONCE; every share counts the
    same load over all 256."""
    rng = np.random.default_rng(0)
    d, f, e, k, t = 16, 8, 256, 10, 96
    normal = lambda *s, scale=0.5: jnp.asarray(  # noqa: E731
        rng.normal(size=s) * scale, jnp.float32)
    params = {"router": normal(d, e),
              "experts": {"w_gate": normal(e, d, f), "w_up": normal(e, d, f),
                          "w_down": normal(e, f, d)},
              "shared": {"w_gate": normal(d, f), "w_up": normal(d, f),
                         "w_down": normal(f, d)}}
    x = normal(2, t // 2, d, scale=1.0)
    xt = x.reshape(-1, d)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = arch.routed(params, xt, top_k=k, first=0,
                                  renormalise=True, scaling=2.5)
        shared = arch._swiglu(params["shared"], xt)
        total, loads = 0, []
        for chip in range(32):
            mine = {n: w[8 * chip:8 * chip + 8]
                    for n, w in params["experts"].items()}
            out, counts = jax.jit(functools.partial(
                moe_ffn_held, k=k, first_expert=8 * chip, router="softmax",
                scaling=2.5, block=16))(
                    x, params["router"], None, mine, params["shared"])
            ref, _, _ = arch.routed(dict(params, experts=mine), xt, top_k=k,
                                    first=8 * chip, renormalise=True,
                                    scaling=2.5)
            assert _err(out.reshape(-1, d), ref) < 1e-5, chip
            sent = counts["load"][8 * chip:8 * chip + 8]
            assert int(counts["done"]) == int(jnp.sum(sent))
            loads.append(np.asarray(counts["load"]))
            total = total + out.reshape(-1, d)
    assert _err(total - 31 * shared, whole) < 1e-5
    assert float(jnp.max(jnp.abs(shared))) > 0.05
    assert all(np.array_equal(loads[0], l) for l in loads)
    assert int(loads[0].sum()) == t * k


# ---- planted faults, through the benchmark's own decision ------------------
@pytest.mark.parametrize("fault", [None, *FAULTS], ids=lambda f: f or "none")
def test_the_cells_limits_catch_a_planted_fault(fault):
    """The benchmark's own decision (``kinds/train_job.py`` ``decide`` at
    the configuration's ``check``, over the positions the reference's mask
    counts) on the program's tail logits and loss against the
    reference's: the program passes, each departure
    ``benchmark/tests/laguna_control.py`` plants (the same it plants on
    the chip) does not."""
    params, tokens, targets, (want_loss, want_tail, counted), _ = (
        families.right("laguna", 8, loss_chunk=64))
    program, weights = (_tiny(), params) if fault is None else plant(
        _tiny(), params, fault)

    @jax.jit
    def run(params, tokens, targets):
        logits = program.apply(params, tokens)
        return logits[:, -32:], L.cross_entropy_loss(logits, targets)

    with jax.default_matmul_precision("highest"):
        got_tail, got_loss = run(weights, tokens, targets)
    numbers = train_job.tail_numbers(got_tail, want_tail, counted)
    ok = train_job.decide(numbers, want_loss, float(got_loss),
                          CONFIG["check"])
    assert ok == (fault is None), numbers
    assert numbers["positions_counted"] >= 8
    if fault is None:
        assert numbers["logits_err_max"] < 1e-4 > numbers["loss_err"]
