"""DeepSeek-V3 (ISSUE 64: kanana-2-30b-a3b's block) at the tiny preset on
the CPU: the whole model against the plain float32 reference the benchmark
keeps (``benchmark/architectures/deepseek_v3.py``, which imports nothing
from the program): loss, tail logits and every gradient; each planted
departure from the equations (``benchmark/tests/kanana_control.py``, the
same it plants on the chip) fails the benchmark's own decision at the
cell's ``check`` where the right program passes; the eight shares of a
routed layer add up to the uncut layer with the shared experts counted
once; the rotation of interleaved pairs; the configuration file builds the
published model; what the family refuses. A CPU run shows results and
counts, never a time."""

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import DeepseekV3, get_model_class
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.ops import layers as L

from helpers.families import config_of, right, tail_loss_grads, tiny
from helpers.families import (BENCH, _close, _err,  # noqa: F401
                               _reference_grads, _telemetry_isolation)

CONFIG = config_of("deepseek_v3")
_tiny = functools.partial(tiny, "deepseek_v3")

if str(BENCH / "tests") not in sys.path:
    sys.path.insert(0, str(BENCH / "tests"))
from architectures import deepseek_v3 as arch  # noqa: E402  (benchmark/,
#                                               on sys.path by families)
from kanana_control import FAULTS, plant  # noqa: E402
from kinds import train_job  # noqa: E402
from lib import modelspec  # noqa: E402


@functools.lru_cache(maxsize=None)
def _right():
    params, tokens, targets, want, m = right("deepseek_v3")
    return params, tokens, targets, want, _reference_grads(
        arch, params, tokens, targets, m)


# every parameter group ISSUE 64 names, by the leaf's path
_NAMED = ("wq", "w_kva", "kv_norm", "w_kvb", "wo", "w_gate", "w_up",
          "w_down", "router", "ln1_scale", "ln2_scale", "tokens", "scale",
          "lm_head")


# ---- the whole stack against float32 ---------------------------------------
@pytest.mark.parametrize("variant", ["plain_f32", "flash_chunked_loss_f32",
                                     "flash_chunked_loss_bf16"])
def test_loss_logits_and_gradients_match_the_float32_reference(variant):
    """Float32: loss to 2e-5, tail logits to 5e-4 of their largest, and on
    the cell's path (flash kernels at a key of 24 and a value of 16,
    chunked loss, every layer rematted, the scan over the routed layers)
    every gradient to 3e-3 of its largest: the direct query, the latent's
    two projections and its norm, the dense lead, the held and the shared
    experts; a share (16 of 128 held) leaves the routing alone in the
    backward, so the router's and the selection bias's gradients are zero
    on both sides. bfloat16 weights (what the engine computes with) at the
    init's own scale against the float32 reference on the same weights,
    over the positions its mask counts: loss to 0.5%, logits to 5% of their
    largest and 2% rms."""
    kw = dict(remat=False) if variant == "plain_f32" else dict(
        attn_impl="flash", loss_chunk=64)
    model = _tiny(**kw)
    params, tokens, targets, (want, want_tail, _), want_g = _right()
    if variant.endswith("bf16"):
        params = model.init(jax.random.PRNGKey(3))
        m = modelspec.reference_model(arch, model, CONFIG["check"])
        with jax.default_matmul_precision("highest"):
            want, want_tail, counted = arch.reference(
                params, tokens, targets, m, 32)
        low = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)
        got_tail, got, _ = tail_loss_grads(model, low, tokens, targets,
                                           grads=False)
        numbers = train_job.tail_numbers(got_tail, want_tail, counted)
        assert abs(float(got) - want) <= 5e-3 * want
        assert numbers["logits_err_max"] < 5e-2, numbers
        assert numbers["logits_err_rms"] < 2e-2, numbers
        return
    with jax.default_matmul_precision("highest"):
        got_tail, got, got_g = tail_loss_grads(
            model, params, tokens, targets, grads=variant != "plain_f32")
    assert abs(float(got) - want) <= 2e-5 * want
    assert _err(got_tail, want_tail) < 5e-4
    if got_g is None:
        return
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = jax.tree_util.tree_leaves_with_path(got_g)
    assert [p for p, _ in flat_w] == [p for p, _ in flat_g]
    seen = set()
    for (path, w), (_, g) in zip(flat_w, flat_g):
        name = jax.tree_util.keystr(path)
        seen.add(path[-1].key)
        if path[-1].key == "router_bias":
            assert not np.any(w) and not np.any(g), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _err(g, w) < 3e-3, name
    assert set(_NAMED) <= seen


# ---- planted faults, through the benchmark's own decision ------------------
@pytest.mark.parametrize("fault", [None, *FAULTS], ids=lambda f: f or "none")
def test_the_cells_limits_catch_a_planted_fault(fault):
    """The benchmark's own decision (``kinds/train_job.py`` ``decide`` at
    the configuration's ``check``, over the positions the reference's mask
    counts) on the program's tail logits and loss against the reference's:
    the program passes, each departure ``benchmark/tests/
    kanana_control.py`` plants does not: rotate-half where the pairs are
    neighbours, a rotated key a head from other columns, five experts for
    six, weights not renormalised, the scaling left out, one shared expert
    of the two, a norm on the query."""
    params, tokens, targets, (want_loss, want_tail, counted), _ = right(
        "deepseek_v3")
    model = _tiny()
    program = model if fault is None else plant(model, fault)

    @jax.jit
    def run(params, tokens, targets):
        logits = program.apply(params, tokens)
        return logits[:, -32:], L.cross_entropy_loss(logits, targets)

    with jax.default_matmul_precision("highest"):
        got_tail, got_loss = run(params, tokens, targets)
    numbers = train_job.tail_numbers(got_tail, want_tail, counted)
    ok = train_job.decide(numbers, want_loss, float(got_loss),
                          CONFIG["check"])
    assert ok == (fault is None), (fault, numbers)
    assert numbers["positions_counted"] >= 8
    if fault is None:
        assert numbers["logits_err_max"] < 1e-4 > numbers["loss_err"]


# ---- the rotation ----------------------------------------------------------
def test_interleaved_pairs_rotate_as_the_reference_rotates_neighbours():
    """``pairs_to_halves`` then ``apply_rotary`` is the reference's rotation
    of each neighbouring pair, laid out as halves: pair i of the output's
    halves is the rotated (x_2i, x_2i+1); q . k is the same either way."""
    rng = np.random.default_rng(0)
    x, y = (jnp.asarray(rng.normal(size=(2, 24, 3, 8)), jnp.float32)
            for _ in range(2))
    cos, sin = L.rotary_embedding(24, 8, 1e6)
    def rot(v):
        return L.apply_rotary(L.pairs_to_halves(v), cos, sin)

    want_x, want_y = arch.rotate_pairs(x, 1e6), arch.rotate_pairs(y, 1e6)
    _close(rot(x), L.pairs_to_halves(want_x), 1e-6, "the pairs as halves")
    _close(jnp.einsum("bshd,bthd->bhst", rot(x), rot(y)),
           jnp.einsum("bshd,bthd->bhst", want_x, want_y), 1e-5, "q . k")
    assert _err(L.apply_rotary(x, cos, sin), L.pairs_to_halves(want_x)) > 0.1


# ---- the shares add up -----------------------------------------------------
def test_the_eight_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """``moe_ffn_held`` as the family calls it (sigmoid, renormalised top 6
    times 2.448, two shared experts as one SwiGLU): the eight shares of 16
    experts sum to what the float32 reference gives with all 128 held,
    when the shared experts are counted ONCE."""
    rng = np.random.default_rng(0)
    d, f, e, k, t = 64, 32, 128, 6, 96
    f32 = jnp.float32
    normal = lambda *s, scale=1.0: jnp.asarray(  # noqa: E731
        rng.normal(size=s) * scale, f32)
    h = normal(1, t, d)
    p = {"router": normal(d, e, scale=d ** -0.5),
         "router_bias": normal(e, scale=0.05),
         "experts": {"w_gate": normal(e, d, f, scale=0.2),
                     "w_up": normal(e, d, f, scale=0.2),
                     "w_down": normal(e, f, d, scale=0.2)},
         "shared": {"w_gate": normal(d, 2 * f, scale=0.2),
                    "w_up": normal(d, 2 * f, scale=0.2),
                    "w_down": normal(2 * f, d, scale=0.2)}}
    with jax.default_matmul_precision("highest"):
        want = arch.routed(p, h[0], top_k=k, first=0, renormalise=True,
                           scaling=2.448)[0][None]
        total = sharded_moe._swiglu_rows(
            h[0], p["shared"]["w_gate"], p["shared"]["w_up"])[2] \
            @ p["shared"]["w_down"]
        for first in range(0, e, 16):
            share = {name: w[first:first + 16]
                     for name, w in p["experts"].items()}
            y, counts = sharded_moe.moe_ffn_held(
                h, p["router"], p["router_bias"], share, None, k=k,
                first_expert=first, scaling=2.448)
            total = total + y
            assert int(counts["load"].sum()) == t * k
    _close(total, want, 2e-5, "the shares' sum")


# ---- the configuration, the counts, the plan -------------------------------
def test_the_configuration_file_builds_the_published_model():
    """``lib/modelspec.py`` holds the model as built to every published
    key of the file (``arch.WIDTHS``: the latent's widths, the rotation's
    keys and the router's among them); a preset that drifts fails the run;
    the counts are ISSUE 64's arithmetic; the sequence is the published
    context and the flash call cuts its rows in two."""
    import importlib
    flash = importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention")
    model = modelspec.build_model(CONFIG, arch, {})
    c = model.config
    mla = 2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256 + 32 * 128 * 2048
    assert mla == 26345984
    routed = mla + 4096 + 262272 + 9437184 + 16 * 4718592
    assert routed == 111547008
    assert c.num_params() == 511857152 == 4 * routed + 65669120
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    assert n == c.num_params()
    assert c.layer_kinds() == [("mla", "routed")] * 4
    assert (model.lead, model.period, model.repeats, model.left) == (
        0, 1, 4, 0)
    assert c.max_seq_len == CONFIG["max_position_embeddings"] == 32768
    assert model._rope[0].shape == (32768, 32)      # the rotated 64
    assert model._rope_pairs and c.q_lora_rank == 0
    assert flash.segments(32768, 192, 128) == 2
    m = modelspec.reference_model(arch, model)
    assert m["n_routed_experts"] == m["num_experts"] == 16
    assert m["num_routed_experts"] == 128 and m["rope_scaling"] is None
    assert c.flops_per_token(32768) == pytest.approx(
        arch.train_flops_per_token(m, 32768), rel=0.01)
    for key, bad in (("kv_lora_rank", 256), ("qk_rope_head_dim", 32),
                     ("rope_interleave", False), ("rope_theta", 10000),
                     ("num_experts_per_tok", 8), ("n_shared_experts", 1),
                     ("first_k_dense_replace", 1),
                     ("routed_scaling_factor", 2.5)):
        drifted = json.loads(json.dumps(CONFIG))
        drifted[key] = bad
        with pytest.raises(ValueError, match=key):
            modelspec.build_model(drifted, arch, {})
    whole = DeepseekV3(size="kanana-2-30b-a3b").config
    assert 30.0e9 < whole.num_params() < 31.0e9             # "30B"
    assert 3.0e9 < whole.num_active_params() < 3.9e9        # "A3B"
    kinds = whole.layer_kinds()
    assert kinds[:2] == [("mla", "dense"), ("mla", "routed")]
    assert stack_plan(kinds, 1) == (1, 47, 0)
    small = DeepseekV3(size="tiny", moe_held_experts=16)
    assert small.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(small.init, jax.random.PRNGKey(0))))
    assert (small.lead, small.period, small.repeats, small.left) == (
        1, 1, 4, 0)


def test_the_hand_count_of_train_flops_and_the_kernels_costs():
    """``train_flops_per_token`` of the cell's cut at 32768, part by part,
    against the widths written out; ``mla_flash_call_cost`` at live pairs
    (the same count whether a row is one sweep or two spans) and
    ``moe_call_cost`` at NINE matmul units a row."""
    model = modelspec.build_model(CONFIG, arch, {})
    m = modelspec.reference_model(arch, model)
    s = 32768
    parts = arch.forward_flops_per_token(m, s)
    d, nh = 2048, 32
    assert parts["mla_projections"] == 4 * 2 * (
        d * nh * 192 + d * 576 + 512 * nh * 256 + nh * 128 * d)
    assert parts["mla_attention"] == 4 * 2 * (192 + 128) * nh * (s + 1) / 2
    assert parts["dense_ffn"] == 0
    assert parts["routed_layers"] == 4 * (
        2 * d * 128 + 6 * d * 768 * 2 + 6 * d * 768 * 6 * 16 / 128)
    assert parts["head"] == 2 * d * 16032
    assert arch.train_flops_per_token(m, s) == 3 * parts["total"]
    # attention is over three quarters of the REQUIRED FLOPs at the
    # published context (two thirds of what runs, where remat runs the
    # layers' matmuls again and the kernels keep their output)
    assert 0.75 < parts["mla_attention"] / parts["total"] < 0.8
    pairs = nh * s * (s + 1) // 2
    fwd = arch.mla_flash_call_cost(m, 1, s, backward=False)
    bwd = arch.mla_flash_call_cost(m, 1, s, backward=True)
    assert fwd["flops"] == 4 * 2 * pairs * 320
    assert bwd["flops"] == 4 * 2 * pairs * (3 * 192 + 2 * 128)
    assert fwd["bytes"] == 4 * s * nh * ((2 * 192 + 2 * 128) * 2 + 4)
    # two spans of 16384 hold the same live pairs as one row of 32768
    half = s // 2
    assert 2 * (half * (half + 1) // 2) + half * half == s * (s + 1) // 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert arch.least_seconds(fwd, peaks)[1] == "compute"
    rows = 100
    assert arch.moe_call_cost(m, 1, s, backward=False, rows=rows)["flops"] \
        == 4 * rows * 2 * 3 * d * 768
    assert arch.moe_call_cost(m, 1, s, backward=True, rows=rows)["flops"] \
        == 4 * rows * 2 * 6 * d * 768
    # a balanced router's rows: 6 of 128 x 16 held = 0.75 a token
    assert arch.held_share(m) == 0.75
    assert s * arch.held_share(m) / 16 == 1536


def test_what_the_family_refuses():
    """Serving and the pipeline by mechanism, and a config the layer
    equations do not cover."""
    model = _tiny()
    for entry in (model.block, model.block_decode, model.decode,
                  model.init_cache):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            entry()
    for bad in (dict(moe_router_activation="softmax"),
                dict(tie_embeddings=True), dict(use_bias=True),
                dict(rope_scaling={"type": "yarn", "factor": 4})):
        with pytest.raises(NotImplementedError, match="sigmoid router"):
            _tiny(**bad)
    with pytest.raises(ValueError, match="held"):
        _tiny(moe_held_experts=256)
    with pytest.raises(TypeError):
        _tiny(hc_mult=4)            # no field of THIS family
    assert get_model_class("deepseek_v3") is DeepseekV3
    assert model.optimizer_frozen() == r"router_bias$"
    # a published null is a direct query; a rank would be a low-rank one,
    # with its norm, from the shared piece
    assert "wq" in DeepseekV3(size="tiny", q_lora_rank=None).init(
        jax.random.PRNGKey(0))["layers"]["lead"]["0"]["mla"]
    low = DeepseekV3(size="tiny", q_lora_rank=24)
    mla = low.init(jax.random.PRNGKey(0))["layers"]["lead"]["0"]["mla"]
    assert {"wq_a", "q_norm", "wq_b"} <= set(mla) and "wq" not in mla
    assert low.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(low.init, jax.random.PRNGKey(0))))
