"""Nemotron-H (ISSUE 66: NVIDIA-Nemotron-3-Super-120B-A12B's block) at the
tiny preset on the CPU: the whole model against the plain float32 reference
the benchmark keeps (``benchmark/architectures/nemotron_h.py``, which
imports nothing from the program): loss, tail logits and every gradient;
each planted departure from the equations (``benchmark/tests/
nemotron_control.py``, the same it plants on the chip) fails the
benchmark's own decision at the cell's ``check`` where the right program
passes; the shares of each kind of layer add up to the uncut layer; the
``relu2`` grouped-matmul pair beside the unchanged ``swiglu`` one; the
configuration file builds the published model; what the family refuses. A
CPU run shows results and counts, never a time."""

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import NemotronH, get_model_class
from deepspeed_tpu.models.stack import grouped_query_attention, stack_plan
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.ops import layers as L
from deepspeed_tpu.ops.pallas import grouped_matmul
from deepspeed_tpu.ops.ssd import chunk_ssd

from helpers.families import config_of, right, tail_loss_grads, tiny
from helpers.families import (BENCH, _close, _err,  # noqa: F401
                               _reference_grads, _telemetry_isolation)

CONFIG = config_of("nemotron_h")
_tiny = functools.partial(tiny, "nemotron_h")

if str(BENCH / "tests") not in sys.path:
    sys.path.insert(0, str(BENCH / "tests"))
from architectures import nemotron_h as arch  # noqa: E402  (benchmark/,
#                                              on sys.path by families)
from kinds import train_job  # noqa: E402
from lib import modelspec  # noqa: E402
from nemotron_control import FAULTS, plant  # noqa: E402


@functools.lru_cache(maxsize=None)
def _right():
    params, tokens, targets, want, m = right("nemotron_h")
    return params, tokens, targets, want, _reference_grads(
        arch, params, tokens, targets, m)


# every parameter group ISSUE 66 names, by the leaf's path
_NAMED = ("w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
          "w_out", "wq", "wk", "wv", "wo", "router", "w_dn", "w_up",
          "w_down", "ln1_scale", "tokens", "scale", "lm_head")


# ---- the whole stack against float32 ---------------------------------------
@pytest.mark.parametrize("variant", ["plain_f32", "flash_chunked_loss_f32",
                                     "flash_chunked_loss_bf16"])
def test_loss_logits_and_gradients_match_the_float32_reference(variant):
    """Float32: loss to 2e-5, tail logits to 5e-4 of their largest, and on
    the cell's path (the chunked scan at two groups, flash kernels at 2
    query heads a key-value head, the held dispatch's ``relu2`` kernels
    over the latent, chunked loss, every layer rematted, the scan over the
    period of a routed and a Mamba layer) every gradient to 3e-3 of its
    largest: the Mamba mixer's eight leaves, the attention's four, the
    router, the latent's two projections, the held and the shared experts;
    the selection bias's gradient is zero on both sides. bfloat16 weights
    (what the engine computes with) at the init's own scale against the
    float32 reference on the same weights, over the positions its mask
    counts: loss to 0.5%, logits to 5% of their largest and 2% rms."""
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
    nemotron_control.py`` plants does not: 21 experts for 22, the scaling
    of 5 left out, weights not renormalised, ``relu`` for ``relu^2``, a
    SiLU-gated body, the shared expert left out, every head reading group
    0's B and C, the gated norm over all channels, the softmax scale
    1 / head_dim."""
    params, tokens, targets, (want_loss, want_tail, counted), _ = right(
        "nemotron_h")
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


# ---- the shares add up -----------------------------------------------------
def _normal(rng, *shape, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)


def _mamba_shares(rng, h):
    """(the uncut layer's result, [a share's]): 16 heads of 16 in 8 groups
    by the reference; 4 shares of 4 heads in 2 groups by the program, each
    with its heads' columns of W_in, taps, A, D, dt_bias and norm weight
    and its rows of W_out."""
    d, nh, hd, g, n = 64, 16, 16, 8, 16
    inner, gn = nh * hd, g * n
    p = {"w_in": _normal(rng, d, 2 * inner + 2 * gn + nh, scale=0.2),
         "conv_w": _normal(rng, 4, inner + 2 * gn, scale=0.4),
         "conv_b": _normal(rng, inner + 2 * gn, scale=0.3),
         "dt_bias": _normal(rng, nh), "D": _normal(rng, nh),
         "A_log": jnp.log(jnp.asarray(rng.uniform(1, 16, nh), jnp.float32)),
         "norm": 1 + _normal(rng, inner, scale=0.3),
         "w_out": _normal(rng, inner, d, scale=0.1)}
    want = arch.mamba_mixer(p, h, heads=nh, head_dim=hd, groups=g, state=n,
                            eps=1e-5)
    model = _tiny(mamba_num_heads=nh // 4, n_groups=g // 4, remat=False)
    shares = []
    for r in range(4):
        heads = np.arange(r * nh // 4, (r + 1) * nh // 4)
        x = np.arange(r * inner // 4, (r + 1) * inner // 4)
        bc = np.arange(r * gn // 4, (r + 1) * gn // 4)
        conv = np.concatenate([x, inner + bc, inner + gn + bc])
        cols = np.concatenate([x, inner + conv, 2 * inner + 2 * gn + heads])
        share = {"w_in": p["w_in"][:, cols], "conv_w": p["conv_w"][:, conv],
                 "conv_b": p["conv_b"][conv], "norm": p["norm"][x],
                 "w_out": p["w_out"][x],
                 **{k: p[k][heads] for k in ("dt_bias", "D", "A_log")}}
        shares.append(model._mamba(share, h, chunk_ssd, L.short_conv))
    return want, shares


def _attention_shares(rng, h):
    """8 query heads of 16 on 2 key-value heads by the reference; 4 shares
    of 2 query heads with the ONE key-value head they read by the program."""
    d, nh, nkv, hd = 64, 8, 2, 16
    p = {"wq": _normal(rng, d, nh * hd, scale=0.5),
         "wk": _normal(rng, d, nkv * hd, scale=0.5),
         "wv": _normal(rng, d, nkv * hd, scale=0.3),
         "wo": _normal(rng, nh * hd, d, scale=0.1)}
    want = arch.attention_mixer(p, h, heads=nh, kv_heads=nkv,
                                scale=hd ** -0.5)
    shares = []
    for r in range(4):
        q = slice(r * nh // 4 * hd, (r + 1) * nh // 4 * hd)
        kv = slice(r // 2 * hd, (r // 2 + 1) * hd)
        share = {"wq": p["wq"][:, q], "wk": p["wk"][:, kv],
                 "wv": p["wv"][:, kv], "wo": p["wo"][q]}
        shares.append(grouped_query_attention(
            share, h, L.dot_product_attention, heads=nh // 4, kv_heads=1,
            head_dim=hd))
    return want, shares


def _routed_shares(rng, h):
    """64 experts top 6 x 5 in a latent of 16 beside a shared expert by the
    reference; 4 shares of 16 experts by ``moe_ffn_held`` as the family
    calls it, each through the whole W_up, and the shared expert ONCE."""
    d, lat, f, e, k = 64, 16, 32, 64, 6
    p = {"router": _normal(rng, d, e, scale=d ** -0.5),
         "router_bias": _normal(rng, e, scale=0.05),
         "latent": {"w_dn": _normal(rng, d, lat, scale=0.2),
                    "w_up": _normal(rng, lat, d, scale=0.2)},
         "experts": {"w_up": _normal(rng, e, lat, f, scale=0.3),
                     "w_down": _normal(rng, e, f, lat, scale=0.3)},
         "shared": {"w_up": _normal(rng, d, 3 * f, scale=0.2),
                    "w_down": _normal(rng, 3 * f, d, scale=0.2)}}
    want = arch.routed(p, h[0], top_k=k, first=0, renormalise=True,
                       scaling=5)[0][None]
    shares = [sharded_moe._ffn_rows(h[0], p["shared"], "relu2")[None]]
    for first in range(0, e, 16):
        held = {name: w[first:first + 16]
                for name, w in p["experts"].items()}
        y, counts = sharded_moe.moe_ffn_held(
            h, p["router"], p["router_bias"], held, None, k=k,
            first_expert=first, scaling=5.0, body="relu2",
            latent=p["latent"])
        assert int(counts["load"].sum()) == h.shape[1] * k
        shares.append(y)
    return want, shares


@pytest.mark.parametrize("kind", ["mamba", "attn", "moe"])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(kind):
    """What ties the chip's share to the model: for each kind of layer,
    the partial results of ALL the shares the configuration's deployment
    names (4 head shares of a Mamba layer with 8 groups, each with two
    groups and its own gated norm a group; 4 of an attention layer with 2
    key-value heads, each with the one it reads; the expert shares of a
    routed layer through W_up, which has no bias) add up to what the
    float32 reference gives for the whole layer, the shared expert
    counted once."""
    rng = np.random.default_rng(0)
    h = _normal(rng, 1, 96, 64)
    with jax.default_matmul_precision("highest"):
        want, shares = {"mamba": _mamba_shares, "attn": _attention_shares,
                        "moe": _routed_shares}[kind](rng, h)
        total = sum(shares)
    assert len(shares) == (5 if kind == "moe" else 4)
    assert _err(shares[0], want) > 0.1      # a share is not the layer
    _close(total, want, 5e-5, f"the {kind} shares' sum")


# ---- the kernel pair -------------------------------------------------------
@pytest.mark.parametrize("body", ["swiglu", "relu2"])
def test_the_held_experts_kernels_match_jax_numpy_with_gradients(body):
    """``held_experts_ffn`` through the interpreted kernel pair in either
    body against the dense ``jax.numpy`` sum over the held experts: the
    result, and the gradients of x, the routing weights and every expert
    weight, in float32; a second trip of the sweep (a chunk of one row
    tile) gives the same."""
    rng = np.random.default_rng(1)
    n, d, f, e_all, held, k = 64, 32, 48, 16, 4, 3
    x = _normal(rng, n, d)
    idx = jnp.asarray(np.stack([rng.permutation(e_all)[:k]
                                for _ in range(n)]), jnp.int32)
    w = jnp.asarray(rng.uniform(0.2, 1.0, (n, k)), jnp.float32)
    experts = {name: _normal(rng, held, *((d, f) if name != "w_down"
                                          else (f, d)), scale=0.2)
               for name in grouped_matmul.BODIES[body]}
    ct = _normal(rng, n, d)

    def dense(x, w, experts):
        gates = jnp.sum(w[..., None] * (idx[..., None] == jnp.arange(held)),
                        axis=1)
        out = 0.0
        for j in range(held):
            one = {name: v[j] for name, v in experts.items()}
            out = out + gates[:, j, None] * sharded_moe._ffn_rows(
                x, one, body)
        return jnp.sum(out * ct)

    def kernels(chunk):
        return lambda x, w, experts: jnp.sum(sharded_moe.held_experts_ffn(
            x, idx, w, experts, 0, 16, True, chunk, body)[0] * ct)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(dense, argnums=(0, 1, 2)))(
            x, w, experts)
        for chunk in (None, 16):
            got = jax.jit(jax.value_and_grad(kernels(chunk),
                                             argnums=(0, 1, 2)))(x, w, experts)
            assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(
                float(want[0]))
            for g, v in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
                _close(g, v, 2e-4, f"{body} at chunk {chunk}")


# ---- the configuration, the counts, the plan -------------------------------
def test_the_configuration_file_builds_the_published_model():
    """``lib/modelspec.py`` holds the model as built to every published
    key of the file (``arch.WIDTHS``); a preset that drifts fails the run;
    the counts are ISSUE 66's arithmetic: 773,582,304 parameters at the
    cell's keys, a kind at a time."""
    model = modelspec.build_model(CONFIG, arch, {})
    c = model.config
    mamba = (4096 * 4640 + 5 * 2560 + 3 * 32 + 2048 + 2048 * 4096 + 4096)
    attn = 2 * 4096 * 128 * (8 + 1) + 4096
    routed = (4096 + 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
              + 8 * 2 * 1024 * 2688)
    assert (mamba, attn, routed) == (27413088, 9441280, 98570752)
    assert routed - 8 * 5505024 == 54530560
    assert c.num_params() == 773582304 == (
        5 * mamba + 5 * routed + attn + 2 * 16384 * 4096 + 4096)
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    assert n == c.num_params()
    assert c.layer_kinds() == ["moe", "mamba"] * 5 + ["attn"]
    assert (model.lead, model.period, model.repeats, model.left) == (
        0, 2, 5, 1)
    shape = c.mamba_shape()
    assert (shape.inner, shape.conv_width, shape.norm_groups) == (
        2048, 2560, 2)
    m = modelspec.reference_model(arch, model)
    assert m["n_routed_experts"] == m["num_experts"] == 8
    assert m["num_routed_experts"] == 512 and m["head_dim"] == 128
    assert c.flops_per_token(8192) == pytest.approx(
        arch.train_flops_per_token(m, 8192), rel=0.01)
    for key, bad in (("mamba_num_heads", 64), ("n_groups", 1),
                     ("ssm_state_size", 64), ("moe_latent_size", 2048),
                     ("moe_shared_expert_intermediate_size", 2688),
                     ("num_experts_per_tok", 8), ("head_dim", 64),
                     ("mlp_hidden_act", "silu"), ("chunk_size", 256),
                     ("routed_scaling_factor", 2.5)):
        drifted = json.loads(json.dumps(CONFIG))
        drifted[key] = bad
        with pytest.raises(ValueError, match=key):
            modelspec.build_model(drifted, arch, {})
    whole = NemotronH(size="3-super-120b-a12b").config
    assert 120.0e9 < whole.num_params() < 121.0e9           # "120B"
    assert 12.0e9 < whole.num_active_params() < 13.0e9      # "A12B"
    kinds = whole.layer_kinds()
    assert [kinds.count(k) for k in ("mamba", "moe", "attn")] == [40, 40, 8]
    assert whole.hybrid_override_pattern[26:37] == "EMEMEMEMEM*" == \
        CONFIG["hybrid_override_pattern"]
    assert stack_plan(kinds, 0)[0] * stack_plan(kinds, 0)[1] > 0
    small = _tiny()
    assert small.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(small.init, jax.random.PRNGKey(0))))
    assert (small.lead, small.period, small.repeats, small.left) == (
        0, 2, 2, 1)


def test_the_hand_count_of_train_flops_and_the_kernels_costs():
    """``train_flops_per_token`` of the cell's cut at 8192, part by part,
    against the widths written out (ISSUE 66: about 1.0 GFLOP a token
    forward, the shared experts 44%); ``moe_call_cost`` at SIX matmul
    units a row of 1024 x 2688, ``ssd_call_cost`` at 32 heads in 2 groups,
    ``flash_call_cost`` at 8 query heads on 1 key-value head of 128."""
    model = modelspec.build_model(CONFIG, arch, {})
    m = modelspec.reference_model(arch, model)
    s, d = 8192, 4096
    parts = arch.forward_flops_per_token(m, s)
    assert parts["mamba_projections"] == 5 * (
        2 * (d * 4640 + 2048 * d) + 2 * 4 * 2560)
    assert parts["ssd_state"] == 5 * 4 * 32 * 64 * 128
    assert parts["attention_projections"] == 2 * (2 * d * 1024 + 2 * d * 128)
    assert parts["attention"] == 4 * 128 * 8 * (s + 1) / 2
    assert parts["router_and_latent"] == 5 * 2 * (d * 512 + 2 * d * 1024)
    assert parts["shared_experts"] == 5 * 4 * d * 5376
    assert parts["held_experts"] == 5 * 4 * 1024 * 2688 * 22 * 8 / 512
    assert parts["head"] == 2 * d * 16384
    assert arch.train_flops_per_token(m, s) == 3 * parts["total"]
    assert 1.00e9 < parts["total"] < 1.03e9
    assert 0.43 < parts["shared_experts"] / parts["total"] < 0.45
    assert parts["held_experts"] / parts["total"] < 0.02
    rows = 100
    fwd = arch.moe_call_cost(m, 1, s, backward=False, rows=rows)
    bwd = arch.moe_call_cost(m, 1, s, backward=True, rows=rows)
    assert fwd["flops"] == 5 * rows * 2 * 2 * 1024 * 2688
    assert bwd["flops"] == 2 * fwd["flops"]         # 2 + 4 = six units
    assert fwd["bytes"] == 5 * (8 * 2 * 1024 * 2688 * 2 + 2 * rows * 2048)
    assert arch.held_share(m) == 22 * 8 / 512
    assert s * arch.held_share(m) / 8 == 352
    ssd = arch.ssd_call_cost(m, 1, s, backward=False)
    assert ssd["flops"] == 5 * 4 * 32 * 64 * 128 * s
    assert ssd["bytes"] == 5 * s * (32 * (2 * 64 * 2 + 4) + 2 * 2 * 128 * 2)
    flash = arch.flash_call_cost(m, 1, s, backward=True)
    assert flash["flops"] == 5 * 2 * 128 * 8 * s * (s + 1) // 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert arch.least_seconds(ssd, peaks)[1] == "memory"
    assert arch.least_seconds(flash, peaks)[1] == "compute"


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
                dict(activation="swiglu"), dict(n_group=8),
                dict(moe_num_shared_experts=2)):
        with pytest.raises(NotImplementedError, match="sigmoid router"):
            _tiny(**bad)
    for bad in (dict(moe_held_experts=1024), dict(n_groups=3),
                dict(num_kv_heads=3),
                dict(hybrid_override_pattern="EM-M*"),
                dict(hybrid_override_pattern="EM")):
        with pytest.raises(ValueError):
            _tiny(**bad)
    with pytest.raises(TypeError):
        _tiny(kv_lora_rank=32)          # no field of THIS family
    assert get_model_class("nemotron_h") is NemotronH
    assert model.optimizer_frozen() == r"router_bias$"
    # a share of the heads is no expand x hidden: nothing checks it
    assert _tiny(mamba_num_heads=2, n_groups=1).config.mamba_shape(
        ).inner == 32
