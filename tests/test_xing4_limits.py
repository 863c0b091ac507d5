"""Xing4 (ISSUE 56) held to the limits of its cell's configuration on the
CPU at the tiny preset: each planted departure from the equations
(``benchmark/tests/mhc_control.py``, the same it plants on the chip) fails
the benchmark's own decision where the right program passes; the eight
shares of a routed layer add up to the uncut layer THROUGH the stream
pass behind it, the shared expert counted once; the configuration file
builds the published model; what the family refuses. A CPU run shows
results and counts, never a time."""

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import Xing4, get_model_class
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.ops import layers as L
from deepspeed_tpu.ops import mhc

from helpers.families import config_of, right, tiny
from helpers.families import (BENCH, _close,  # noqa: F401
                               _telemetry_isolation)

CONFIG = config_of("xing4_0")
_tiny = functools.partial(tiny, "xing4_0")

if str(BENCH / "tests") not in sys.path:
    sys.path.insert(0, str(BENCH / "tests"))
from architectures import xing4 as arch  # noqa: E402  (benchmark/, on
#                                      sys.path by families)
from kinds import train_job  # noqa: E402
from lib import modelspec  # noqa: E402
from mhc_control import FAULTS, plant, with_planted_logit  # noqa: E402


# ---- planted faults, through the benchmark's own decision ------------------
@pytest.mark.parametrize("fault", [None, *FAULTS], ids=lambda f: f or "none")
def test_the_cells_limits_catch_a_planted_fault(fault):
    """The benchmark's own decision (``kinds/train_job.py`` ``decide`` at
    the configuration's ``check``, over the positions the reference's mask
    counts) on the program's tail logits and loss against the
    reference's: the program passes, each departure
    ``benchmark/tests/mhc_control.py`` plants does not. The dropped clamp
    is judged on weights with a res logit planted past it (100), which
    the right program clamps and passes on too."""
    params, tokens, targets, want, m = right("xing4_0")
    model = _tiny()
    programs = {fault: model if fault is None else plant(model, fault)}
    if fault == "clamp_dropped":
        params = with_planted_logit(params)
        with jax.default_matmul_precision("highest"):
            want = arch.reference(params, tokens, targets, m, 32)
        programs[None] = model
    want_loss, want_tail, counted = want
    for name, program in programs.items():
        @jax.jit
        def run(params, tokens, targets, program=program):
            logits = program.apply(params, tokens)
            return logits[:, -32:], L.cross_entropy_loss(logits, targets)

        with jax.default_matmul_precision("highest"):
            got_tail, got_loss = run(params, tokens, targets)
        numbers = train_job.tail_numbers(got_tail, want_tail, counted)
        ok = train_job.decide(numbers, want_loss, float(got_loss),
                              CONFIG["check"])
        assert ok == (name is None), (name, numbers)
        assert numbers["positions_counted"] >= 8
        if name is None:
            assert numbers["logits_err_max"] < 1e-4 > numbers["loss_err"]


# ---- the shares add up -----------------------------------------------------
def test_the_eight_shares_of_a_routed_layer_add_up_through_the_stream_pass():
    """``moe_ffn_held`` as the family calls it (sigmoid, renormalised top 4
    times 2, a shared expert): the eight shares of 8 experts, each pushed
    through ``mhc_post`` on ITS share of the streams' update, sum to what
    the float32 reference's sublayer gives with all 64 held, when the
    shared expert and the streams' own part ``H_res X`` are counted ONCE:
    ``mhc_post`` is linear in ``y``, so a share's ``X'`` less ``H_res X``
    is its part of ``H_post^T y``."""
    rng = np.random.default_rng(0)
    d, f, e, k, t, n = 64, 32, 64, 4, 96, 4
    f32 = jnp.float32
    normal = lambda *s, scale=1.0: jnp.asarray(  # noqa: E731
        rng.normal(size=s) * scale, f32)
    x = normal(1, t, n, d)
    hc = {"phi": normal(n * d, n * (n + 2), scale=(n * d) ** -0.5),
          "b": normal(n * (n + 2), scale=0.5),
          "alpha": jnp.asarray([0.5, 0.35, 0.65], f32)}
    p = {"router": normal(d, e, scale=d ** -0.5),
         "router_bias": normal(e, scale=0.05),
         "experts": {"w_gate": normal(e, d, f, scale=0.2),
                     "w_up": normal(e, d, f, scale=0.2),
                     "w_down": normal(e, f, d, scale=0.2)},
         "shared": {"w_gate": normal(d, f, scale=0.2),
                    "w_up": normal(d, f, scale=0.2),
                    "w_down": normal(f, d, scale=0.2)}}
    hyper = dict(eps=1e-6, clamp=(-30.0, 30.0), iters=20)
    with jax.default_matmul_precision("highest"):
        want, = arch.hyper_sublayer(
            x, hc, lambda u: (arch.routed(
                p, u[0], top_k=k, first=0, renormalise=True,
                scaling=2.0)[0][None],), **hyper)
        u, h_post, h_res, _, _ = mhc.mhc_pre(x, hc["phi"], hc["b"],
                                          hc["alpha"], **hyper)
        zero = jnp.zeros_like(u)
        own = mhc.mhc_post(x, zero, h_post, h_res)          # H_res X
        shared = sharded_moe._swiglu_rows(
            u[0], p["shared"]["w_gate"], p["shared"]["w_up"])[2] \
            @ p["shared"]["w_down"]
        total = own + (mhc.mhc_post(x, shared[None], h_post, h_res) - own)
        for first in range(0, e, 8):
            share = {name: w[first:first + 8]
                     for name, w in p["experts"].items()}
            y, counts = sharded_moe.moe_ffn_held(
                u, p["router"], p["router_bias"], share, None, k=k,
                first_expert=first, scaling=2.0)
            total = total + (mhc.mhc_post(x, y, h_post, h_res) - own)
            assert int(counts["load"].sum()) == t * k
    _close(total, want, 2e-5, "the shares' sum through mhc_post")


# ---- the configuration, the counts, the plan -------------------------------
def test_the_configuration_file_builds_the_published_model():
    """``lib/modelspec.py`` holds the model as built to every published
    key of the file (``arch.WIDTHS``; the ``hc_*`` / ``mhc_*`` keys,
    ``q_lora_rank`` and ``rope_scaling`` among them); a preset that drifts
    fails the run; the counts are the configuration file's arithmetic."""
    model = modelspec.build_model(CONFIG, arch, {})
    c = model.config
    mla = 2752512 + 4718592 + 2064384 + 4194304 + 14680064 + 1280
    routed = (mla + 7168 + 688182 + 229376 + 64 + 11010048 + 88080384)
    assert routed == 128426358
    assert c.num_params() == 631149528 == 4 * routed + 117440512 + 3584
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    assert n == c.num_params()
    assert c.layer_kinds() == [("mla", "routed")] * 4
    assert (model.lead, model.period, model.repeats, model.left) == (
        0, 1, 4, 0)
    assert model._rope[0].shape == (8192, 32)       # the rotated 64
    assert c.softmax_mscale == pytest.approx(1.4159, abs=1e-4)
    assert c.rope_table_scaling()["attention_factor"] == 1.0
    m = modelspec.reference_model(arch, model)
    assert m["n_routed_experts"] == m["num_experts"] == 8
    assert m["num_routed_experts"] == 64
    assert m["rope_scaling"] == CONFIG["rope_scaling"]
    assert c.flops_per_token(8192) == pytest.approx(
        arch.train_flops_per_token(m, 8192), rel=0.01)
    for key, bad in (("hc_mult", 2), ("hc_sinkhorn_iters", 10),
                     ("mhc_h_res_clamp_max", 10), ("q_lora_rank", 1536),
                     ("num_experts_per_tok", 8), ("first_k_dense_replace", 1),
                     ("rope_scaling", {**CONFIG["rope_scaling"],
                                       "factor": 32})):
        drifted = json.loads(json.dumps(CONFIG))
        drifted[key] = bad
        with pytest.raises(ValueError, match=key):
            modelspec.build_model(drifted, arch, {})
    # the cut ISSUE 56 reckoned first and the AOT reading left out
    five = Xing4(size="29b-a4b", **{
        **CONFIG["program"]["model_overrides"], "first_k_dense_replace": 1},
        num_layers=5).config
    assert five.num_params() == 759346446
    whole = Xing4(size="29b-a4b").config
    assert 28.5e9 < whole.num_params() < 30.5e9             # "29B"
    assert 3.5e9 < whole.num_active_params() < 4.6e9        # "A4B"
    kinds = whole.layer_kinds()
    assert kinds[:3] == [("mla", "dense")] * 2 + [("mla", "routed")]
    assert stack_plan(kinds, 2) == (1, 38, 0)
    tiny = Xing4(size="tiny", moe_held_experts=8)
    assert tiny.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(tiny.init, jax.random.PRNGKey(0))))
    assert (tiny.lead, tiny.period, tiny.repeats, tiny.left) == (1, 1, 4, 0)


def test_the_hand_count_of_train_flops_per_token():
    """``train_flops_per_token`` of the cell's cut at 8192, part by part,
    against the widths written out."""
    model = modelspec.build_model(CONFIG, arch, {})
    m = modelspec.reference_model(arch, model)
    parts = arch.forward_flops_per_token(m, 8192)
    d, n, nh = 3584, 4, 32
    assert parts["mla_projections"] == 4 * 2 * (
        d * 768 + 768 * nh * 192 + d * 576 + 512 * nh * 256 + nh * 128 * d)
    assert parts["mla_attention"] == 4 * 2 * (192 + 128) * nh * 8193 / 2
    assert parts["hyper_connections"] == 8 * (
        2 * n * d * 24 + 2 * 24 * d)
    assert parts["dense_ffn"] == 0
    assert parts["routed_layers"] == 4 * (
        2 * d * 64 + 6 * d * 1024 + 6 * d * 1024 * 4 * 8 / 64)
    assert parts["head"] == 2 * d * 16384
    assert arch.train_flops_per_token(m, 8192) == 3 * parts["total"]
    # the kernels' costs: one forward and one backward a sublayer
    pre = arch.mhc_pre_call_cost(m, 1, 8192, backward=False)
    assert pre["bytes"] == 8 * ((8192 * 5 * d) * 2 + 8192 * 24 * 4
                                + n * d * 24 * 2)
    post = arch.mhc_post_call_cost(m, 1, 8192, backward=True)
    assert post["bytes"] == 8 * ((8192 * 14 * d) * 2 + 2 * 8192 * 20 * 4)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for cost in (pre, post):
        assert arch.least_seconds(cost, peaks)[1] == "memory"


def test_what_the_family_refuses():
    """Serving and the pipeline by mechanism, and a config the layer
    equations do not cover."""
    model = _tiny()
    for entry in (model.block, model.block_decode, model.decode,
                  model.init_cache):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            entry()
    for bad in (dict(moe_router_activation="softmax"),
                dict(tie_embeddings=True), dict(hc_mult=1),
                dict(q_lora_rank=0),
                dict(rope_scaling={"type": "linear", "factor": 2})):
        with pytest.raises(NotImplementedError, match="sigmoid router"):
            _tiny(**bad)
    with pytest.raises(ValueError, match="held"):
        _tiny(moe_held_experts=128)
    with pytest.raises(TypeError):
        _tiny(kda_head_dim=64)      # no field of THIS family
    assert get_model_class("xing4_0") is Xing4
    assert model.optimizer_frozen() == r"router_bias$"
    # the published start: alpha 0.01, a static b_res of the identity, the
    # latent norms from 1
    p = Xing4(size="tiny").init(jax.random.PRNGKey(0))
    layer = p["layers"]["lead"]["0"]
    assert np.allclose(np.asarray(layer["hc1"]["alpha"], np.float32), 0.01)
    assert np.allclose(np.asarray(layer["hc2"]["b"], np.float32)[8:],
                       np.eye(4).reshape(-1))
    assert np.all(np.asarray(layer["mla"]["q_norm"]) == 1)
    assert layer["hc1"]["phi"].shape == (256, 24)
    assert "lm_head" in p
