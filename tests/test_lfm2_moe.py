"""LFM2-MoE (ISSUE 54): gated short-convolution layers to one QK-norm
grouped-query attention layer, a leading dense layer, and a held share of
bias-corrected sigmoid-routed experts with no shared expert, checked on the
CPU at tiny sizes against the plain float32 reference the benchmark keeps
(``benchmark/architectures/lfm2_moe.py``, which imports nothing from the
program). The kernel pair is ``tests/test_gated_short_conv.py``'s, the
engine and the scopes ``tests/test_lfm2_moe_engine.py``'s, the whole
model's loss, logits and gradients against the reference
``tests/test_lfm2_moe_reference.py``'s (a file is one worker's under
``--dist loadfile``). A CPU run shows results and counts, never a time."""

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import Lfm2Moe, get_model_class
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.ops import layers as L

from helpers.families import config_of, right, tiny
from helpers.families import (BENCH, _close,  # noqa: F401
                               _telemetry_isolation)

CONFIG = config_of("lfm2_moe")
_tiny = functools.partial(tiny, "lfm2_moe")

if str(BENCH / "tests") not in sys.path:
    sys.path.insert(0, str(BENCH / "tests"))
from architectures import lfm2_moe as arch  # noqa: E402  (benchmark/, on
#                                         sys.path by families)
from kinds import train_job  # noqa: E402
from lfm_control import FAULTS, plant  # noqa: E402
from lib import modelspec  # noqa: E402


# ---- planted faults, through the benchmark's own decision ------------------
@pytest.mark.parametrize("fault", [None, *FAULTS], ids=lambda f: f or "none")
def test_the_cells_limits_catch_a_planted_fault(fault):
    """The benchmark's own decision (``kinds/train_job.py`` ``decide`` at
    the configuration's ``check``, over the positions the reference's mask
    counts) on the program's tail logits and loss against the
    reference's: the program passes, each departure
    ``benchmark/tests/lfm_control.py`` plants (the same it plants on the
    chip) does not."""
    params, tokens, targets, (want_loss, want_tail, counted), _ = right("lfm2_moe")
    model = _tiny()
    if fault is not None:
        model = plant(model, fault)

    @jax.jit
    def run(params, tokens, targets):
        logits = model.apply(params, tokens)
        return logits[:, -32:], L.cross_entropy_loss(logits, targets)

    with jax.default_matmul_precision("highest"):
        got_tail, got_loss = run(params, tokens, targets)
    numbers = train_job.tail_numbers(got_tail, want_tail, counted)
    ok = train_job.decide(numbers, want_loss, float(got_loss),
                          CONFIG["check"])
    assert ok == (fault is None), numbers
    assert numbers["positions_counted"] >= 8
    if fault is None:
        assert numbers["logits_err_max"] < 1e-4 > numbers["loss_err"]


# ---- the shares add up -----------------------------------------------------
def test_the_eight_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """``moe_ffn_held`` with the sigmoid router and NO shared expert: the
    eight shares of 8 experts sum to what the float32 reference gives for
    all 64 held at once (there is no shared expert to count once), and a
    token that chose no expert of a share gets exactly nothing from it."""
    rng = np.random.default_rng(0)
    d, f, e, k, n = 64, 32, 64, 4, 96
    f32 = jnp.float32
    h = jnp.asarray(rng.normal(size=(1, n, d)), f32)
    p = {"router": jnp.asarray(rng.normal(size=(d, e)) * d ** -0.5, f32),
         "router_bias": jnp.asarray(rng.normal(size=(e,)) * 0.05, f32),
         "experts": {
             "w_gate": jnp.asarray(rng.normal(size=(e, d, f)) * 0.2, f32),
             "w_up": jnp.asarray(rng.normal(size=(e, d, f)) * 0.2, f32),
             "w_down": jnp.asarray(rng.normal(size=(e, f, d)) * 0.2, f32)}}
    with jax.default_matmul_precision("highest"):
        want, _, _ = arch.routed(p, h[0], top_k=k, first=0, renormalise=True,
                                 scaling=1.0)
        total, loads = 0.0, []
        for first in range(0, e, 8):
            share = {name: w[first:first + 8]
                     for name, w in p["experts"].items()}
            out, counts = sharded_moe.moe_ffn_held(
                h, p["router"], p["router_bias"], share, None, k=k,
                first_expert=first, router="sigmoid", router_grad=False)
            total = total + out[0]
            loads.append(np.asarray(counts["load"]))
            if first == 0:
                idx = np.argsort(-np.asarray(
                    jax.nn.sigmoid(h[0] @ p["router"]) + p["router_bias"]),
                    axis=-1)[:, :k]
                none = (idx >= 8).all(axis=-1)
                assert none.sum() > n // 3      # C(56,4) / C(64,4) = 57.8%
                assert not np.any(np.asarray(out[0])[none])
                assert int(counts["done"]) == int((idx < 8).sum())
    _close(total, want, 1e-5, "the shares' sum")
    assert all((load == loads[0]).all() for load in loads)
    assert loads[0].sum() == n * k


# ---- the configuration, the counts, the plan -------------------------------
def test_the_configuration_file_builds_the_published_model():
    """``lib/modelspec.py`` holds the model as built to every published
    key of the file (``arch.WIDTHS``); a preset that drifts fails the run;
    the counts are ISSUE 54's arithmetic."""
    model = modelspec.build_model(CONFIG, arch, {})
    c = model.config
    conv, attn, norms = 16783360, 10485888, 4096
    dense, routed = 72351744, 131072 + 64 + 8 * 9437184
    assert c.num_params() == 469284992 + 4 * 64 == (
        conv + norms + dense + attn + norms + routed
        + 3 * (conv + norms + routed) + 16777216 + 2048)
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    assert n == c.num_params()
    assert c.layer_kinds() == [("conv", "dense"), ("attn", "moe")] + [
        ("conv", "moe")] * 3
    assert (model.lead, model.period, model.repeats, model.left) == (
        1, 0, 0, 4)
    assert c.head_dim == 64 == c.hidden_size // c.num_heads
    assert model._rope[0].shape == (8192, 32)       # the whole head
    m = modelspec.reference_model(arch, model)
    assert m["num_experts"] == 8 and m["num_routed_experts"] == 64
    assert m["rope_parameters"] == CONFIG["rope_parameters"]
    assert c.flops_per_token(8192) == pytest.approx(
        arch.train_flops_per_token(m, 8192), rel=0.01)
    for key, bad in (("conv_L_cache", 4), ("num_key_value_heads", 4),
                     ("num_experts_per_tok", 8), ("num_dense_layers", 2),
                     ("moe_intermediate_size", 1024),
                     ("rope_parameters", {"rope_theta": 10000,
                                          "rope_type": "default"})):
        drifted = json.loads(json.dumps(CONFIG))
        drifted[key] = bad
        with pytest.raises(ValueError, match=key):
            modelspec.build_model(drifted, arch, {})
    # the two cuts ISSUE 54 reckoned and left out
    sixteen = Lfm2Moe(size="24b-a2b", **{
        **CONFIG["program"]["model_overrides"], "moe_held_experts": 16},
        num_layers=5).config
    assert 770e6 < sixteen.num_params() < 773e6
    whole = Lfm2Moe(size="24b-a2b").config
    assert 23.5e9 < whole.num_params() < 24.5e9             # "24B"
    assert 2.0e9 < whole.num_active_params() < 2.6e9        # "A2B"
    kinds = whole.layer_kinds()
    assert [k[0] for k in kinds].count("attn") == 10
    assert [k[1] for k in kinds].count("dense") == 2
    assert kinds[:3] == [("conv", "dense")] * 2 + [("attn", "moe")]
    assert stack_plan(kinds, 2) == (4, 9, 2)
    tiny = Lfm2Moe(size="tiny", moe_held_experts=8)
    assert tiny.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(tiny.init, jax.random.PRNGKey(0))))
    assert (tiny.lead, tiny.period, tiny.repeats, tiny.left) == (1, 0, 0, 4)


def test_what_the_family_refuses():
    """Serving and the pipeline by mechanism (a convolution's tail has no
    cache; a stack of kinds has no single block), and a config the layer
    equations do not cover."""
    model = _tiny()
    for entry in (model.block, model.block_decode, model.decode,
                  model.init_cache):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            entry()
    from deepspeed_tpu.runtime.pipe.pipelined_model import PipelinedDecoderLM
    piped = PipelinedDecoderLM(model, None, 3, 2)
    with pytest.raises(NotImplementedError, match="no single block"):
        piped.inner.block(None, None)
    for bad in (dict(moe_router_activation="softmax"),
                dict(use_expert_bias=False), dict(conv_bias=True),
                dict(tie_embeddings=False), dict(moe_num_shared_experts=1)):
        with pytest.raises(NotImplementedError, match="sigmoid router"):
            _tiny(**bad)
    with pytest.raises(ValueError, match="layer_types"):
        _tiny(layer_types=["conv", "mamba", "conv", "conv", "conv"])
    with pytest.raises(ValueError, match="held"):
        _tiny(moe_held_experts=128)
    assert get_model_class("lfm2_moe") is Lfm2Moe
    assert model.optimizer_frozen() == r"router_bias$"
    # the published init: every norm, w_q and w_k among them, from 1
    p = Lfm2Moe(size="tiny").init(jax.random.PRNGKey(0))
    attn = p["layers"]["tail"]["0"]["attn"]      # the preset's five layers
    assert np.all(np.asarray(attn["q_norm"]) == 1)
    assert np.all(np.asarray(p["final_norm"]["scale"]) == 1)
    assert "lm_head" not in p       # the head is the table
    taps = np.asarray(p["layers"]["lead"]["0"]["conv"]["taps"])
    assert taps.shape == (3, 64) and np.abs(taps).max() <= 3 ** -0.5
