"""Qwen3-Next (ISSUE 46): Gated DeltaNet layers (a gate a HEAD through the
delta rule's kernels, fewer key heads than value heads, a SiLU-gated norm)
to one gated attention layer (QK-norm, a quarter of the head rotated, a
sigmoid gate on the output), each with a held share of softmax-routed
experts beside a gated shared expert, checked on the CPU at tiny sizes
against the plain float32 reference the benchmark keeps
(``benchmark/architectures/qwen3_next.py``, which imports nothing from the
program). The scan with a gate a head and the held share are
``tests/test_qwen3_next_scan.py``'s, the engine and the scopes
``tests/test_qwen3_next_engine.py``'s, the whole model's loss, logits and
gradients against the reference ``tests/test_qwen3_next_reference.py``'s
(PR 50; a file is one worker's under ``--dist loadfile``). A CPU run shows
results and counts, never a time."""

import functools
import json
import sys

import jax
import numpy as np
import pytest

from deepspeed_tpu.models import Qwen3Next, get_model_class
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.ops import layers as L

from helpers.families import config_of, right, tiny
from helpers.families import (BENCH, _telemetry_isolation)  # noqa: F401

CONFIG = config_of("qwen3_next")
_tiny = functools.partial(tiny, "qwen3_next")

if str(BENCH / "tests") not in sys.path:
    sys.path.insert(0, str(BENCH / "tests"))
from architectures import qwen3_next as arch  # noqa: E402  (benchmark/, on
#                                           sys.path by families)
from gdn_control import FAULTS, plant  # noqa: E402
from kinds import train_job  # noqa: E402
from lib import modelspec  # noqa: E402


# ---- planted faults, through the benchmark's own decision ------------------
@pytest.mark.parametrize("fault", [None, *FAULTS], ids=lambda f: f or "none")
def test_the_cells_limits_catch_a_planted_fault(fault):
    """The benchmark's own decision (``kinds/train_job.py`` ``decide`` at
    the configuration's ``check``, over the positions the reference's mask
    counts) on the program's tail logits and loss against the
    reference's: the program passes, each departure
    ``benchmark/tests/gdn_control.py`` plants (the same it plants on the
    chip) does not."""
    params, tokens, targets, (want_loss, want_tail, counted), _ = (
        right("qwen3_next"))
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


# ---- the configuration, the counts, the plan -------------------------------
def test_the_configuration_file_builds_the_published_model():
    """``lib/modelspec.py`` holds the model as built to every published
    key of the file (``arch.WIDTHS``); a preset that drifts fails the run;
    the counts are ISSUE 46's arithmetic."""
    model = modelspec.build_model(CONFIG, arch, {})
    c = model.config
    gdn, attn, moe = 33718464, 27263488, 104859648
    assert moe == 1048576 + 3147776 + 32 * 3145728
    assert c.num_params() == 625667136 == (
        3 * gdn + attn + 4 * (moe + 4096) + 77791232 + 2048)
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    assert n == c.num_params()
    sixteen = Qwen3Next(size="80b-a3b", num_layers=4, vocab_size=18992,
                        moe_held_experts=16)
    assert sixteen.config.num_params() == 424340544
    assert c.layer_kinds() == ["linear_attention"] * 3 + ["full_attention"]
    assert (model.lead, model.period, model.repeats, model.left) == (
        0, 1, 3, 1)     # three Gated DeltaNet layers under one scan
    assert c.head_dim == 256 != c.hidden_size // c.num_heads
    assert c.rotary_dim == 64
    m = modelspec.reference_model(arch, model)
    assert m["num_experts"] == 32 and m["num_routed_experts"] == 512
    assert c.flops_per_token(16384) == pytest.approx(
        arch.train_flops_per_token(m, 16384), rel=0.01)
    for key, bad in (("head_dim", 128), ("linear_num_key_heads", 32),
                     ("partial_rotary_factor", 0.5),
                     ("num_experts_per_tok", 8),
                     ("shared_expert_intermediate_size", 0)):
        drifted = json.loads(json.dumps(CONFIG))
        drifted[key] = bad
        with pytest.raises(ValueError, match=key):
            modelspec.build_model(drifted, arch, {})
    whole = Qwen3Next(size="80b-a3b").config
    assert 79e9 < whole.num_params() < 82e9                 # "80B"
    assert 2.8e9 < whole.num_active_params() < 4e9          # "A3B"
    assert whole.layer_kinds().count("full_attention") == 12
    assert stack_plan(whole.layer_kinds(), 0) == (4, 12, 0)
    tiny = _tiny()
    assert tiny.config.num_params() == sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(tiny.init, jax.random.PRNGKey(0))))


def test_what_the_family_refuses():
    """Serving and the pipeline by mechanism (a recurrent state and a
    convolution's tail have no cache; a stack of kinds has no single
    block), and a config the layer equations do not cover."""
    model = _tiny()
    for entry in (model.block, model.block_decode, model.decode,
                  model.init_cache):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            entry()
    from deepspeed_tpu.runtime.pipe.pipelined_model import PipelinedDecoderLM
    piped = PipelinedDecoderLM(model, None, 2, 2)
    with pytest.raises(NotImplementedError, match="no single block"):
        piped.inner.block(None, None)
    with pytest.raises(NotImplementedError, match="softmax router"):
        _tiny(moe_router_activation="sigmoid")
    with pytest.raises(NotImplementedError, match="mlp_only_layers"):
        _tiny(mlp_only_layers=[0])
    with pytest.raises(ValueError, match="value heads"):
        _tiny(linear_num_key_heads=3)
    with pytest.raises(ValueError, match="rotary_pct"):
        _tiny(rotary_pct=0.0)
    with pytest.raises(ValueError, match="held"):
        _tiny(moe_held_experts=1024)
    assert get_model_class("qwen3_next") is Qwen3Next
    # the published init: w_q and w_k from 0, every (1 + w) norm from 0
    p = Qwen3Next(size="tiny").init(jax.random.PRNGKey(0))
    assert not np.any(p["layers"]["tail"]["0"]["attn"]["q_norm"])
    assert not np.any(p["final_norm"]["scale"])
    assert np.all(np.asarray(p["layers"]["period"]["0"]["gdn"]["o_norm"])
                  == 1)


