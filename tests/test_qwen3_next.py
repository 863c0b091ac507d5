"""Qwen3-Next (ISSUE 46): Gated DeltaNet layers (a gate a HEAD through the
delta rule's kernels, fewer key heads than value heads, a SiLU-gated norm)
to one gated attention layer (QK-norm, a quarter of the head rotated, a
sigmoid gate on the output), each with a held share of softmax-routed
experts beside a gated shared expert, checked on the CPU at tiny sizes
against the plain float32 reference the benchmark keeps
(``benchmark/architectures/qwen3_next.py``, which imports nothing from the
program). The scan with a gate a head and the held share are
``tests/test_qwen3_next_scan.py``'s, the engine, the scopes and the five
other families' train steps ``tests/test_qwen3_next_engine.py``'s (a file
is one worker's under ``--dist loadfile``). A CPU run shows results and
counts, never a time."""

import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import Qwen3Next, get_model_class
from deepspeed_tpu.models.stack import stack_plan
from deepspeed_tpu.ops import layers as L

from helpers.family_cases import (_batch, _drop_compiled_programs,  # noqa: F401,E501
                                  _err, _telemetry_isolation)
from helpers.family_cases import qnext_tiny as _tiny

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
for path in (BENCH, BENCH / "tests"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
from architectures import qwen3_next as arch  # noqa: E402
from gdn_control import FAULTS, plant  # noqa: E402
from kinds import train_job  # noqa: E402
from lib import modelspec  # noqa: E402

NAME = "qwen3-next-80b-ep16-zero3-1chip"
CONFIG = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())


def _weights(model, seed=3):
    """Seeded weights under which every part this family adds carries
    weight in the logits: a small embedding under larger values, outputs
    and experts; a shared expert's gate and a decay off their flat middle;
    and every norm weight drawn (they start at 0 or 1, where ``(1 + w)``
    and ``w`` cannot be told from a missing weight)."""
    boost = {"tokens": 0.05, "wv": 4.0, "wo": 8.0, "w_ba": 20.0,
             "w_gate": 6.0, "w_up": 6.0, "w_down": 8.0, "shared_gate": 50.0}
    norms = {"ln1_scale", "ln2_scale", "scale", "q_norm", "k_norm", "o_norm"}
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def one(path, w):
        name = path[-1].key
        if name in norms:
            return w + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype)
        if name == "A_log":         # slow heads too: the state has to matter
            return w - 4.0
        return w * boost.get(name, 1.0)

    return jax.tree_util.tree_map_with_path(
        one, model.init(jax.random.PRNGKey(seed)))


def _ref_loss(params, tokens, targets, m):
    hidden, _ = arch._forward(params, tokens, m)
    return arch.loss_of(hidden, params["lm_head"], targets)


# ---- the whole model against the plain reference ---------------------------
@functools.lru_cache(maxsize=None)
def _right(held: int = 32):
    """Boosted weights with ``held`` of the 512 experts held, a batch, what
    the float32 reference says of them at the cell's own margin (loss,
    tail logits, mask), and the reference's gradient."""
    model = _tiny(moe_held_experts=held)
    params = _weights(model)
    tokens, targets = _batch(model)
    m = modelspec.reference_model(arch, model, CONFIG["check"])
    with jax.default_matmul_precision("highest"):
        want = arch.reference(params, tokens, targets, m, 32)
        grads = jax.grad(_ref_loss)(params, tokens, targets, m)
    return params, tokens, targets, want, grads


# the gradients ISSUE 46 names, by the leaf's path
_NAMED = ("w_qkvz", "w_ba", "A_log", "dt_bias", "o_norm", "conv", "wq",
          "q_norm", "k_norm", "shared_gate", "w_gate", "ln1_scale")


@pytest.mark.parametrize("variant", ["plain_f32", "flash_chunked_loss_f32",
                                     "flash_chunked_loss_bf16"])
def test_loss_logits_and_gradients_match_the_float32_reference(variant):
    """Float32: loss to 2e-5, tail logits to 5e-4 of their largest, and on
    the cell's path (flash kernels, the chunked scan's kernels, chunked
    loss, every layer rematted) every gradient to 3e-3 of its largest; a
    share's routers' gradients are zero on both sides. The gate half of
    ``W_q`` is compared apart from its query half. bfloat16 weights (what
    the engine computes with) at the init's own scale against the float32
    reference on the same weights, over the positions its mask counts:
    loss to 0.5%, logits to 5% of their largest and 2% rms."""
    kw = dict(remat=False) if variant == "plain_f32" else dict(
        attn_impl="flash", loss_chunk=64)
    model = _tiny(**kw)
    params, tokens, targets, (want, want_tail, _), want_g = _right()
    if variant.endswith("bf16"):
        # the init's own weights, as the cell runs them: under the boost a
        # rounding of 2^-9 is amplified past any limit worth holding
        params = model.init(jax.random.PRNGKey(3))
        m = modelspec.reference_model(arch, model, CONFIG["check"])
        with jax.default_matmul_precision("highest"):
            want, want_tail, counted = arch.reference(
                params, tokens, targets, m, 32)
        low = jax.tree_util.tree_map(
            lambda w: w.astype(jnp.bfloat16), params)
        numbers = train_job.tail_numbers(
            model.apply(low, tokens)[:, -32:], want_tail, counted)
        got = float(model.loss(low, (tokens, targets)))
        assert abs(got - want) <= 5e-3 * want
        assert numbers["logits_err_max"] < 5e-2, numbers
        assert numbers["logits_err_rms"] < 2e-2, numbers
        return
    with jax.default_matmul_precision("highest"):
        got_tail = model.apply(params, tokens)[:, -32:]
        if variant == "plain_f32":
            got, got_g = model.loss(params, (tokens, targets)), None
        else:
            got, got_g = jax.value_and_grad(model.loss)(params,
                                                        (tokens, targets))
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
        if name.endswith("['router']"):
            assert not np.any(w) and not np.any(g), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _err(g, w) < 3e-3, name
        if path[-1].key == "wq":        # [D, H, (query | gate)]
            hd = model.config.head_dim
            halves = lambda x: x.reshape(*x.shape[:-1], -1, 2, hd)  # noqa: E731
            for half in (0, 1):
                assert _err(halves(g)[..., half, :],
                            halves(w)[..., half, :]) < 3e-3, (name, half)
    assert set(_NAMED) <= seen


# ---- planted faults, through the benchmark's own decision ------------------
@pytest.mark.parametrize("fault", [None, *FAULTS], ids=lambda f: f or "none")
def test_the_cells_limits_catch_a_planted_fault(fault):
    """The benchmark's own decision (``kinds/train_job.py`` ``decide`` at
    the configuration's ``check``, over the positions the reference's mask
    counts) on the program's tail logits and loss against the
    reference's: the program passes, each departure
    ``benchmark/tests/gdn_control.py`` plants (the same it plants on the
    chip) does not."""
    params, tokens, targets, (want_loss, want_tail, counted), _ = _right()
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


