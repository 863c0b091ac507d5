"""What the test files of the Kimi-Linear, Granite, Mellum, Qwen3-Next,
LFM2-MoE and Xing4 families share (``tests/test_kimi_linear*.py``,
``tests/test_kda*_kernels.py``, ``tests/test_granite_hybrid*.py``,
``tests/test_mellum*.py``, ``tests/test_qwen3_next*.py``,
``tests/test_lfm2_moe*.py``, ``tests/test_short_conv_step.py``; since PR 50 a family's float32 reference
comparison is ``tests/test_<family>_reference.py`` and every family's step
pin a row of ``tests/test_step_pins.py``, which take the engine's
``DS_CONFIG`` from here as the families' engine files do): a file is one
worker's under ``--dist loadfile``, so each family's cases lie in several
files and their fixtures and inputs here. Importing this puts
``benchmark/`` on ``sys.path`` (the references are ``architectures/``'s)."""

import functools
import gc
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.models import (GraniteHybrid, KimiLinear, Lfm2Moe,
                                  Mellum, Qwen3Next, Xing4)

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from architectures import (kimi_linear, lfm2_moe, mellum,  # noqa: E402
                           qwen3_next, xing4)
from lib import modelspec  # noqa: E402

def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


GRANITE_CONFIG = _config("granite-4.0-h-micro-zero3-1chip")
MELLUM_CONFIG = _config("mellum2-12b-ep4-zero3-1chip")
QNEXT_CONFIG = _config("qwen3-next-80b-ep16-zero3-1chip")
LFM_CONFIG = _config("lfm2-24b-ep8-zero3-1chip")
XING_CONFIG = _config("xing4.0-29b-ep8-zero3-1chip")


# the engine every family's tiny model is trained and lowered under: ZeRO-3
# bf16 over every device of the mesh
DS_CONFIG = {
    "train_batch_size": 8, "bf16": {"enabled": True},
    "zero_optimization": {"stage": 3},
    "optimizer": {"type": "AdamW",
                  "params": {"lr": 3e-4, "weight_decay": 0.1}},
    "gradient_clipping": 1.0, "mesh": {"fsdp": -1},
    "steps_per_print": 10 ** 9}


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    telemetry.shutdown()
    yield
    telemetry.shutdown()


@pytest.fixture(autouse=True)
def _drop_compiled_programs():
    """For a file that runs kernels EAGERLY in interpret mode (the two
    KDA kernel files): a call compiles some hundred small programs that no
    cache ever finds again, each a few memory mappings, and a test worker
    that has run such a file passed the kernel's 65530 mappings a process
    and died in XLA's compiler (PR 35). Dropping JAX's caches after a test
    returns them. A file whose cases each go through one ``jax.jit`` does
    not import this (PR 50): it makes every case trace and compile its
    model's ``init`` and every small eager line again."""
    yield
    jax.clear_caches()
    gc.collect()


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-30)


def _close(got, want, tol, what=""):
    err = _err(got, want)
    assert err <= tol, f"{what}: {err} of {float(jnp.max(jnp.abs(want)))}"


def _batch(model, b=2, s=128, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, model.config.vocab_size, (b, s + 1))
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _reference_says(arch, config, model, params):
    """``params``, a batch, what the float32 reference ``arch`` says of them
    at the margin of the cell's own ``check`` (loss, tail logits, mask), and
    the reference's model ``m``: what a family's reference comparison and
    its planted faults are both held to."""
    tokens, targets = _batch(model)
    m = modelspec.reference_model(arch, model, config["check"])
    with jax.default_matmul_precision("highest"):
        want = arch.reference(params, tokens, targets, m, 32)
    return params, tokens, targets, want, m


def _reference_grads(arch, params, tokens, targets, m):
    """The gradient of the float32 reference's loss, for an ``arch`` whose
    head is ``lm_head``; one program (eager, every line of the reference
    compiles alone)."""
    def loss(params, tokens, targets):
        hidden, _ = arch._forward(params, tokens, m)
        return arch.loss_of(hidden, params["lm_head"], targets)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(loss))(params, tokens, targets)


# ---- Kimi-Linear -----------------------------------------------------------
def kimi_tiny(**kw):
    return KimiLinear(size="tiny", moe_held_experts=8, **kw)


def kimi_ref_loss(params, tokens, targets, m):
    hidden, _ = kimi_linear._forward(params, tokens, m)
    return kimi_linear.loss_of(hidden, params["lm_head"], targets)


def _kda_inputs(b=2, s=192, h=3, dk=32, dv=16, seed=0):
    rng = np.random.default_rng(seed)
    l2 = lambda x: x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = l2(rng.normal(size=(b, s, h, dk))) / np.sqrt(dk)
    k = l2(rng.normal(size=(b, s, h, dk)))
    v = rng.normal(size=(b, s, h, dv))
    g = -np.exp(rng.uniform(-6, 0.5, size=(b, s, h, dk)))
    g[..., 0] = -1.6        # a fast channel: -102 over a chunk of 64
    g[..., 1] = -4.0
    beta = 1 / (1 + np.exp(-rng.normal(size=(b, s, h))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _as_bf16(args):
    """q, k, v rounded to bfloat16 as the model hands them in; g and beta
    stay float32."""
    return [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])


def _walk_eqns(jaxpr):
    """Every equation of ``jaxpr``, through its sub-jaxprs (scan, map,
    remat, custom_vjp) but not into a Pallas kernel's body, whose values
    are VMEM."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _walk_eqns(sub)


# ---- Granite 4.0-H ---------------------------------------------------------
def granite_tiny(**kw):
    return GraniteHybrid(size="tiny", **kw)


def granite_weights(model, seed=3):
    """Seeded weights under which every layer carries weight in the
    logits: at the init's own scale the one attention layer adds 0.2% to
    the final hidden state (uniform softmax, a small output projection
    times 0.22), and no check could see a fault in it."""
    boost = {"wq": 16.0, "wk": 16.0, "wv": 8.0, "wo": 8.0}
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w * boost.get(path[-1].key, 1.0),
        model.init(jax.random.PRNGKey(seed)))


# ---- Mellum 2 --------------------------------------------------------------
def mellum_tiny(**kw):
    kw.setdefault("moe_held_experts", 16)
    return Mellum(size="tiny", **kw)


def mellum_weights(model, seed=3):
    """Seeded weights under which the attention layers AND the experts
    carry weight in the logits (``PERF.md`` section 2 found for Granite
    that at the init's own scale a softmax is near uniform and a layer's
    output projection small, so no check could see a fault in the layer):
    sharper scores, larger values, larger experts."""
    boost = {"tokens": 0.02, "wq": 4.0, "wk": 4.0, "wv": 8.0, "wo": 8.0,
             "w_gate": 6.0, "w_up": 6.0, "w_down": 8.0}
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w * boost.get(path[-1].key, 1.0),
        model.init(jax.random.PRNGKey(seed)))


@functools.lru_cache(maxsize=None)
def mellum_right(held: int = 16):
    """``_reference_says`` of the right model's boosted weights with
    ``held`` of the 64 experts held."""
    model = mellum_tiny(loss_chunk=64, moe_held_experts=held)
    return _reference_says(mellum, MELLUM_CONFIG, model,
                           mellum_weights(model))


# ---- Qwen3-Next ------------------------------------------------------------
def qnext_tiny(**kw):
    """32 of the tiny preset's 512 experts held, and the attention layer's
    ``w_q`` / ``w_k`` from 2 as the benchmark's configuration sets them."""
    kw.setdefault("moe_held_experts", 32)
    kw.setdefault("qk_norm_init", 2.0)
    return Qwen3Next(size="tiny", **kw)


def qnext_weights(model, seed=3):
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


@functools.lru_cache(maxsize=None)
def qnext_right():
    """``_reference_says`` of the right model's boosted weights."""
    model = qnext_tiny()
    return _reference_says(qwen3_next, QNEXT_CONFIG, model,
                           qnext_weights(model))


# ---- LFM2-MoE --------------------------------------------------------------
def lfm_tiny(**kw):
    """8 of the tiny preset's 64 experts held, as the cell holds them, in
    three layers that hold every kind (a dense conv layer, a routed
    attention layer, a routed conv layer): two layers fewer to compile a
    case than the preset's own five."""
    kw.setdefault("moe_held_experts", 8)
    if "layer_types" not in kw:
        kw.update(num_layers=3,
                  layer_types=["conv", "full_attention", "conv"])
    return Lfm2Moe(size="tiny", **kw)


def lfm_weights(model, seed=3):
    """Seeded weights under which every part this family adds carries
    weight in the logits at the tiny widths: a larger table under larger
    projections, outputs and experts (at the init's own scale a layer of
    hidden 64 adds a hundredth of the embedding), sharper attention scores,
    an expert bias that moves the selection past the mask's margin, and
    every norm weight drawn (they start at 1, where ``w`` cannot be told
    from a missing weight)."""
    boost = {"tokens": 5.0, "w_in": 6.0, "w_out": 8.0, "wv": 4.0, "wo": 8.0,
             "w_gate": 6.0, "w_up": 6.0, "w_down": 8.0, "router_bias": 10.0}
    norms = {"ln1_scale", "ln2_scale", "scale"}
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def one(path, w):
        name = path[-1].key
        if name in norms:
            return w + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype)
        if name in ("q_norm", "k_norm"):    # scores of deviation 9
            return 3.0 * w + 0.5 * jax.random.normal(next(keys), w.shape,
                                                     w.dtype)
        return w * boost.get(name, 1.0)

    return jax.tree_util.tree_map_with_path(
        one, model.init(jax.random.PRNGKey(seed)))


@functools.lru_cache(maxsize=None)
def lfm_right(held: int = 8):
    """``_reference_says`` of the right model's boosted weights with
    ``held`` of the 64 experts held."""
    model = lfm_tiny(moe_held_experts=held)
    return _reference_says(lfm2_moe, LFM_CONFIG, model, lfm_weights(model))


# ---- Xing4 -----------------------------------------------------------------
def xing_tiny(**kw):
    """8 of the tiny preset's 64 experts held and the seeded values of the
    benchmark's configuration (``assumed``: the coefficients' static and
    input-dependent parts at comparable deviation),
    in three layers that hold both kinds (a leading dense layer, two
    routed ones under the scan): two layers fewer to compile a case than
    the preset's own five."""
    kw.setdefault("moe_held_experts", 8)
    kw.setdefault("num_layers", 3)
    for key in ("mhc_alpha_init", "mhc_b_std"):
        kw.setdefault(key, XING_CONFIG["program"]["model_overrides"][key])
    return Xing4(size="tiny", **kw)


def xing_weights(model, seed=3):
    """Seeded weights under which every part this family adds carries
    weight in the logits at the tiny widths: a larger table under larger
    values, outputs and experts (at the init's own scale a layer of hidden
    64 adds a hundredth of the embedding), sharper attention scores,
    alphas apart from one another,
    and every norm weight drawn (they start at 1, where ``w`` cannot
    be told from a missing weight)."""
    boost = {"tokens": 5.0, "wq_b": 3.0, "w_kva": 4.0, "w_kvb": 3.0, "wo": 8.0, "w_gate": 6.0,
             "w_up": 6.0, "w_down": 8.0, "router_bias": 10.0}
    norms = {"ln1_scale", "ln2_scale", "scale", "q_norm", "kv_norm"}
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def one(path, w):
        name = path[-1].key
        if name in norms:
            return w + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype)
        if name == "alpha":
            return w * jnp.asarray([1.0, 0.7, 1.3], w.dtype)
        return w * boost.get(name, 1.0)

    return jax.tree_util.tree_map_with_path(
        one, model.init(jax.random.PRNGKey(seed)))


@functools.lru_cache(maxsize=None)
def xing_right(held: int = 8):
    """``_reference_says`` of the right model's boosted weights with
    ``held`` of the 64 experts held."""
    model = xing_tiny(moe_held_experts=held)
    return _reference_says(xing4, XING_CONFIG, model, xing_weights(model))
