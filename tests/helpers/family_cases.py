"""What the test files of the Kimi-Linear and Granite families share
(``tests/test_kimi_linear*.py``, ``tests/test_kda*_kernels.py``,
``tests/test_granite_hybrid*.py``, ``tests/test_short_conv_step.py``,
``tests/test_qwen3_next*.py``): a
file is one worker's under ``--dist loadfile``, so each family's cases lie
in several files and their fixtures and inputs here. Importing this puts
``benchmark/`` on ``sys.path`` (the references are ``architectures/``'s)."""

import gc
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.models import GraniteHybrid, KimiLinear, Qwen3Next

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from architectures import kimi_linear  # noqa: E402

GRANITE_CONFIG = json.loads(
    (BENCH / "configs" / "granite-4.0-h-micro-zero3-1chip.json").read_text())


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    telemetry.shutdown()
    yield
    telemetry.shutdown()


@pytest.fixture(autouse=True)
def _drop_compiled_programs():
    """The KDA tests run the kernels eagerly in interpret mode: a call
    compiles some hundred small programs that no cache ever finds again,
    each a few memory mappings, and a test worker that has run this file
    passed the kernel's 65530 mappings a process and died in XLA's
    compiler (PR 35). Dropping JAX's caches after a test returns them."""
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


# ---- Qwen3-Next ------------------------------------------------------------
def qnext_tiny(**kw):
    """32 of the tiny preset's 512 experts held, and the attention layer's
    ``w_q`` / ``w_k`` from 2 as the benchmark's configuration sets them."""
    kw.setdefault("moe_held_experts", 32)
    kw.setdefault("qk_norm_init", 2.0)
    return Qwen3Next(size="tiny", **kw)
