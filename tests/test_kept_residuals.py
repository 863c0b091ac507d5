"""A rematted layer keeps what a kernel's forward rule declares (ISSUE 47):
the flash kernel's output and row log-sum-exp pass through
``ops/pallas/_common.py`` ``_keep`` under the name ``KEPT_RESIDUAL``, and
every policy of ``models/transformer.py`` ``_remat_policy`` saves that name,
so the backward of a layer under ``jax.checkpoint`` reruns its norms and
projections but not ``ds_flash_fwd``. The kernels are interpreted here: a
CPU run shows counts and bits, never a time."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.models import (GraniteHybrid, KimiLinear, Mellum, Mistral,
                                  Ouro, Qwen3Next, ouro, stack, transformer)
from deepspeed_tpu.ops.pallas import _common
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

from helpers.family_cases import DS_CONFIG as _DS_CONFIG
from helpers.family_cases import (_batch, _telemetry_isolation,  # noqa: F401
                                  _walk_eqns)


# family -> (class, the `tiny` preset's switches, attention layer
# applications in the TRACED program: a scan's body is traced once, so
# Ouro's 2 layers x 4 passes are one application, and Mellum's two kinds
# of attention layer are two)
_REMATTED = {
    "kimi_linear": (KimiLinear, dict(moe_held_experts=8), 1),
    "granite_hybrid": (GraniteHybrid, {}, 1),
    "mellum": (Mellum, dict(moe_held_experts=16), 2),
    "ouro": (Ouro, {}, 1),
    "qwen3_next": (Qwen3Next, dict(moe_held_experts=32), 1),
}


def _tiny(family):
    cls, model_kw, _ = _REMATTED[family]
    return cls(size="tiny", attn_impl="flash", loss_chunk=64, **model_kw)


def _kernel_calls(fn, *args):
    """How often each Pallas kernel is called in ``fn``'s traced program,
    by the kernel's name. Interpreted kernels lower to plain HLO, so the
    lowered text of a CPU step holds no kernel's name: the jaxpr that is
    lowered does. Traced through a function of its own, so that no trace
    made under another policy is found again."""
    _common._TRACED.clear()
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    return collections.Counter(
        eqn.params["name"] for eqn in _walk_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call")


def _keep_nothing(monkeypatch):
    """``jax.checkpoint(policy=None)`` wherever a model asks
    ``_remat_policy``: what every policy name meant before this PR."""
    for module in (transformer, stack, ouro):
        monkeypatch.setattr(module, "_remat_policy", lambda name: None)


def _train_step_calls(family):
    model = _tiny(family)
    assert model.config.remat and model.config.remat_policy == \
        "nothing_saveable"
    engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
    tok = np.zeros((8, model.config.max_seq_len), np.int32)
    return _kernel_calls(engine._train_step, engine.state,
                         engine._put_batch((tok, tok)))


@pytest.mark.parametrize("family", list(_REMATTED))
def test_a_rematted_step_runs_the_forward_kernel_once_an_application(
        family, monkeypatch):
    """The engine's train step of each rematted family holds one
    ``ds_flash_fwd`` and one ``ds_flash_bwd`` an attention layer
    application; the same step built on ``policy=None`` holds the forward
    kernel twice."""
    applications = _REMATTED[family][2]
    calls = _train_step_calls(family)
    assert calls["ds_flash_fwd"] == calls["ds_flash_bwd"] == applications
    _keep_nothing(monkeypatch)
    calls = _train_step_calls(family)
    assert calls["ds_flash_bwd"] == applications
    assert calls["ds_flash_fwd"] == 2 * applications


@pytest.mark.parametrize("family", ["mellum", "ouro"])
def test_the_gradients_are_policy_nones_bit_for_bit(family, monkeypatch):
    """The kept ``o`` and ``lse`` are the bits the rerun would have made:
    every gradient of the loss is the one ``policy=None`` gives."""
    model = _tiny(family)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                          model.init(jax.random.PRNGKey(1)))
    batch = _batch(model, b=2)

    def loss(p):
        out = model.loss(p, batch)
        return out[0] if isinstance(out, tuple) else out

    kept = jax.device_get(jax.jit(jax.value_and_grad(loss))(params))
    _keep_nothing(monkeypatch)
    rerun = jax.device_get(jax.jit(jax.value_and_grad(loss))(params))
    assert float(kept[0]) == float(rerun[0]) and np.isfinite(kept[0])
    flat = jax.tree_util.tree_leaves_with_path(kept[1])
    assert any(np.any(np.asarray(g, np.float32) != 0) for _, g in flat)
    for (path, got), want in zip(flat, jax.tree.leaves(rerun[1])):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=jax.tree_util.keystr(path))


def test_the_gauge_reads_what_one_call_declares():
    """``ds_kernel_kept_bytes{kernel="flash"}`` is set where the forward
    rule is traced: the bytes of ``o`` (in q's dtype) and ``lse``
    (float32, a row a head) of the call last traced; a call that is not
    differentiated traces no rule and declares nothing."""
    b, s, hq, hkv, d, dv = 2, 256, 4, 2, 64, 32
    q = jax.ShapeDtypeStruct((b, s, hq, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, hkv, dv), jnp.bfloat16)

    def grad(q, k, v):
        return jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=True).astype(jnp.float32)))(q)

    telemetry.configure()
    reg = telemetry.get_registry()
    jax.eval_shape(lambda *a: flash_attention(*a, causal=True), q, k, v)
    assert reg.get("ds_kernel_kept_bytes") is None
    jax.eval_shape(grad, q, k, v)
    o = np.zeros((b * hq, s, dv), jnp.bfloat16)
    lse = np.zeros((b * hq, 1, s), np.float32)
    assert reg.get("ds_kernel_kept_bytes").value(kernel="flash") == \
        o.nbytes + lse.nbytes == 2 * b * hq * s * dv + 4 * b * hq * s


@pytest.mark.parametrize("policy", ["save_attn_ffn", "dots_saveable"])
def test_every_policy_keeps_the_name(policy, monkeypatch):
    """``save_attn_ffn`` (its own three names) and a stock policy keep
    the declared residuals too: the one-kind decoder's scan under either
    holds the forward kernel once, and twice under the policy as it was
    (the stock one alone; the three names alone)."""
    model = Mistral(size="tiny", attn_impl="flash", loss_chunk=64,
                    sliding_window=64, remat_policy=policy)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def grad(p, t):
        return jax.grad(lambda p: model.loss(p, (t, t)))(p)

    calls = _kernel_calls(grad, params, tok)
    assert calls["ds_flash_fwd"] == calls["ds_flash_bwd"] == 1
    policies = jax.checkpoint_policies
    as_it_was = {"save_attn_ffn": policies.save_only_these_names(
        "qkv", "attn_out", "ffn"),
        "dots_saveable": policies.dots_saveable}[policy]
    monkeypatch.setattr(transformer, "_remat_policy", lambda name: as_it_was)
    calls = _kernel_calls(grad, params, tok)
    assert (calls["ds_flash_fwd"], calls["ds_flash_bwd"]) == (2, 1)
