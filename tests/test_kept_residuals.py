"""A rematted layer keeps what a kernel's forward rule declares (ISSUE 47):
the flash kernel's output and row log-sum-exp pass through
``ops/pallas/_common.py`` ``_keep`` under the name ``KEPT_RESIDUAL``, and
every policy of ``models/transformer.py`` ``_remat_policy`` saves that name,
so the backward of a layer under ``jax.checkpoint`` reruns its norms and
projections but not ``ds_flash_fwd``. The delta rule's scan declares its
``o`` the same way (ISSUE 51, ``ops/kda.py`` ``chunk_kda``): the rerun then
holds no kernel of the scan, and the backward's own rerun of a head group
is the one left (the scan alone and its gauge: ``tests/test_kept_scan.py``). The kernels
are interpreted here: a CPU run shows counts and bits, never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import telemetry
from deepspeed_tpu.models import Mistral, transformer
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

from helpers.family_cases import DS_CONFIG as _DS_CONFIG
from helpers.family_cases import _telemetry_isolation  # noqa: F401
from helpers.kept_cases import (REMATTED, keep_nothing, kernel_calls, tiny,
                                value_and_grads)


@functools.cache
def _train_step_calls(family, nothing_kept=False):
    """The kernels of the engine's train step (eight virtual devices, so
    the scans run per shard), once a family and policy for the cases that
    read them."""
    with pytest.MonkeyPatch.context() as patch:
        if nothing_kept:
            keep_nothing(patch)
        model = tiny(family)
        assert model.config.remat and model.config.remat_policy == \
            "nothing_saveable"
        engine, *_ = ds.initialize(model=model, config=dict(_DS_CONFIG))
        tok = np.zeros((8, model.config.max_seq_len), np.int32)
        return kernel_calls(engine._train_step, engine.state,
                            engine._put_batch((tok, tok)))


@pytest.mark.parametrize("family", list(REMATTED))
def test_a_rematted_step_runs_the_forward_kernel_once_an_application(family):
    """The engine's train step of each rematted family holds one
    ``ds_flash_fwd`` and one ``ds_flash_bwd`` an attention layer
    application; the same step built on ``policy=None`` holds the forward
    kernel twice."""
    applications = REMATTED[family][2]
    calls = _train_step_calls(family)
    assert calls["ds_flash_fwd"] == calls["ds_flash_bwd"] == applications
    calls = _train_step_calls(family, nothing_kept=True)
    assert calls["ds_flash_bwd"] == applications
    assert calls["ds_flash_fwd"] == 2 * applications


@pytest.mark.parametrize("family, runs", [("kimi_linear", 2),
                                          ("qwen3_next", 3)])
def test_a_rematted_step_runs_the_scan_twice_where_groups_are_a_loop(
        family, runs):
    """Kimi-Linear's KDA heads run in head groups under a loop: a layer's
    forward and its groups' own rerun in the backward are what is left, the
    preparation's forward and ``ds_kda_fwd`` (its ``o`` form once, its
    checkpoint form once) twice a backward kernel, where the step built on
    ``policy=None`` holds them three times (the layer's rerun made ``o``
    again). Qwen3-Next's Gated DeltaNet heads run in ONE group, which keeps
    nothing: three times under either. Every other kernel but
    ``ds_flash_fwd`` runs as often as under ``policy=None``: the backward
    still needs q, k, v, g and beta, so the convolutions rerun as
    before, and the gated norm behind the scan (ISSUE 55) runs its forward
    twice (the forward; remat's rerun for the output matmul) and its
    backward once a layer under either."""
    kept = _train_step_calls(family)
    rerun = _train_step_calls(family, nothing_kept=True)
    assert kept["ds_kda_prep_bwd"] == kept["ds_kda_bwd"] > 0
    assert (kept["ds_gated_norm_fwd"], kept["ds_gated_norm_bwd"]) == (
        2 * kept["ds_kda_bwd"], kept["ds_kda_bwd"])
    for fwd, bwd in (("ds_kda_prep_fwd", "ds_kda_prep_bwd"),
                     ("ds_kda_fwd", "ds_kda_bwd")):
        assert kept[fwd] == runs * kept[bwd]
        assert rerun[fwd] == 3 * rerun[bwd] == 3 * kept[bwd]
    moved = {"ds_kda_prep_fwd", "ds_kda_fwd", "ds_flash_fwd"}
    assert {k: n for k, n in kept.items() if k not in moved} == \
        {k: n for k, n in rerun.items() if k not in moved}


@pytest.mark.parametrize("family", ["mellum", "ouro", "qwen3_next"])
def test_the_gradients_are_policy_nones_bit_for_bit(family, monkeypatch):
    """The kept ``o`` and ``lse`` are the bits the rerun would have made:
    every gradient of the loss is the one ``policy=None`` gives
    (Qwen3-Next's one head group keeps nothing of its scans: its gated
    attention layer's flash kernels do)."""
    kept = value_and_grads(family)
    keep_nothing(monkeypatch)
    rerun = value_and_grads(family)
    assert kept[0] == rerun[0]
    for path, got in kept[1].items():
        np.testing.assert_array_equal(got, rerun[1][path], err_msg=path)


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

    calls = kernel_calls(grad, params, tok)
    assert calls["ds_flash_fwd"] == calls["ds_flash_bwd"] == 1
    policies = jax.checkpoint_policies
    as_it_was = {"save_attn_ffn": policies.save_only_these_names(
        "qkv", "attn_out", "ffn"),
        "dots_saveable": policies.dots_saveable}[policy]
    monkeypatch.setattr(transformer, "_remat_policy", lambda name: as_it_was)
    calls = kernel_calls(grad, params, tok)
    assert (calls["ds_flash_fwd"], calls["ds_flash_bwd"]) == (2, 1)
