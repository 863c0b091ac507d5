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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.models import Mistral, transformer
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

from helpers.families import _telemetry_isolation  # noqa: F401
from helpers.families import flat_grads, kernel_calls, program


@pytest.mark.parametrize("family", ["mellum", "ouro", "qwen3_next"])
def test_the_gradients_are_policy_nones_bit_for_bit(family):
    """The kept ``o`` and ``lse`` are the bits the rerun would have made:
    every gradient of the loss is the one ``policy=None`` gives
    (Qwen3-Next's one head group keeps nothing of its scans: its gated
    attention layer's flash kernels do: it is held at two such layers, the
    row's ``kept`` cut, ISSUE 58)."""
    kept, rerun = (program(family, "kept", patch=patch
                           ).loss_and_grads(1) for patch in
                   (None, "keep_nothing"))
    assert np.isfinite(kept[0]) and kept[0] == rerun[0]
    want = flat_grads(rerun[1])
    for path, got in flat_grads(kept[1]).items():
        np.testing.assert_array_equal(got, want[path], err_msg=path)


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
