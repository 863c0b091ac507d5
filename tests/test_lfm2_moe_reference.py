"""LFM2-MoE's whole tiny model against the plain float32 reference the
benchmark keeps (``benchmark/architectures/lfm2_moe.py``, which imports
nothing from the program): loss, tail logits and every gradient in float32
at batch 2, loss and logits on bfloat16 weights. A family's float32
reference comparison is the longest thing its tests hold, and a file is one
worker's under ``--dist loadfile``. A CPU run shows results and counts,
never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers.families import config_of, right, tail_loss_grads, tiny
from helpers.families import (_err, _telemetry_isolation)  # noqa: F401
from architectures import lfm2_moe as arch  # noqa: E402  (benchmark/, on
#                                         sys.path by families)
from kinds import train_job  # noqa: E402
from lib import modelspec  # noqa: E402

CONFIG = config_of("lfm2_moe")
_tiny = functools.partial(tiny, "lfm2_moe")


@functools.lru_cache(maxsize=None)
def _right():
    """``right("lfm2_moe")`` (boosted weights, a batch of two sequences, the
    float32 reference's loss, tail logits and mask) with the reference's
    gradient in the place of its model; the head is the table."""
    params, tokens, targets, want, m = right("lfm2_moe")

    def loss(params, tokens, targets):
        hidden, _ = arch._forward(params, tokens, m)
        return arch.loss_of(hidden, arch.head_of(params), targets)
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(loss))(params, tokens, targets)
    return params, tokens, targets, want, grads


# the gradients ISSUE 54 names, by the leaf's path
_NAMED = ("w_in", "taps", "w_out", "wq", "wk", "q_norm", "k_norm", "w_gate",
          "w_down", "ln1_scale", "tokens", "scale")


@pytest.mark.parametrize("variant", ["plain_f32", "flash_chunked_loss_f32",
                                     "flash_chunked_loss_bf16"])
def test_loss_logits_and_gradients_match_the_float32_reference(variant):
    """Float32: loss to 2e-5, tail logits to 5e-4 of their largest, and on
    the cell's path (the gated convolution's kernels, flash kernels,
    chunked loss, every layer rematted) every gradient to 3e-3 of its
    largest; a share's routers' and expert biases' gradients are zero on
    both sides. bfloat16 weights (what the engine computes with) at the
    init's own scale against the float32 reference on the same weights,
    over the positions its mask counts: loss to 0.5%, logits to 5% of
    their largest and 2% rms."""
    kw = dict(remat=False) if variant == "plain_f32" else dict(
        attn_impl="flash", loss_chunk=64)
    model = _tiny(**kw)
    params, tokens, targets, (want, want_tail, _), want_g = _right()
    assert tokens.shape[0] == 2
    if variant.endswith("bf16"):
        params = model.init(jax.random.PRNGKey(3))
        m = modelspec.reference_model(arch, model, CONFIG["check"])
        with jax.default_matmul_precision("highest"):
            want, want_tail, counted = arch.reference(
                params, tokens, targets, m, 32)
        low = jax.tree_util.tree_map(
            lambda w: w.astype(jnp.bfloat16), params)
        got_tail, got, _ = tail_loss_grads(model, low, tokens, targets,
                                           grads=False)
        numbers = train_job.tail_numbers(got_tail, want_tail, counted)
        got = float(got)
        assert abs(got - want) <= 5e-3 * want
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
        if path[-1].key in ("router", "router_bias"):
            assert not np.any(w) and not np.any(g), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _err(g, w) < 3e-3, name
    assert set(_NAMED) <= seen
