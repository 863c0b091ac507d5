"""Both callers of the short convolution's kernels (ISSUE 43),
Kimi-Linear and Granite 4.0-H, held to the loss and gradients they
computed with the ``jax.numpy`` lines in the op's place. The case was
``tests/test_granite_hybrid.py``'s until PR 45 (a file is one worker's
under ``--dist loadfile``, and this is four steps of each family in
interpret mode), and one case a family until PR 50 (Kimi-Linear's four
steps in one case were the longest case of the suite). A CPU run shows
results and counts, never a time."""

import functools

import jax
import numpy as np
import pytest

from deepspeed_tpu.models import GraniteHybrid, KimiLinear
from deepspeed_tpu.ops import layers as L

from helpers import short_conv_reference  # noqa: E402  (tests/helpers)
from helpers.family_cases import _batch


# ---- with the jax.numpy convolution back in, the parent's step -------------
_STEPS = {
    "kimi_linear_the_cells_switches": (KimiLinear, dict(
        moe_held_experts=8, attn_impl="flash", loss_chunk=64,
        kda_head_groups=2)),
    "granite_hybrid_the_cells_switches": (GraniteHybrid, dict(
        attn_impl="flash", loss_chunk=64)),
}


@functools.lru_cache(maxsize=None)
def _loss_and_grads(family, dtype, reference: bool):
    """Loss and gradients of a tiny model's step on ``dtype`` weights,
    with ``ops.layers.short_conv`` as it is or, ``reference``, as the
    parent's lines had it (``tests/helpers/short_conv_reference.py``:
    ``causal_conv``, the SiLU and ``_kda``'s local l2 norm, the taps and
    the SiLU in the weights' dtype). Each whole step is computed once: the
    float32 kernels' step is what both cases of a family hold to."""
    cls, kw = _STEPS[family]
    with pytest.MonkeyPatch.context() as patch:
        if reference:
            patch.setattr(L, "short_conv", short_conv_reference.short_conv)
        model = cls(size="tiny", **kw)
        params = jax.tree.map(lambda x: x.astype(dtype),
                              model.init(jax.random.PRNGKey(3)))
        out = jax.jit(jax.value_and_grad(model.loss))(params, _batch(model))
        return jax.device_get(out)


def _leaf_errors(got, want):
    """{leaf: |got - want| / |want| (l2)} over the leaves with a gradient
    (the router's bias has none: selection only)."""
    out = {}
    for (path, w), (_, g) in zip(jax.tree_util.tree_leaves_with_path(want),
                                 jax.tree_util.tree_leaves_with_path(got),
                                 strict=True):
        w, g = (np.asarray(v, np.float32) for v in (w, g))
        if np.any(w):
            out[jax.tree_util.keystr(path)] = float(
                np.linalg.norm(g - w) / np.linalg.norm(w))
        else:
            assert not np.any(g), path
    return out


@pytest.mark.parametrize("family", list(_STEPS))
def test_the_float32_step_computes_the_parents_loss_and_gradients(family):
    """ISSUE 43: the short convolution, the SiLU and the l2 norms became
    one kernel pair and nothing else moved: with the parent's
    ``jax.numpy`` lines patched back in for the op, the tiny Kimi-Linear
    and Granite steps compute the same loss and gradients. In float32
    the two forms are one function (the kernels sum a head's squares from
    three bf16 pieces and take the SiLU through tanh: rounding in the
    seventh digit)."""
    exact, exact_g = _loss_and_grads(family, "float32", False)
    parent, parent_g = _loss_and_grads(family, "float32", True)
    assert abs(float(exact) - float(parent)) <= 2e-6 * float(parent)
    same = _leaf_errors(exact_g, parent_g)
    assert max(same.values()) < 2e-4, max(same.items(), key=lambda kv: kv[1])


@pytest.mark.parametrize("family", list(_STEPS))
def test_the_bfloat16_step_lies_no_further_from_float32_than_the_parents(
        family):
    """On bf16 weights each form is its own rounding of the float32
    function (the parent rounded the taps' products, their sum and the
    SiLU to bf16, the kernels round once), so each is held to the
    float32 gradients: the kernels' lie NO FURTHER from them than the
    parent's (0.8 of its distance on Kimi's leaves, 0.9 on Granite's,
    where a leaf is 1% to 5% from float32 in either form). A routed
    expert's leaves are 10% to 20% off in both: a rounding sends a token
    to another expert."""
    exact, exact_g = _loss_and_grads(family, "float32", False)
    now, now_g = _loss_and_grads(family, "bfloat16", False)
    parent, parent_g = _loss_and_grads(family, "bfloat16", True)
    assert (abs(float(now) - float(exact))
            <= 1.5 * abs(float(parent) - float(exact)) + 2e-4 * float(exact))
    mine, theirs = (_leaf_errors(g, exact_g) for g in (now_g, parent_g))
    for name in mine:       # no leaf goes astray
        assert mine[name] <= 2 * theirs[name] + 1e-2, (
            name, mine[name], theirs[name])
    smooth = [n for n in mine if "['experts']" not in n
              and "['router']" not in n]
    assert len(smooth) > 30
    assert (np.mean([mine[n] for n in smooth])
            <= np.mean([theirs[n] for n in smooth]))
