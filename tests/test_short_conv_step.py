"""Both callers of the short convolution's kernels (ISSUE 43),
Kimi-Linear and Granite 4.0-H, held to the loss and gradients they
computed with the ``jax.numpy`` lines in the op's place, at the smallest
stack that holds the op (ISSUE 58: ONE layer of the kind, the row's
``short_conv`` cut of ``tests/helpers/families.py``; the families' whole
stacks are held to float32 in ``tests/test_<family>_reference.py`` and the
kernels in ``tests/test_short_conv.py``). The case was
``tests/test_granite_hybrid.py``'s until PR 45, one case a family until PR
50, and the preset's whole five layers until PR 58 (Kimi-Linear's were four
of the eight cases over 90 s). A CPU run shows results and counts, never a
time."""

import jax
import numpy as np
import pytest

from helpers.families import SHORT_CONV, program


def _loss_and_grads(family, dtype, reference: bool):
    """Loss and gradients of one layer's step on ``dtype`` weights, with
    ``ops.layers.short_conv`` as it is or, ``reference``, as the parent's
    lines had it (``tests/helpers/short_conv_reference.py``:
    ``causal_conv``, the SiLU and ``_kda``'s local l2 norm, the taps and
    the SiLU in the weights' dtype). Each step is computed once a file
    (``families.program``): the float32 kernels' step is what both cases
    of a family hold to."""
    return program(family, "short_conv", dtype=dtype,
                   patch="short_conv_reference" if reference else None
                   ).loss_and_grads(3)


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


@pytest.mark.parametrize("family", SHORT_CONV)
def test_the_float32_step_computes_the_parents_loss_and_gradients(family):
    """ISSUE 43: the short convolution, the SiLU and the l2 norms became
    one kernel pair and nothing else moved: with the parent's
    ``jax.numpy`` lines patched back in for the op, a KDA and a Mamba-2
    layer's step compute the same loss and gradients (read at one layer,
    Kimi-Linear's and Granite's: a loss 8e-8 and 0 apart, a leaf 9e-7 and
    5e-7). In float32
    the two forms are one function (the kernels sum a head's squares from
    three bf16 pieces and take the SiLU through tanh: rounding in the
    seventh digit)."""
    exact, exact_g = _loss_and_grads(family, "float32", False)
    parent, parent_g = _loss_and_grads(family, "float32", True)
    assert abs(float(exact) - float(parent)) <= 2e-6 * float(parent)
    same = _leaf_errors(exact_g, parent_g)
    assert max(same.values()) < 2e-4, max(same.items(), key=lambda kv: kv[1])


@pytest.mark.parametrize("family", SHORT_CONV)
def test_the_bfloat16_step_lies_no_further_from_float32_than_the_parents(
        family):
    """On bf16 weights each form is its own rounding of the float32
    function (the parent rounded the taps' products, their sum and the
    SiLU to bf16, the kernels round once), so each is held to the
    float32 gradients: the kernels' lie NO FURTHER from them than the
    parent's (0.8 of its distance on Kimi's leaves, 0.9 on Granite's,
    where a leaf is 1% to 5% from float32 in either form). A routed
    expert's leaves are 10% to 20% off in both: a rounding sends a token
    to another expert. One layer has 24 smooth leaves (Kimi-Linear's KDA
    layer) or 15 (Granite's Mamba-2 layer, every one of its leaves) where
    the five-layer stacks had over 30."""
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
    assert len(smooth) >= 15
    assert (np.mean([mine[n] for n in smooth])
            <= np.mean([theirs[n] for n in smooth]))
