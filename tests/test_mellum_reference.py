"""Mellum 2's whole tiny model against the plain float32 reference the
benchmark keeps (``benchmark/architectures/mellum.py``, which imports
nothing from the program): loss, tail logits and every gradient, at three
settings of the model's switches. These cases were ``tests/test_mellum.py``'s
until PR 50: a family's float32 reference comparison is the longest thing
its file held, and a file is one worker's under ``--dist loadfile``. A CPU
run shows results and counts, never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers.families import right, tail_loss_grads, tiny
from helpers.families import (_err, _reference_grads,  # noqa: F401
                               _telemetry_isolation)
from architectures import mellum as arch  # noqa: E402  (benchmark/, on
#                                           sys.path by families)

_tiny = functools.partial(tiny, "mellum")


@functools.lru_cache(maxsize=None)
def _right(held: int):
    """``right("mellum")`` (boosted weights with ``held`` of the 64 experts
    held, a batch, the float32 reference's loss, tail logits and mask) with
    the reference's gradient in the place of its model."""
    params, tokens, targets, want, m = right("mellum", held, loss_chunk=64)
    grads = _reference_grads(arch, params, tokens, targets, m)
    return params, tokens, targets, want, grads


@pytest.mark.parametrize("variant", ["plain", "flash_chunked_loss",
                                     "flash_whole_layer_held"])
def test_loss_logits_and_gradients_match_the_float32_reference(variant):
    """Loss to 2e-5 (float32 sums in another order), tail logits to 5e-4
    of their largest (the boosted scores sharpen the softmax, which
    amplifies the last bits), and on the cell's path (flash kernels,
    chunked loss, every layer rematted) every gradient to 2e-3 of its
    largest (the kernels' online softmax and the dispatch's scatter-adds
    sum in another order than the reference's dense forms). A share (16
    of 64 held) leaves the routing alone in the backward: its routers'
    gradients are zero on both sides, and what flows to the layer's input
    flows through the experts alone; with the whole layer held the router
    trains and its gradient is compared like the others."""
    held = 64 if variant == "flash_whole_layer_held" else 16
    kw = dict(remat=False) if variant == "plain" else dict(
        attn_impl="flash", loss_chunk=64)
    model = _tiny(moe_held_experts=held, **kw)
    params, tokens, targets, (want, want_tail, _), want_g = _right(held)
    with jax.default_matmul_precision("highest"):
        got_tail, got, got_g = tail_loss_grads(
            model, params, tokens, targets, grads=variant != "plain")
    assert abs(float(got) - want) <= 2e-5 * want
    assert _err(got_tail, want_tail) < 5e-4
    if got_g is None:
        return
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = jax.tree_util.tree_leaves_with_path(got_g)
    assert [p for p, _ in flat_w] == [p for p, _ in flat_g]
    for (path, w), (_, g) in zip(flat_w, flat_g):
        name = jax.tree_util.keystr(path)
        if held < 64 and name.endswith("['router']"):
            assert not np.any(w) and not np.any(g), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _err(g, w) < 2e-3, name
