"""Laguna's whole tiny model (a leading dense full layer, three routed
window layers, a routed full layer) against the plain float32 reference the
benchmark keeps (``benchmark/architectures/laguna.py``, which imports
nothing from the program): loss, tail logits and every gradient, at three
settings of the model's switches. A family's float32 reference comparison
is the longest thing it has, and a file is one worker's under ``--dist
loadfile``. A CPU run shows results and counts, never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers.families import right, tail_loss_grads, tiny
from helpers.families import (_err, _reference_grads,  # noqa: F401
                               _telemetry_isolation)
from architectures import laguna as arch  # noqa: E402  (benchmark/, on
#                                           sys.path by families)

_tiny = functools.partial(tiny, "laguna")


# the whole-layer case routes over 32 experts and holds them all: the
# reference evaluates every held expert on every token, and 256 of them
# were four minutes of one case
WHOLE = 32


@functools.lru_cache(maxsize=None)
def _right(held: int):
    """``right("laguna")`` (boosted weights with ``held`` experts held, of
    the router's 256 or, held whole, of ``WHOLE``; a batch, the float32
    reference's loss, tail logits and mask) with the reference's gradient
    in the place of its model."""
    kw = dict(num_experts=WHOLE) if held == WHOLE else {}
    params, tokens, targets, want, m = right("laguna", held, loss_chunk=64,
                                             **kw)
    grads = _reference_grads(arch, params, tokens, targets, m)
    return params, tokens, targets, want, grads


@pytest.mark.parametrize("variant", ["plain", "flash_chunked_loss",
                                     "flash_whole_layer_held"])
def test_loss_logits_and_gradients_match_the_float32_reference(variant):
    """Loss to 2e-5 (float32 sums in another order), tail logits to 5e-4
    of their largest, and on the cell's path (flash kernels at 3 and 2
    query heads a key head, the window equal to a quarter of the
    sequence, chunked loss, every layer rematted) every gradient to 2e-3
    of its largest: of every kind of leaf in both kinds of attention layer
    (``wq``, ``wk``, ``wv``, the gate's ``wg``, ``wo``), the leading
    layer's dense SwiGLU, the shared and the held experts. A share (8 of
    256 held) leaves the routing alone in the backward: its routers'
    gradients are zero on both sides; with the whole layer held (a router
    of ``WHOLE`` outputs, top 10) the router trains and its gradient is
    compared like the others."""
    held = WHOLE if variant == "flash_whole_layer_held" else 8
    kw = dict(remat=False) if variant == "plain" else dict(
        attn_impl="flash", loss_chunk=64)
    if held == WHOLE:
        kw["num_experts"] = WHOLE
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
    seen = set()
    for (path, w), (_, g) in zip(flat_w, flat_g):
        name = jax.tree_util.keystr(path)
        seen.add(tuple(k.key for k in path[-2:]))
        if held < WHOLE and name.endswith("['router']"):
            assert not np.any(w) and not np.any(g), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _err(g, w) < 2e-3, name
    assert {("swa", "wg"), ("full", "wg"), ("swa", "wq"), ("full", "wo"),
            ("mlp", "w_up"), ("shared", "w_down"), ("experts", "w_gate"),
            ("moe", "router")} <= seen
