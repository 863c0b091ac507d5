"""Granite 4.0-H's whole tiny model against the plain float32 reference the
benchmark keeps (``benchmark/architectures/granite_hybrid.py``, which
imports nothing from the program): the loss and every gradient, at three
settings of the model's switches. These cases were
``tests/test_granite_hybrid.py``'s until PR 50: a family's float32
reference comparison is the longest thing its file held, and a file is one
worker's under ``--dist loadfile``. A CPU run shows results and counts,
never a time."""

import functools

import jax
import jax.numpy as jnp
import pytest

from helpers.families import tiny, weights
from helpers.families import (_batch, _err,  # noqa: F401
                               _telemetry_isolation)
from architectures import granite_hybrid as arch  # noqa: E402  (benchmark/,
#                                           on sys.path by families)
from lib import modelspec  # noqa: E402  (benchmark/, by families)

_tiny = functools.partial(tiny, "granite_hybrid")
_weights = functools.partial(weights, "granite_hybrid")


def _ref_loss(params, tokens, targets, m):
    hidden = arch.final_hidden(params, tokens, m) / m["logits_scaling"]
    return arch.loss_of(hidden, params["embed"]["tokens"].T, targets)


@functools.lru_cache(maxsize=None)
def _right():
    """Seeded weights, a batch, and the float32 reference's loss and
    gradients of them: the same for every setting of the model's switches
    (none of them is a field of the reference's model)."""
    model = _tiny()
    params = _weights(model)
    tokens, targets = _batch(model)
    m = modelspec.reference_model(arch, model)
    with jax.default_matmul_precision("highest"):
        # jitted: eager, every line of the reference compiles alone
        want, want_g = jax.jit(jax.value_and_grad(
            lambda *a: _ref_loss(*a, m)))(params, tokens, targets)
    return params, tokens, targets, want, want_g


@pytest.mark.parametrize("variant", ["plain", "flash_chunked_loss",
                                     "no_remat"])
def test_loss_and_gradients_match_the_float32_reference(variant):
    kw = {"plain": {},
          "flash_chunked_loss": dict(attn_impl="flash", loss_chunk=64),
          "no_remat": dict(remat=False)}[variant]
    model = _tiny(**kw)
    params, tokens, targets, want, want_g = _right()
    with jax.default_matmul_precision("highest"):
        # jitted: eager, every interpreted kernel call compiles alone
        got, got_g = jax.jit(jax.value_and_grad(model.loss))(
            params, (tokens, targets))
    assert abs(float(got) - float(want)) <= 2e-5 * float(want)
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = jax.tree_util.tree_leaves_with_path(got_g)
    assert [p for p, _ in flat_w] == [p for p, _ in flat_g]
    for (path, w), (_, g) in zip(flat_w, flat_g):
        name = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _err(g, w) < 2e-3, name
