"""The router's selection as a kernel pair (ISSUE 67): ``ops/pallas/router.py``
``ds_router_fwd`` / ``ds_router_bwd`` under ``moe/sharded_moe.py``
``top_k_of`` against XLA's ``lax.top_k``, gather and ``bincount``
(``_top_k_xla``), in interpret mode, every call jitted (one program a
case), at the eight routed cells' (router, experts, k) and 128 to 384
tokens: experts chosen, weights, load and the weights' gradient, element
for element, with planted ties. Which shapes a router takes the pair at
(``router.fits``: a step's worth of tokens in whole grid tiles; the pair
itself runs at any whole lane blocks, ``router.runs``), and what the gauge
says of each. A CPU run shows results and counts, never a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepspeed_tpu import telemetry
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import (_top_k_xla, sigmoid_top_k,
                                           softmax_top_k, top_k_of)
from deepspeed_tpu.ops.pallas import router

from helpers.families import _telemetry_isolation  # noqa: F401

F32 = jnp.float32
# the cell's family: (router, experts routed over, choices a token, tokens
# a step of its cell)
CELLS = {"nemotron": ("sigmoid", 512, 22, 8192),
         "kanana": ("sigmoid", 128, 6, 32768),
         "qwen3_next": ("softmax", 512, 10, 16384),
         "kimi": ("sigmoid", 256, 8, 16384),
         "mellum": ("softmax", 64, 8, 16384),
         "laguna": ("softmax", 256, 10, 8192),
         "lfm2": ("sigmoid", 64, 4, 2 * 8192),
         "xing4": ("sigmoid", 64, 4, 8192)}
TOKENS = 128


def _same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, (g, w)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _pair(select, scores, k):
    """``top_k_of``'s kernel branch at any shape the pair runs at."""
    idx, w, load = router.top_k_rows(
        select.T, None if scores is None else scores.T, k)
    return idx.T, w.T, load


def _scores(kind, experts, seed=0, tokens=TOKENS):
    """(select, scores or None) as the router of ``kind`` makes them, with
    ties planted in ``select``: rows 0 to 7 hold eight experts at the
    row's maximum, rows 8 to 15 every expert equal, and for a sigmoid
    router rows 16 to 23 hold scores that differ under a bias that makes
    ``select`` equal."""
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((tokens, experts), dtype=np.float32)
    select = np.array(jax.nn.softmax(jnp.asarray(logits), axis=-1)
                      if kind == "softmax" else
                      jax.nn.sigmoid(jnp.asarray(logits)))
    scores = None
    if kind == "sigmoid":
        scores = select.copy()
        select += 0.05 * rng.standard_normal(experts, dtype=np.float32)
        scores[16:24, 1::4] = 0.25 + 0.001 * rng.integers(
            0, 8, scores[16:24, 1::4].shape).astype(np.float32)
        select[16:24, 1::4] = 2.0       # a bias of 2 - scores
        scores = jnp.asarray(scores)
    select[:8, 3::8][:, :8] = select[:8].max(axis=1, keepdims=True)
    select[8:16] = select[8:16, :1]
    return jnp.asarray(select), scores


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_pair_is_xlas_top_k_gather_and_bincount_element_for_element(cell):
    kind, experts, k, step = CELLS[cell]
    select, scores = _scores(kind, experts)
    # the cell's router takes the pair; the pair runs at this case's tokens
    assert router.fits(step, experts, k) and router.runs(TOKENS, experts, k)
    ct = jax.random.normal(jax.random.PRNGKey(1), (TOKENS, k), F32)

    def both(fn):
        def loss(select, scores):
            idx, w, load = fn(select, scores, k)
            return jnp.sum(w * ct), (idx, w, load)
        # the weights' gradient goes to the scores they are
        return jax.jit(jax.value_and_grad(
            loss, argnums=0 if scores is None else 1, has_aux=True))

    (_, got), dgot = both(_pair)(select, scores)
    (_, want), dwant = both(_top_k_xla)(select, scores)
    _same(got, want)
    _same(dgot, dwant)
    idx, _, load = (np.asarray(x) for x in got)
    # a stable sort's order: among equals the lower index first; the ties
    # were there
    np.testing.assert_array_equal(idx, np.argsort(
        -np.asarray(select), axis=1, kind="stable")[:, :k])
    assert np.sum(np.asarray(select[0]) == np.asarray(select[0]).max()) >= 8
    assert list(idx[8]) == list(range(k))
    if scores is not None:
        assert list(idx[16]) == list(range(1, 4 * k, 4))
    assert load.sum() == TOKENS * k and load.dtype == np.int32
    assert np.count_nonzero(np.asarray(dgot)) <= TOKENS * k


@pytest.mark.parametrize("cell", ["kanana", "laguna", "mellum", "xing4"])
def test_a_routers_weights_and_gradient_do_not_see_the_form(cell,
                                                           monkeypatch):
    """``sigmoid_top_k`` / ``softmax_top_k`` on logits, the renormalising
    divide and the scaling behind the pair: what they return and the
    gradient to the logits equal the XLA form's to the bit, at 384 tokens
    (three lane blocks: a tile of 128)."""
    kind, experts, k, _ = CELLS[cell]
    tokens = 384
    logits = jax.random.normal(jax.random.PRNGKey(2), (tokens, experts), F32)
    logits = logits.at[:4, 1].set(logits[:4, 0])        # a tie a row
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (experts,), F32)
    ct = jax.random.normal(jax.random.PRNGKey(4), (tokens, k), F32)

    def route(logits):
        if kind == "sigmoid":
            return sigmoid_top_k(logits, bias, k, scaling=2.5)
        return softmax_top_k(logits, k)

    def run():
        return jax.jit(jax.value_and_grad(
            lambda x: (lambda out: (jnp.sum(out[1] * ct), out))(route(x)),
            has_aux=True))(logits)

    monkeypatch.setattr(router, "fits", router.runs)
    (_, got), dgot = run()
    monkeypatch.setattr(router, "fits", lambda *a: False)
    (_, want), dwant = run()
    _same(got, want)
    _same(dgot, dwant)
    assert np.asarray(got[3]).sum() == tokens * k


@pytest.mark.parametrize("tokens,experts,k", [
    (96, 64, 4), (128, 12, 2), (1024, 512, 22), (8192 + 128, 64, 4)])
def test_a_shape_outside_the_rule_takes_xlas_form_with_the_same_results(
        tokens, experts, k):
    """A step's worth of rows in whole grid tiles, experts in whole
    sublane tiles: what does not fit (the tiny test models' 1024 tokens
    among it) is served by XLA's ops, counted ``form=xla``, and reads what
    ``lax.top_k`` reads; the pair refuses what it cannot run."""
    assert not router.fits(tokens, experts, k)
    select = jax.random.uniform(jax.random.PRNGKey(5), (tokens, experts))
    scores = jax.random.uniform(jax.random.PRNGKey(6), (tokens, experts))
    telemetry.configure()
    idx, w, load = jax.jit(lambda a, b: top_k_of(a, b, k))(select, scores)
    g = telemetry.get_registry().get("ds_router_calls")
    labels = dict(experts=str(experts), k=str(k))
    assert g.value(form="xla", **labels) == 1
    assert g.value(form="kernel", **labels) == 0
    want = lax.top_k(select, k)[1]
    _same((idx, w, load),
          (want, jnp.take_along_axis(scores, want, axis=-1),
           jnp.bincount(want.reshape(-1), length=experts).astype(jnp.int32)))
    if not router.runs(tokens, experts, k):
        with pytest.raises(ValueError, match="top_k_rows"):
            router.top_k_rows(select.T, scores.T, k)


def test_the_gauge_counts_a_routed_layers_router_by_form_experts_and_k():
    """``moe_ffn_held`` at a cell's 8192 tokens builds ONE router as the
    pair (``form=kernel``), at a tiny model's 1024 as XLA's; the layer's
    ``load`` is the ``bincount`` of the experts chosen either way."""
    d, experts, held, k = 16, 16, 2, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    ex = {"w_gate": jax.random.normal(ks[0], (held, d, 128), F32) / 4,
          "w_up": jax.random.normal(ks[1], (held, d, 128), F32) / 4,
          "w_down": jax.random.normal(ks[2], (held, 128, d), F32) / 11}
    rw = jax.random.normal(ks[3], (d, experts), F32)
    telemetry.configure()

    def count(form):
        g = telemetry.get_registry().get("ds_router_calls")
        return 0 if g is None else g.value(form=form, experts="16", k="2")

    for seq, form in ((8192, "kernel"), (1024, "xla")):
        assert router.fits(seq, experts, k) == (form == "kernel")
        x = jax.random.normal(jax.random.PRNGKey(seq), (1, seq, d), F32)
        before = count(form)
        _, counts = jax.jit(lambda x: sharded_moe.moe_ffn_held(
            x, rw, jnp.zeros((experts,)), ex, None, k=k))(x)
        assert count(form) == before + 1
        idx = _top_k_xla(jax.nn.sigmoid(x[0] @ rw), None, k)[0]
        _same(counts["load"], jnp.bincount(
            idx.reshape(-1), length=experts).astype(jnp.int32))
