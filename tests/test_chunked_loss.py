"""The chunked loss head (``loss_chunk > 0``) against the plain one:
``DecoderLM._chunked_ce`` is a ``jax.custom_vjp`` whose forward rule
computes the gradient while each [B, loss_chunk, V] slab of logits is
live (ISSUE 25). Host-only; f32 parameters, so the tolerances are tight.
A CPU run shows results and counts, never a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.base import ModelConfig
from deepspeed_tpu.models.transformer import DecoderLM
from deepspeed_tpu.ops import layers as L

B, S, D, V = 2, 32, 32, 96      # V differs from every other size


def _model(head, loss_chunk):
    return DecoderLM(ModelConfig(
        vocab_size=V, hidden_size=D, intermediate_size=2 * D, num_layers=1,
        num_heads=4, max_seq_len=S, norm_type="rmsnorm",
        activation="swiglu", position_embedding="rope", use_bias=False,
        tie_embeddings=head == "tied", lm_head_bias=head == "biased",
        loss_chunk=loss_chunk, param_dtype=jnp.float32))


def _inputs(model, mask="none"):
    """Parameters (the bias made non-zero), hidden states and targets."""
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    if "lm_head_b" in params:
        params["lm_head_b"] = jnp.asarray(rng.normal(0, 0.5, (V,)),
                                          jnp.float32)
    x = jnp.asarray(rng.normal(0, 1.0, (B, S, D)), jnp.float32)
    targets = rng.integers(0, V, (B, S))
    if mask == "third":
        targets[:, ::3] = -100
    elif mask == "all":
        targets[:] = -100
    return params, x, jnp.asarray(targets, jnp.int32)


def _head_leaves(tree):
    """The leaves the head's loss reaches: W (the embedding where it is
    tied) and the bias."""
    if "lm_head" not in tree:
        return {"embed": tree["embed"]["tokens"]}
    return {k: tree[k] for k in ("lm_head", "lm_head_b") if k in tree}


def _plain_ce(model, params, x, targets):
    return L.cross_entropy_loss(model._project_vocab(params, x), targets)


def _loss_and_grads(fn, model, params, x, targets):
    loss, (gp, gx) = jax.value_and_grad(
        lambda p, h: fn(model, p, h, targets), argnums=(0, 1))(params, x)
    return loss, dict(_head_leaves(gp), x=gx)


def _chunked_ce(model, params, x, targets):
    return model._chunked_ce(params, x, targets)


@pytest.mark.parametrize("chunk", [S, S // 4])
@pytest.mark.parametrize("mask", ["none", "third", "all"])
@pytest.mark.parametrize("head", ["untied", "tied", "biased"])
def test_chunked_loss_and_grads_match_full_logits(head, mask, chunk):
    model = _model(head, chunk)
    params, x, targets = _inputs(model, mask)
    want, want_g = _loss_and_grads(_plain_ce, model, params, x, targets)
    got, got_g = _loss_and_grads(_chunked_ce, model, params, x, targets)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert set(got_g) == set(want_g)
    for k in want_g:
        assert got_g[k].dtype == want_g[k].dtype, k
        np.testing.assert_allclose(got_g[k], want_g[k], atol=1e-5,
                                   rtol=1e-5, err_msg=k)
    if mask == "all":
        assert float(got) == 0.0
        assert all(not np.any(np.asarray(g)) for g in got_g.values())
    else:
        assert all(np.any(np.asarray(g)) for g in got_g.values())


@pytest.mark.parametrize("dtype,scale,tol", [
    (jnp.bfloat16, 1.0, 2e-2),
    # fp16 under a loss scale: the rule applies scale/count in f32 before
    # the one rounding, so what loss scaling protects stays protected
    (jnp.float16, 2.0 ** 12, 4e-3),
])
def test_low_precision_agrees_with_f32(dtype, scale, tol):
    model = _model("biased", S // 4)
    params, x, targets = _inputs(model, "third")
    _, want_g = _loss_and_grads(_plain_ce, model, params, x, targets)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa: E731

    def scaled(model, p, h, t):
        return model._chunked_ce(p, h, t) * scale

    got, got_g = _loss_and_grads(scaled, model, cast(params), cast(x),
                                 targets)
    want = _plain_ce(model, params, x, targets)
    np.testing.assert_allclose(got / scale, want, rtol=tol)
    for k, w in want_g.items():
        assert got_g[k].dtype == dtype, k
        g = np.asarray(got_g[k], np.float32) / scale
        assert np.all(np.isfinite(g)), k
        # error relative to the gradient's own scale, as for a matmul
        err = np.max(np.abs(g - np.asarray(w))) / np.max(np.abs(w))
        assert err < tol, (k, err)


def _batch():
    """Tokens and shifted targets, a third of them masked."""
    tok = np.random.default_rng(0).integers(0, V, (B, S + 1))
    targets = tok[:, 1:].copy()
    targets[:, ::3] = -100
    return tok[:, :-1], targets


@pytest.mark.parametrize("head", ["untied", "tied"])
def test_hessian_vector_product_matches_full_logits(head):
    """runtime/eigenvalue.py's pattern: forward over reverse runs through
    the rules' own ops."""
    chunked, plain = _model(head, S // 4), _model(head, 0)
    params = chunked.init(jax.random.PRNGKey(1))
    batch = _batch()
    v = jax.tree.map(
        lambda p: jax.random.normal(jax.random.PRNGKey(2), p.shape,
                                    p.dtype), params)

    def hvp(model):
        grad_fn = jax.grad(lambda p: model.loss(p, batch))
        return jax.jvp(grad_fn, (params,), (v,))

    (g1, h1), (g0, h0) = hvp(chunked), hvp(plain)
    for got, want in ((g1, g0), (h1, h0)):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, atol=2e-5, rtol=1e-4), got, want)
    assert any(np.any(np.asarray(h)) for h in jax.tree.leaves(h1))


# ---- the mechanism's witness: vocabulary-sized matmuls per chunk ----------
def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def _vocab_dots(jaxpr, in_scan=False, out=None):
    """[(inside a scan body?, output shape)] of every ``dot_general`` with
    a vocabulary-sized dimension on an operand or its result."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
            if any(V in s for s in shapes):
                out.append((in_scan, eqn.outvars[0].aval.shape))
        for inner in _sub_jaxprs(eqn):
            _vocab_dots(inner, in_scan or eqn.primitive.name == "scan", out)
    return out


def _scans_with_vocab_dots(jaxpr, out=None):
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        for inner in _sub_jaxprs(eqn):
            if eqn.primitive.name == "scan":
                n = len(_vocab_dots(inner))
                if n:
                    out.append((eqn.params["length"], n))
            _scans_with_vocab_dots(inner, out)
    return out


@pytest.mark.parametrize("head", ["untied", "tied", "biased"])
def test_primal_runs_one_vocab_matmul_per_chunk(head):
    """No gradient asked (eval_batch, the benchmark's agreement path): a
    loss-only scan that pays for no gradient."""
    model = _model(head, S // 4)
    params, batch = model.init(jax.random.PRNGKey(0)), _batch()
    jaxpr = jax.make_jaxpr(jax.jit(model.loss))(params, batch).jaxpr
    assert _scans_with_vocab_dots(jaxpr) == [(4, 1)]
    assert all(in_scan for in_scan, _ in _vocab_dots(jaxpr))


@pytest.mark.parametrize("head", ["untied", "tied", "biased"])
def test_grad_runs_three_vocab_matmuls_per_chunk(head):
    """x_c @ W, dlogits @ W^T and x_c^T @ dlogits, all in the forward
    rule's scan: four (a second x_c @ W in a backward scan) means the
    recomputation is back."""
    model = _model(head, S // 4)
    params, batch = model.init(jax.random.PRNGKey(0)), _batch()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(model.loss))(
        params, batch).jaxpr
    assert _scans_with_vocab_dots(jaxpr) == [(4, 3)]
    dots = _vocab_dots(jaxpr)
    assert all(in_scan for in_scan, _ in dots)
    # the slab of logits is never larger than [B, loss_chunk, V]
    assert sorted(shape for _, shape in dots) == sorted(
        [(B, S // 4, V), (B, S // 4, D), (D, V)])


def test_frozen_head_drops_the_dead_weight_gradient():
    """linear/optimized_linear.py differentiates ``module.loss`` with the
    head frozen: dW is dead after the backward rule, and XLA takes its
    matmul and its accumulator out of the scan."""
    model = _model("untied", S // 4)
    params, x, targets = _inputs(model)
    fn = jax.jit(jax.grad(
        lambda h: model._chunked_ce(params, h, targets)))
    hlo = fn.lower(x).compile().as_text()
    dots = [line for line in hlo.splitlines()
            if " dot(" in line and f"{V}" in line.split(" dot(")[0]]
    assert not any(f"[{D},{V}]" in d.split(" dot(")[0] for d in dots), dots
