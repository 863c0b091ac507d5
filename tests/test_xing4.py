"""Xing4 (ISSUE 56): four residual streams mixed by Sinkhorn-constrained
hyper-connections round rotated, low-rank-query latent attention and a held
share of bias-corrected sigmoid-routed experts, checked on the CPU at tiny
sizes: the whole model against the plain float32 reference the benchmark
keeps (``benchmark/architectures/xing4.py``, which imports nothing from
the program), ``ops/mhc.py`` against a loop over the tokens, and the six
kernels of ``ops/pallas/mhc.py`` in interpret mode against ``ops/mhc.py``.
The planted faults and the shares are ``tests/test_xing4_limits.py``'s, the
engine ``tests/test_xing4_engine.py``'s (a file is one worker's under
``--dist loadfile``). A CPU run shows results and counts, never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import mhc
from deepspeed_tpu.ops.pallas import mhc as kernels

from helpers.families import config_of, right, tail_loss_grads, tiny
from helpers.families import (_err, _reference_grads,  # noqa: F401
                               _telemetry_isolation)
from architectures import xing4 as arch  # noqa: E402  (benchmark/, on
#                                      sys.path by families)
from kinds import train_job  # noqa: E402
from lib import modelspec  # noqa: E402

CONFIG = config_of("xing4_0")
_tiny = functools.partial(tiny, "xing4_0")

F32 = jnp.float32


# ---- the whole model against the float32 reference -------------------------
@functools.lru_cache(maxsize=None)
def _right():
    params, tokens, targets, want, m = right("xing4_0")
    return params, tokens, targets, want, _reference_grads(
        arch, params, tokens, targets, m)


# every parameter group ISSUE 56 names, by the leaf's path
_NAMED = ("phi", "b", "alpha", "wq_a", "q_norm", "wq_b", "w_kva", "kv_norm",
          "w_kvb", "wo", "w_gate", "w_down", "router", "ln1_scale",
          "ln2_scale", "tokens", "scale", "lm_head")


@pytest.mark.parametrize("variant", ["plain_f32", "flash_chunked_loss_f32",
                                     "flash_chunked_loss_bf16"])
def test_loss_logits_and_gradients_match_the_float32_reference(variant):
    """Float32: loss to 2e-5, tail logits to 5e-4 of their largest, and on
    the cell's path (flash kernels, chunked loss, every layer rematted,
    the scan over the routed layers) every gradient, ``phi``, ``b`` and
    ``alpha`` of both sublayers among them, to 3e-3 of its largest; the
    selection bias's gradient is zero on both sides. bfloat16 weights
    (what the engine computes with) at the init's own scale against the
    float32 reference on the same weights, over the positions its mask
    counts: loss to 0.5%, logits to 5% of their largest and 2% rms."""
    kw = dict(remat=False) if variant == "plain_f32" else dict(
        attn_impl="flash", loss_chunk=64)
    model = _tiny(**kw)
    params, tokens, targets, (want, want_tail, _), want_g = _right()
    if variant.endswith("bf16"):
        params = model.init(jax.random.PRNGKey(3))
        m = modelspec.reference_model(arch, model, CONFIG["check"])
        with jax.default_matmul_precision("highest"):
            want, want_tail, counted = arch.reference(
                params, tokens, targets, m, 32)
        low = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)
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
        if path[-1].key == "router_bias":
            assert not np.any(w) and not np.any(g), name
            continue
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _err(g, w) < 3e-3, name
    assert set(_NAMED) <= seen


# ---- ops/mhc.py against a loop over the tokens -----------------------------
def _inputs(t=48, n=4, c=32, dtype=F32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    k = n * (n + 2)
    x = jax.random.normal(ks[0], (t, n, c), F32).astype(dtype)
    phi = (jax.random.normal(ks[1], (n * c, k), F32)
           * (n * c) ** -0.5).astype(dtype)
    b = (0.5 * jax.random.normal(ks[2], (k,), F32)).astype(dtype)
    b = b.at[2 * n:].add(jnp.eye(n).reshape(-1).astype(dtype))
    alpha = jnp.asarray([0.5, 0.35, 0.65], dtype)
    y = jax.random.normal(ks[3], (t, c), F32).astype(dtype)
    return x, phi, b, alpha, y


def test_the_two_passes_are_the_equations_a_token_at_a_time():
    """``mhc_pre`` / ``mhc_post`` (the ``jax.numpy`` forms, tokens on the
    last axis inside the Sinkhorn iteration) against ISSUE 56's equations
    written for ONE token in numpy float64 and looped; ``H_res`` is doubly
    stochastic to the residual the op reports, and the residual is the
    largest deviation of any row or column sum."""
    x, phi, b, alpha, y = _inputs()
    t, n, c = x.shape
    eps, lo, hi, iters = 1e-6, -30.0, 30.0, 20
    u, h_post, h_res, residual, _ = mhc.mhc_pre(
        x[None], phi, b, alpha, eps=eps, clamp=(lo, hi), iters=iters)
    out = mhc.mhc_post(x[None], y[None], h_post, h_res)
    X, P, B, A, Y = (np.asarray(v, np.float64) for v in (x, phi, b, alpha, y))
    sig = lambda v: 1 / (1 + np.exp(-v))  # noqa: E731
    worst = 0.0
    for tok in range(t):
        vec = X[tok].reshape(-1)
        xv = vec / np.sqrt(np.mean(vec ** 2) + eps)
        z = xv @ P
        h_pre = sig(A[0] * z[:n] + B[:n])
        post = 2 * sig(A[1] * z[n:2 * n] + B[n:2 * n])
        m = np.exp(np.clip(A[2] * z[2 * n:] + B[2 * n:], lo, hi)).reshape(n, n)
        for _ in range(iters):
            m = m / (m.sum(axis=1, keepdims=True) + eps)
            m = m / (m.sum(axis=0, keepdims=True) + eps)
        worst = max(worst, np.abs(m.sum(axis=1) - 1).max(),
                    np.abs(m.sum(axis=0) - 1).max())
        np.testing.assert_allclose(u[0, tok], h_pre @ X[tok], atol=2e-5)
        np.testing.assert_allclose(h_post[0, tok], post, atol=2e-6)
        np.testing.assert_allclose(h_res[0, tok], m, atol=2e-6)
        np.testing.assert_allclose(
            out[0, tok], m @ X[tok] + post[:, None] * Y[tok][None],
            atol=3e-5)
    got = np.asarray(h_res[0], np.float64)
    sums = max(np.abs(got.sum(-1) - 1).max(), np.abs(got.sum(-2) - 1).max())
    assert float(residual) == pytest.approx(sums, abs=1e-6)
    assert float(residual) == pytest.approx(worst, abs=1e-6)
    assert 0 < float(residual) < 1e-3
    # a single iteration leaves the rows far off: the counter sees it
    one = mhc.mhc_pre(x[None], phi, b, alpha, eps=eps, clamp=(lo, hi),
                      iters=1)[3]
    assert float(one) > 30 * float(residual)


def test_a_logit_past_the_clamp_is_clamped_and_the_shapes_are_checked():
    x, phi, b, alpha, y = _inputs()
    n = x.shape[1]
    far = b.at[2 * n + 1].set(100.0)
    u, h_post, h_res, residual, _ = mhc.mhc_pre(x[None], phi, far, alpha)
    assert np.all(np.isfinite(np.asarray(h_res)))
    assert np.asarray(h_res)[0, :, 0, 1].min() > 0.9
    loose = mhc.mhc_pre(x[None], phi, far, alpha, clamp=(-1e30, 1e30))[2]
    assert not np.all(np.isfinite(np.asarray(loose)))
    with pytest.raises(ValueError, match="mhc_pre"):
        mhc.mhc_pre(x[None], phi[:, :-1], b, alpha)
    with pytest.raises(ValueError, match="mhc_post"):
        mhc.mhc_post(x[None], y[None, :, :-1], h_post, h_res)


# ---- the kernels, interpreted, against ops/mhc.py --------------------------
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("tokens", [64, 96], ids=["t64", "t96"])
def test_the_kernels_match_the_jnp_forms_forward_and_both_cotangents(
        dtype, tokens):
    """``ds_mhc_pre_*`` and ``ds_mhc_post_*`` in interpret mode against
    ``ops/mhc.py`` ``pre_reference`` / ``post_reference`` on the same
    operands: the outputs, and the gradient of a weighted sum of BOTH
    outputs of the pre pass (``raw`` and ``u``) to every operand (``X``,
    ``phi``, ``b``, ``alpha``; ``X``, ``y``, ``H_post``, ``H_res``).
    Float32 to 2e-5 of the largest; bfloat16 streams to a rounding. 96
    tokens are three row tiles of 32: ``dphi``, ``db`` and ``dalpha`` are
    summed over them inside the kernel. The pre pass's ``raw`` is a
    128-lane row (zeros behind its columns) and its third result is ``X``
    itself; the post pass reads its coefficients as ONE row
    (``coefficient_row``)."""
    x, phi, b, alpha, y = _inputs(t=tokens, dtype=dtype)
    t, n, c = x.shape
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    w_raw = jax.random.normal(ks[0], (t, n * (n + 2)), F32)
    w_u = jax.random.normal(ks[1], (t, c), F32)
    w_out = jax.random.normal(ks[2], (t, n, c), F32)
    h_post = 2 * jax.random.uniform(ks[3], (t, n), F32)
    h_res = jax.random.uniform(ks[4], (t, n * n), F32)
    tol = 2e-5 if dtype == F32 else 1.2e-2

    def kernel_pre(*a):
        raw, u, on = kernels.mhc_pre(*a)
        assert on.shape == a[0].shape and raw.shape == (t, 128)
        return raw[:, :n * (n + 2)], u

    def kernel_post(x, y, h_post, h_res):
        return kernels.mhc_post(x, y, kernels.coefficient_row(h_post, h_res))

    def pre(fn):
        def loss(x, phi, b, alpha):
            raw, u = fn(x, phi, b, alpha, 1e-6)
            return (raw, u), jnp.sum(raw * w_raw) + jnp.sum(
                u.astype(F32) * w_u)
        return jax.jit(lambda *a: (loss(*a)[0], jax.grad(
            lambda *a: loss(*a)[1], argnums=(0, 1, 2, 3))(*a)))

    def post(fn):
        loss = lambda *a: jnp.sum(fn(*a).astype(F32) * w_out)  # noqa: E731
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            loss, argnums=(0, 1, 2, 3))(*a)))

    for make, got_fn, want_fn, args in (
            (pre, kernel_pre, mhc.pre_reference, (x, phi, b, alpha)),
            (post, kernel_post, mhc.post_reference, (x, y, h_post, h_res))):
        got, want = make(got_fn)(*args), make(want_fn)(*args)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.shape == w.shape and g.dtype == w.dtype
            assert _err(g.astype(F32), w.astype(F32)) < tol


def _rows(t, n, seed=3):
    """Float32 rows as ``ds_mhc_pre_fwd`` writes them, [t, 128]: n (n +
    2) logits a token, two of them past the clamp, zeros behind."""
    k = n * (n + 2)
    raw = 2.0 * jax.random.normal(jax.random.PRNGKey(seed + t + n), (t, k),
                                  F32)
    raw = raw.at[3, 2 * n + 1].set(100.0).at[5, 2 * n].set(-80.0)
    return jnp.pad(raw, ((0, 0), (0, 128 - k)))


_HYPER = dict(eps=1e-6, clamp=(-30.0, 30.0), iters=20)


def _pair(raw, n):
    return kernels.coefficients(raw, n, *_HYPER.values())


@pytest.mark.parametrize("tokens, n", [(64, 4), (96, 4), (64, 3), (96, 2),
                                       (256, 4)])
def test_the_coefficient_kernel_is_the_jnp_form(tokens, n, monkeypatch):
    """(a) ``ds_mhc_coef_fwd`` in interpret mode against ``ops/mhc.py``
    ``coefficients`` on the same float32 rows: ``H_post``, ``H_res`` and
    the Sinkhorn residual to 1e-6 absolute, zeros in the pads of the row it
    writes, ``[H_post | H_res | 0]``. 256 tokens at 128 a grid step are
    two grid steps of one sublane row; the residual is the larger."""
    monkeypatch.setattr(kernels, "_COEF_TOKENS", 128)
    raw = _rows(tokens, n)
    k = n * (n + 2)
    want = jax.jit(lambda r: mhc.coefficients(r[:, n:k], n, **_HYPER))(raw)
    coef, residual = jax.jit(lambda r: _pair(r, n))(raw)
    assert coef.shape == raw.shape and coef.dtype == F32
    assert np.all(np.isfinite(np.asarray(coef)))
    np.testing.assert_allclose(coef[:, :n], want[0], atol=1e-6, rtol=0)
    np.testing.assert_allclose(coef[:, n:n + n * n], want[1], atol=1e-6,
                               rtol=0)
    assert float(jnp.abs(coef[:, n + n * n:]).max()) == 0.0
    assert residual.shape == ()
    assert float(residual) == pytest.approx(float(want[2]), abs=1e-6)


@pytest.mark.parametrize("tokens, n, tile", [(64, 4, 1024), (96, 3, 1024),
                                             (256, 4, 128), (2048, 2, 1024)])
def test_the_coefficient_backward_is_the_unrolled_gradient(tokens, n, tile,
                                                           monkeypatch):
    """(b) ``ds_mhc_coef_bwd`` against ``jax.vjp`` of the ``jax.numpy``
    form (twenty unrolled iterations) for random ``dH_post``, ``dH_res``
    handed in as the row ``ds_mhc_post_bwd`` writes: within 1e-5 of the
    largest entry, and exactly 0 in the pre columns and the pads. 2048
    tokens are two grid steps of eight sublane rows (the strided reads of
    the transposed tile)."""
    monkeypatch.setattr(kernels, "_COEF_TOKENS", tile)
    raw = _rows(tokens, n)
    k = n * (n + 2)
    ks = jax.random.split(jax.random.PRNGKey(tokens), 2)
    d_post = jax.random.normal(ks[0], (tokens, n), F32)
    d_res = jax.random.normal(ks[1], (tokens, n * n), F32)
    want = jax.jit(lambda r: jax.vjp(
        lambda r: mhc.coefficients(r[:, n:k], n, **_HYPER)[:2], r)[1](
            (d_post, d_res))[0])(raw)
    row = jnp.pad(jnp.concatenate([d_post, d_res], axis=-1),
                  ((0, 0), (0, 128 - n - n * n)))
    # what the post pass leaves in the lanes it does not own must not leak
    row = row.at[:, n + n * n:].set(7.0)
    got = jax.jit(lambda r: jax.vjp(lambda r: _pair(r, n)[0], r)[1](
        row)[0])(raw)
    assert got.shape == raw.shape and got.dtype == F32
    assert float(jnp.abs(want).max()) > 0.1
    assert _err(got[:, n:k], want[:, n:k]) < 1e-5
    assert float(jnp.abs(got[:, :n]).max()) == 0.0
    assert float(jnp.abs(got[:, k:]).max()) == 0.0
    with pytest.raises(ValueError, match="do not fit one row"):
        _pair(raw, 11)


@pytest.mark.parametrize("flow", ["handed_on", "own_x", "own_coefficients",
                                  "pre_alone"])
def test_the_op_calls_the_kernels_where_the_backend_is_the_chip(
        flow, monkeypatch):
    """``ops/mhc.py`` takes the kernel pairs on a TPU and the ``jax.numpy``
    forms elsewhere; through the kernels (interpreted) the op's results and
    the rematted gradient of a sublayer (``mhc_pre`` -> a matmul ->
    ``mhc_post``) are the ``jax.numpy`` path's: ``dX``, ``dphi``, ``db``,
    ``dalpha``, ``dy``'s weight. (c) ``handed_on``: ``mhc_post`` reads the
    ``X`` and the row that ``mhc_pre`` handed on, so the jaxpr holds the
    six kernels and no ``concatenate`` of ``H_post`` and ``H_res``, and
    ``ds_mhc_handed_on_bytes`` says what ``ds_mhc_pre_bwd`` took in.
    ``own_x``: the caller's own ``x`` (two consumers: JAX adds).
    ``own_coefficients``: other arrays than the handed-on ones (a planted
    fault's ``0.5 * h_post``) are made a row of. ``pre_alone``: no
    consumer of the handed-on ``X``."""
    x, phi, b, alpha, y = _inputs(t=64)
    w = jax.random.normal(jax.random.PRNGKey(5), (x.shape[-1],) * 2, F32) \
        * x.shape[-1] ** -0.5

    def loss(*a):
        def sublayer(x, phi, b, alpha, w):      # anew a trace: remat caches
            u, h_post, h_res, _, on = mhc.mhc_pre(x[None], phi, b, alpha)
            if flow == "pre_alone":
                return u @ w + jnp.sum(h_res, axis=-1) @ jnp.ones(
                    (x.shape[1], x.shape[-1])) + jnp.sum(h_post)
            if flow == "own_x":
                on = x[None]
            if flow == "own_coefficients":
                h_post = 0.5 * h_post
            return mhc.mhc_post(on, u @ w, h_post, h_res)
        return jnp.sum(jax.checkpoint(sublayer)(*a) ** 2)

    args = (x, phi, b, alpha, w)
    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
    want = jax.jit(grad)(*args)
    monkeypatch.setattr(mhc, "_use_kernels", lambda: True)
    import deepspeed_tpu.telemetry as telemetry
    telemetry.configure()
    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(*args))
    for name in ("ds_mhc_pre_fwd", "ds_mhc_pre_bwd", "ds_mhc_coef_fwd",
                 "ds_mhc_coef_bwd"):
        assert name in jaxpr
    if flow != "pre_alone":
        assert "ds_mhc_post_fwd" in jaxpr and "ds_mhc_post_bwd" in jaxpr
    if flow == "handed_on":
        assert "concatenate[dimension=1]" not in jaxpr
        assert telemetry.get_registry().get(
            "ds_mhc_handed_on_bytes").value() == x.size * 4
    if flow == "own_coefficients":
        assert "concatenate[dimension=1]" in jaxpr
    got = jax.jit(grad)(*args)
    for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w_.shape and _err(g, w_) < 2e-5
