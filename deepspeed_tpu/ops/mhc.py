"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606): the residual path of a stack whose
state between sublayers is ``n`` streams of ``C`` channels a token, ``X``
[B, S, n, C], mixed round each sublayer ``F`` by three sets of
coefficients made from ``X`` itself (``phi`` [n C, n (n + 2)], columns
``[pre | post | res]``; ``b`` [n (n + 2)]; ``alpha`` [3])::

    xv  = vec(X) / sqrt(mean(vec(X)^2) + eps)            no learned gain
    raw = alpha_k * (xv phi) + b                         k: pre, post, res
    H_pre = sigmoid(raw_pre);   H_post = 2 sigmoid(raw_post)      [n] each
    M = exp(clip(raw_res, lo, hi)) as [n, n];  ``iters`` times:
        M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps);  H_res = M
    u  = sum_i H_pre[i] X[i]                   mhc_pre: the sublayer's input
    X' = H_res X + H_post^T F(norm(u))         mhc_post

Float32 inside, the streams in and out in their own dtype. ``xv phi`` is
taken as ``(vec(X) phi)`` divided by the rms after it (the paper's order:
one read of ``X`` gives both). The Sinkhorn iteration is differentiated as
written, ``iters`` unrolled steps; it runs with the TOKENS on the last
axis ([n, n, T]: a [T, n, n] array's [4, 4] minor dimensions would fill a
thirty-second of a vector register).

On the chip all of it is Pallas kernel pairs (``ops/pallas/mhc.py``):
``ds_mhc_pre_*`` gives ``raw`` and ``u`` in one read of ``X``,
``ds_mhc_coef_*`` (scope ``ds.mhc_coef``) makes ``H_post`` and ``H_res`` of
it, ``ds_mhc_post_*`` gives ``X'`` in one read of ``X`` and ``y``; a
token's coefficients pass from kernel to kernel as ONE 128-lane float32
row, forward and backward, with no op of XLA's between. ``mhc_pre`` hands
``X`` on to ``mhc_post`` (``Handed``), so the streams have ONE consumer and
the post pass's ``dX`` is added inside ``ds_mhc_pre_bwd``. On any other
backend the passes and ``coefficients`` are the ``jax.numpy`` forms here,
which the kernels are tested against.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


class Handed(NamedTuple):
    """What ``mhc_pre`` hands ``mhc_post`` on the chip in ``X``'s place:
    the streams as the pre pass's kernels passed them on, and the row
    ``[H_post | H_res | 0]`` that ``h_post`` and ``h_res`` were cut from.
    ``mhc_post`` reads the row when it is given those two arrays
    themselves, and builds one from whatever else it is given."""
    x: jax.Array
    row: jax.Array
    h_post: jax.Array
    h_res: jax.Array


def _use_kernels() -> bool:
    return jax.default_backend() == "tpu"


def expand_alpha(alpha, n: int):
    """alpha [3] (pre, post, res) as a row of n (n + 2): a column's own
    scalar."""
    return alpha.astype(_F32)[np.repeat(np.arange(3), [n, n, n * n])]


def coefficients(raw, n: int, *, eps: float, clamp: tuple, iters: int):
    """``raw`` [T, n + n n] float32 (the post and res columns) ->
    (H_post [T, n], H_res [T, n n] row-major, the largest |rowsum - 1| or
    |colsum - 1| of any token's H_res: what ``iters`` iterations leave)."""
    with jax.named_scope("ds.mhc_coef"):
        t = raw.shape[0]
        raw = raw.T                                   # tokens last
        h_post = 2.0 * jax.nn.sigmoid(raw[:n])
        m = jnp.exp(jnp.clip(raw[n:], *clamp)).reshape(n, n, t)
        for _ in range(iters):
            m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
            m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        done = jax.lax.stop_gradient(m)
        residual = jnp.maximum(
            jnp.max(jnp.abs(jnp.sum(done, axis=1) - 1.0)),
            jnp.max(jnp.abs(jnp.sum(done, axis=0) - 1.0)))
        return h_post.T, m.reshape(n * n, t).T, residual


def pre_reference(x, phi, b, alpha, eps):
    """x [T, n, C] -> (raw [T, n (n + 2)] float32, u [T, C] in x's
    dtype)."""
    t, n, c = x.shape
    xf = x.astype(_F32)
    flat = xf.reshape(t, n * c)
    r = jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    raw = expand_alpha(alpha, n) * ((flat @ phi.astype(_F32)) * r) \
        + b.astype(_F32)
    h_pre = jax.nn.sigmoid(raw[:, :n])
    u = jnp.sum(h_pre[:, :, None] * xf, axis=1)
    return raw, u.astype(x.dtype)


def post_reference(x, y, h_post, h_res):
    """x [T, n, C], y [T, C], h_post [T, n], h_res [T, n n] -> X'."""
    t, n, _ = x.shape
    out = jnp.einsum("tij,tjc->tic", h_res.reshape(t, n, n), x.astype(_F32))
    out = out + h_post[:, :, None] * y.astype(_F32)[:, None, :]
    return out.astype(x.dtype)


def mhc_pre(x, phi, b, alpha, *, eps: float = 1e-6,
            clamp: tuple = (-30.0, 30.0), iters: int = 20):
    """The pass in front of a sublayer. x [B, S, n, C]; phi [n C,
    n (n + 2)]; b [n (n + 2)]; alpha [3]. Returns (u [B, S, C] in x's
    dtype, H_post [B, S, n] and H_res [B, S, n, n] float32, the Sinkhorn
    residual: a float32 scalar, no gradient, and the streams for
    ``mhc_post`` to read: ``x``, or on the chip a ``Handed``; a caller that
    gives ``mhc_post`` this and not its own ``x`` leaves the streams ONE
    consumer)."""
    bsz, s, n, c = x.shape
    if phi.shape != (n * c, n * (n + 2)) or b.shape != (n * (n + 2),) \
            or alpha.shape != (3,):
        raise ValueError(
            f"mhc_pre: x {x.shape} wants phi [{n * c}, {n * (n + 2)}], b "
            f"[{n * (n + 2)}] and alpha [3], not {phi.shape}, {b.shape}, "
            f"{alpha.shape}")
    flat = x.reshape(bsz * s, n, c)
    on, row = x, None
    if _use_kernels():
        from .pallas import mhc as kernels
        raw, u, flat = kernels.mhc_pre(flat, phi, b, alpha, float(eps))
        row, residual = kernels.coefficients(
            raw, n, float(eps), tuple(float(v) for v in clamp), int(iters))
        h_post, h_res = row[:, :n], row[:, n:n + n * n]
    else:
        with jax.named_scope("ds.mhc_pre"):
            raw, u = pre_reference(flat, phi, b, alpha, eps)
        h_post, h_res, residual = coefficients(
            raw[:, n:], n, eps=eps, clamp=clamp, iters=iters)
    u, h_post, h_res = (u.reshape(bsz, s, c), h_post.reshape(bsz, s, n),
                        h_res.reshape(bsz, s, n, n))
    if row is not None:
        on = Handed(flat.reshape(x.shape), row, h_post, h_res)
    return u, h_post, h_res, residual, on


def mhc_post(x, y, h_post, h_res):
    """The pass behind a sublayer: ``X' = H_res X + H_post^T y``. x
    [B, S, n, C] (or what ``mhc_pre`` handed on), y [B, S, C], H_post
    [B, S, n], H_res [B, S, n, n]; returns X' like x."""
    row = None
    if isinstance(x, Handed):
        if h_post is x.h_post and h_res is x.h_res:
            row = x.row
        x = x.x
    bsz, s, n, c = x.shape
    if y.shape != (bsz, s, c) or h_post.shape != (bsz, s, n) \
            or h_res.shape != (bsz, s, n, n):
        raise ValueError(
            f"mhc_post: x {x.shape} wants y [B, S, C], H_post [B, S, n] "
            f"and H_res [B, S, n, n], not {y.shape}, {h_post.shape}, "
            f"{h_res.shape}")
    t = bsz * s
    args = (x.reshape(t, n, c), y.reshape(t, c).astype(x.dtype),
            h_post.reshape(t, n).astype(_F32),
            h_res.reshape(t, n * n).astype(_F32))
    if _use_kernels():
        from .pallas import mhc as kernels
        if row is None:
            row = kernels.coefficient_row(*args[2:])
        out = kernels.mhc_post(*args[:2], row)
    else:
        with jax.named_scope("ds.mhc_post"):
            out = post_reference(*args)
    return out.reshape(x.shape)
