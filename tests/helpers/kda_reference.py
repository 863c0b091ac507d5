"""The preparation of chunked KDA in ``jax.numpy``, as ``ops/kda.py`` held
it until PR 35: the reference of the kernels ``ds_kda_prep_fwd`` /
``ds_kda_prep_bwd`` (``ops/pallas/kda.py``), whose gradients are this
one's autodiff. ``tests/test_kimi_linear.py`` compares them;
``tools/kda_kernel_bench.py`` times them side by side on the chip."""

from __future__ import annotations

import jax
import jax.numpy as jnp

SUB = 8         # rows a sub-block of the score matrices
CLAMP = 60.0    # largest exponent a sub-block's own columns may carry


def _scores(q, k, kb, beta, G, dt):
    """(a_kk, a_qk) [..., C, C]: ``sum_c x_ic kb_jc exp(G_ic - G_jc)`` for
    x = k below the diagonal and for x = q on and below it, 0 elsewhere;
    by row blocks of SUB rows so that no factor overflows. The factors are
    formed in float32 and multiplied in ``dt``. A block's own columns
    carry ``exp(G_f - G_j)``, which grows with the decay: it is held to
    ``exp(CLAMP)``, so a channel that decays by more than CLAMP within SUB
    rows (|g| > 8.5 a token: it forgets in one) loses its already
    negligible terms off the diagonal and nothing is ever infinite; the
    diagonal needs no decay and is exact."""
    c = k.shape[-2]
    out = []
    for r0 in range(0, c, SUB):
        r1 = r0 + SUB
        ref = G[..., r0:r0 + 1, :]
        shrink = jnp.exp(G[..., r0:r1, :] - ref)
        left = jnp.concatenate([k[..., r0:r1, :] * shrink,
                                q[..., r0:r1, :] * shrink], axis=-2)
        right = kb[..., :r1, :] * jnp.exp(
            jnp.minimum(ref - G[..., :r1, :], CLAMP))
        s = jnp.einsum("...ik,...jk->...ij", left.astype(dt),
                       right.astype(dt), preferred_element_type=jnp.float32)
        out.append(jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, c - r1)]))
    ii = jnp.arange(c)
    a_kk = jnp.concatenate([s[..., :SUB, :] for s in out], axis=-2)
    a_qk = jnp.concatenate([s[..., SUB:, :] for s in out], axis=-2)
    own = beta * jnp.sum(q * k, axis=-1)            # beta_i <q_i, k_i>
    a_qk = jnp.where(ii[:, None] == ii[None, :], own[..., None], a_qk)
    return (jnp.where(ii[:, None] > ii[None, :], a_kk, 0.0),
            jnp.where(ii[:, None] >= ii[None, :], a_qk, 0.0))


def _neumann(x, order: int):
    """(I + x)^-1 = (I - x)(I + x^2)(I + x^4)... for ``x^order = 0``."""
    mm = lambda a, b: jnp.matmul(  # noqa: E731
        a, b, precision=jax.lax.Precision.HIGHEST)
    inv = jnp.eye(x.shape[-1], dtype=x.dtype) - x
    while order > 2:
        x = mm(x, x)
        inv = inv + mm(inv, x)
        order //= 2
    return inv


def _inverse_unit_lower(a):
    """(I + a)^-1 for strictly lower triangular ``a`` [..., C, C], float32.
    Exact, in two finite Neumann products of [C, C] matmuls: with ``d`` the
    SUB x SUB blocks on the diagonal and ``low`` the rest,
    ``I + a = (I + d)(I + (I + d)^-1 low)``; ``d^SUB = 0`` and the second
    factor's strictly block-lower part is nilpotent of order C / SUB."""
    c = a.shape[-1]
    blk = jnp.arange(c) // SUB
    d = jnp.where(blk[:, None] == blk[None, :], a, 0.0)
    t = _neumann(d, SUB)
    hi = jax.lax.Precision.HIGHEST
    m = jnp.matmul(t, a - d, precision=hi)
    return jnp.matmul(_neumann(m, c // SUB), t, precision=hi)


def prepare(q, k, v, g, beta, *, chunk: int):
    """u_v, w, q_in, a_qk, k_out [B, H, N, C, .] and shrink [B, H, N, dk],
    what ``ops.pallas.kda.kda_recurrence`` takes, from q, k, v, g [B, S,
    H, .] and beta [B, S, H]."""
    f32 = jnp.float32
    b, s, h, dk = q.shape
    if s % chunk or chunk % SUB:
        raise ValueError(
            f"chunk_kda: sequence {s} must be a multiple of the chunk "
            f"{chunk}, and the chunk of {SUB}")
    n = s // chunk
    dt = q.dtype

    def by_chunk(x):        # [B, S, H, ...] -> [B, H, N, C, ...]
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g = (by_chunk(x.astype(f32)) for x in (q, k, v, g))
    beta = by_chunk(beta.astype(f32))               # [B, H, N, C]
    G = jnp.cumsum(g, axis=-2)
    kb = k * beta[..., None]
    # A_ij = beta_j <k_i e^{G_i - G_j}, k_j> (j < i); the same with q
    # and the diagonal for the outputs
    a_kk, a_qk = _scores(q, k, kb, beta, G, dt)
    t = _inverse_unit_lower(a_kk)
    # a log-decay is never positive: the clamp only says so
    decay = jnp.exp(jnp.minimum(G, 0.0))
    tail = G[..., -1:, :]                           # G_C
    mm = lambda x, y: jnp.matmul(  # noqa: E731
        x.astype(dt), y.astype(dt), preferred_element_type=f32)
    u_v = mm(t, v)                                  # T V
    w = mm(t, k * decay)                            # T (K e^G)
    q_in = (q * decay).astype(dt)
    k_out = (kb * jnp.exp(tail - G)).astype(dt)
    shrink = jnp.exp(jnp.minimum(tail[..., 0, :], 0.0))
    return u_v, w.astype(dt), q_in, a_qk.astype(dt), k_out, shrink
