"""Kimi Delta Attention (KDA): a gated delta rule with a decay per key
channel, in its chunked form (Kimi-Linear, ``linear_attn_config``).

Per head, with a float32 state ``S`` [dk, dv], ``S_0 = 0``::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log-decay of each KEY CHANNEL, ``beta_t`` in (0, 1).
``recurrent_kda`` is that recurrence token by token (tests only).
``chunk_kda`` is what the models run: chunks of ``CHUNK`` tokens, inside a
chunk with incoming state ``S`` and ``G_r = sum_{i<=r} g_i``::

    A_ij = beta_j <k_i * exp(G_i - G_j), k_j>   (j < i, else 0)
    U    = (I + A)^-1 (V - (K * exp(G)) S)
    o_r  = S^T (q_r * exp(G_r)) + sum_{j<=r} beta_j <q_r * exp(G_r - G_j), k_j> u_j
    S'   = Diag(exp(G_C)) S + sum_j (k_j * exp(G_C - G_j)) beta_j u_j^T

Everything that does not need ``S`` (both score matrices, the inverse
``T = (I + A)^-1``, ``T V``, ``T (K * exp(G))`` and the decay products) is
computed for all chunks at once, as batched matmuls in ``jax.numpy``
under autodiff: the preparation. What needs ``S`` is serial in the
chunks and runs in a Pallas kernel pair under one ``custom_vjp``
(``ops/pallas/kda.py`` ``kda_recurrence``): the forward carries ``S`` in
VMEM across the chunks and writes ``o`` alone; the backward rebuilds the
states by segments of ``SEG`` chunks from float32 segment checkpoints
and carries ``dS`` in VMEM, so no state history and no per-chunk
residual of the recurrence reaches HBM. (Until PR 32 this was a
``lax.scan`` with an autodiff backward that stacked a state a chunk.)
On the chip the preparation is five sixths of the mixer's time and the
kernels one twentieth (``PERF.md`` section 5).

``exp(G_i - G_j) <= 1``, but ``exp(G_i) * exp(-G_j)`` overflows float32
where a channel decays fast (a log-decay of -1.6 a token is -100 over a
chunk). So the score matrices are built by row blocks of ``SUB`` rows,
each factored about the block's own first row ``G_f``: rows carry
``exp(G_i - G_f) <= 1``, earlier columns ``exp(G_f - G_j) <= 1``, and
the block's own columns at most ``exp((SUB - 1) |g|)``, held to
``exp(CLAMP)`` (``_scores``; the published kernel factors by sub-blocks
too). On the chip a training run drifted past the overflow of 16-row
blocks (|g| > 5.5 a token) within 30 steps and its loss went NaN (PR 31):
hence 8 rows, the clamp and the exact diagonal. The inverse of the unit lower triangle is exact block
elimination (``_inverse_unit_lower``): the ``SUB x SUB`` diagonal blocks
by the finite Neumann product ``(I - A)(I + A^2)(I + A^4)...``
(``A^SUB = 0``), the blocks below them by a second one of order
``CHUNK / SUB``, all as [CHUNK, CHUNK] matmuls.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .pallas.kda import kda_recurrence

CHUNK = 64      # tokens a chunk: the matmuls are [64, 128] x [128, 128]
SUB = 8         # rows a sub-block of the score matrices
CLAMP = 60.0    # largest exponent a sub-block's own columns may carry


def recurrent_kda(q, k, v, g, beta):
    """The recurrence, token by token. q, k [B, S, H, dk]; v [B, S, H, dv];
    g [B, S, H, dk] (log-decay); beta [B, S, H]. Returns o [B, S, H, dv]
    float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        u = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t * b_t[..., None], u)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(x.swapaxes(0, 1) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    return o.swapaxes(0, 1)


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


def chunk_kda(q, k, v, g, beta, *, chunk: int = CHUNK, head_groups: int = 1):
    """The chunked form; arguments as ``recurrent_kda``. The matmuls run in
    ``q``'s dtype with float32 accumulation, the decays, the score
    matrices' inverse and the carried state in float32. Returns o
    [B, S, H, dv] in ``v``'s dtype. ``S`` must be a multiple of ``chunk``.

    The heads (they are independent) run in ``head_groups`` groups, one
    after the other under ``lax.map``, each under its own
    ``jax.checkpoint``: the chunk-wise operands and the residuals of the
    preparation's backward (a dozen arrays of ``[B, S, H, 128]`` float32)
    live for one group at a time. A group's backward runs its
    preparation again, then the forward kernel's checkpoint form and the
    backward kernel; the forward kernel itself is not run again (its
    ``o`` is dead in the rerun)."""
    h = q.shape[2]
    if h % head_groups:
        raise ValueError(f"chunk_kda: {h} heads in {head_groups} groups")

    def split(x):       # [B, S, H, ...] -> [G, B, S, H/G, ...]
        x = x.reshape(*x.shape[:2], head_groups, h // head_groups,
                      *x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    one = jax.checkpoint(
        lambda xs: _chunk_kda(*xs, chunk=chunk), prevent_cse=False)
    with jax.named_scope("ds.kda_scan"):
        o = jax.lax.map(one, tuple(split(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 2)                       # [B, S, G, H/G, dv]
    return o.reshape(*o.shape[:2], h, o.shape[-1])


def sharded_chunk_kda(act_sharding):
    """``chunk_kda`` for a multi-device mesh: per shard of the batch under
    a shard_map, because GSPMD cannot partition the kernels' Mosaic calls
    (as ``ops.pallas.flash_attention.sharded_flash_attention``, which see).
    ``act_sharding`` is the layout the model's activations are pinned to,
    ``[B(batch axes), S, D]``; the batch is split over its batch axes where
    they divide it, every other axis sees replicated inputs (heads and
    sequences are independent, so the per-shard result is exact)."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import active_mesh
    from ..utils.jax_compat import shard_map

    entry = act_sharding.spec[0] if len(act_sharding.spec) else None
    batch_axes = (entry,) if isinstance(entry, str) else tuple(entry or ())

    def kda(q, k, v, g, beta, **kw):
        use, free = active_mesh(act_sharding.mesh)
        b_ax = tuple(a for a in batch_axes
                     if a in free and use.shape[a] > 1)
        if q.shape[0] % math.prod(use.shape[a] for a in b_ax):
            b_ax = ()       # uneven batch: replicate, still exact
        wide, flat = (P(b_ax or None, *[None] * n) for n in (3, 2))
        return shard_map(
            functools.partial(chunk_kda, **kw), mesh=use,
            axis_names=set(free), in_specs=(wide,) * 4 + (flat,),
            out_specs=wide, check_vma=False)(q, k, v, g, beta)

    return kda


def _chunk_kda(q, k, v, g, beta, *, chunk):
    f32 = jnp.float32
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if s % chunk or chunk % SUB:
        raise ValueError(
            f"chunk_kda: sequence {s} must be a multiple of the chunk "
            f"{chunk}, and the chunk of {SUB}")
    n = s // chunk
    dt, out_dt = q.dtype, v.dtype

    def by_chunk(x):        # [B, S, H, ...] -> [B, H, N, C, ...]
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    with jax.named_scope("ds.kda_scan"):
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
        o = kda_recurrence(u_v, w.astype(dt), q_in, a_qk.astype(dt), k_out,
                           shrink, out_dtype=out_dt)    # [B, H, N, C, dv]
    return jnp.moveaxis(o, 1, 3).reshape(b, s, h, dv)
