"""State-space duality (SSD): the Mamba-2 scan in its chunked form.

Per head ``h`` (width ``P``) with a float32 state ``S`` [P, N], ``S_0 = 0``,
a step ``dt_t > 0`` and a decay rate ``A_h < 0``::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

``B_t``, ``C_t`` in R^N are shared by the heads of a group (``G`` groups of
``H / G`` heads). ``recurrent_ssd`` is that recurrence token by token
(tests only). ``chunk_ssd`` is what the models run: chunks of ``Q`` tokens,
inside a chunk with incoming state ``S`` and ``a_r = sum_{i<=r} dt_i A``::

    y_r  = sum_{j<=r} <C_r, B_j> exp(a_r - a_j) dt_j x_j  +  exp(a_r) S C_r
    S'   = exp(a_Q) S + sum_j exp(a_Q - a_j) dt_j x_j B_j^T

The first sum is a masked [Q, Q] matrix a head and chunk times the chunk's
inputs, the sum in ``S'`` one [P, Q] x [Q, N] product a chunk; only
``S' = exp(a_Q) S + ...`` is serial, and runs as one ``lax.scan`` over the
chunks. Every exponent is a difference ``a_i - a_j`` with ``i >= j`` (or
``a_r`` itself): never positive, taken BEFORE the exponential, so nothing
overflows however fast a head decays (``dt A`` of -1.6 a token is -410 over
a chunk of 256; a product ``exp(a_i) exp(-a_j)`` would be inf x 0). The
decays, their cumulative sums and the carried state are float32; the
matmuls run in ``x``'s dtype with float32 accumulation. The backward is
autodiff's. ``D x`` and the output gate are the caller's.

All of it is ``jax.numpy``: the masked decay matrix [B, H, S/Q, Q, Q] goes
through HBM in float32 (537 MB a layer at 64 heads x 8192 tokens), which
is what a kernel would keep in VMEM (``PERF.md`` section 7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 256     # tokens a chunk (``mamba_chunk_size`` as published)


def recurrent_ssd(x, dt, A, B, C):
    """The recurrence, token by token. x [B, S, H, P]; dt [B, S, H];
    A [H]; B, C [B, S, G, N]. Returns y [B, S, H, P] float32."""
    f32 = jnp.float32
    x, dt, A, B, C = (v.astype(f32) for v in (x, dt, A, B, C))
    b, _, h, p = x.shape
    g, n = B.shape[2:]
    x = x.reshape(*x.shape[:2], g, h // g, p)
    dt = dt.reshape(*dt.shape[:2], g, h // g)

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * A.reshape(g, h // g))[..., None, None]
        state = state + jnp.einsum("bghp,bgn->bghpn", x_t * dt_t[..., None],
                                   b_t)
        return state, jnp.einsum("bghpn,bgn->bghp", state, c_t)

    xs = tuple(v.swapaxes(0, 1) for v in (x, dt, B, C))
    _, y = jax.lax.scan(step, jnp.zeros((b, g, h // g, p, n), f32), xs)
    return y.swapaxes(0, 1).reshape(b, -1, h, p)


def chunk_ssd(x, dt, A, B, C, *, chunk: int = CHUNK):
    """The chunked form; arguments as ``recurrent_ssd``. Returns y
    [B, S, H, P] in ``x``'s dtype. ``S`` must be a multiple of ``chunk``.
    All heads at once and no checkpoint of its own: the caller's remat of
    the layer is the only rerun."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    if s % chunk:
        raise ValueError(f"chunk_ssd: sequence {s} must be a multiple of "
                         f"the chunk {chunk}")
    if h % g:
        raise ValueError(f"chunk_ssd: {h} heads in {g} groups of B and C")
    c, r = s // chunk, h // g
    mm = x.dtype
    with jax.named_scope("ds.ssd"):
        dt = dt.astype(f32).reshape(b, c, chunk, g, r)
        a = jnp.cumsum(dt * A.astype(f32).reshape(g, r), axis=2)
        xd = (x.astype(f32).reshape(b, c, chunk, g, r, p)
              * dt[..., None]).astype(mm)           # dt_j x_j
        B = B.reshape(b, c, chunk, g, n).astype(mm)
        C = C.reshape(b, c, chunk, g, n).astype(mm)
        a = jnp.moveaxis(a, 2, -1)                  # [b, c, g, r, Q]
        # within a chunk: <C_i, B_j> exp(a_i - a_j) for j <= i
        ii = jnp.arange(chunk)
        diff = a[..., :, None] - a[..., None, :]
        decay = jnp.exp(jnp.where(ii[:, None] >= ii[None, :], diff,
                                  -jnp.inf))        # [b, c, g, r, Q, Q]
        cb = jnp.einsum("bcign,bcjgn->bcgij", C, B,
                        preferred_element_type=f32)
        m = (cb[:, :, :, None] * decay).astype(mm)
        y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xd,
                       preferred_element_type=f32)
        # each chunk's own contribution to the state at its end
        last = a[..., -1]                           # a_Q  [b, c, g, r]
        to_end = jnp.exp(last[..., None] - a)       # exp(a_Q - a_j) <= 1
        # from the rounded xd, not its float32 form: kept live for this, the
        # float32 array cost 1.7 ms a step on the chip (PR 34)
        xe = (xd.astype(f32)
              * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(mm)
        own = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xe, B,
                         preferred_element_type=f32)

        # the recurrence over the chunks: the state each chunk starts from
        # (a running sum of log-decays is never positive: the clamps only
        # say so)
        def step(state, xs):
            own_c, shrink = xs
            return state * shrink[..., None, None] + own_c, state

        _, start = jax.lax.scan(
            step, jnp.zeros((b, g, r, p, n), f32),
            (jnp.moveaxis(own, 1, 0),
             jnp.moveaxis(jnp.exp(jnp.minimum(last, 0.0)), 1, 0)))
        start = jnp.moveaxis(start, 0, 1)           # [b, c, g, r, p, n]
        carried = jnp.einsum("bcign,bcgrpn->bcigrp", C, start.astype(mm),
                             preferred_element_type=f32)
        y = y + carried * jnp.moveaxis(
            jnp.exp(jnp.minimum(a, 0.0)), -1, 2)[..., None]
    return y.reshape(b, s, h, p).astype(x.dtype)
