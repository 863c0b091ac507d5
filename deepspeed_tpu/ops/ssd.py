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
``S' = exp(a_Q) S + ...`` is serial in the chunks. Every exponent is a
difference ``a_i - a_j`` with ``i >= j`` (or ``a_r`` itself): never
positive, taken BEFORE the exponential, so nothing
overflows however fast a head decays (``dt A`` of -1.6 a token is -410 over
a chunk of 256; a product ``exp(a_i) exp(-a_j)`` would be inf x 0). The
decays, their cumulative sums and the carried state are float32; the
matmuls run in ``x``'s dtype with float32 accumulation. ``D x`` and the
output gate are the caller's.

It runs as a Pallas kernel pair under one ``jax.custom_vjp``
(``ops/pallas/ssd.py`` ``ssd_scan``, PR 37). The forward reads x, dt, B, C
once in the model's own layout, makes a chunk's running sum ``a`` for every
head, ``C B^T`` once a group and each head's masked decay blocks in VMEM,
carries the float32 state in VMEM across the chunks and writes ``y``
alone. The backward takes the chunks last to first from the state each
chunk started from (the forward kernel's second form: float32, 67 MB a
layer at the cell's shape, alive only inside the backward), carries ``dS``
in VMEM and writes dx, ddt, dB, dC and dA's share. Nothing of size [Q, Q]
reaches HBM and the residuals are the five inputs. A rematted layer runs
``ds_ssd_fwd`` twice (its ``y`` feeds the gate and the gated norm, whose
backward needs it again), the second form once and ``ds_ssd_bwd`` once
(``PERF.md`` section 5 has their times). Until PR 37 all of it was
``jax.numpy`` under autodiff, the decay matrix [B, H, S/Q, Q, Q] through
HBM in float32 (537 MB a layer at 64 heads x 8192 tokens):
``tests/helpers/ssd_reference.py`` keeps that form as the kernels'
reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas.ssd import ssd_scan

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
    s, h = x.shape[1:3]
    g = B.shape[2]
    if s % chunk:
        raise ValueError(f"chunk_ssd: sequence {s} must be a multiple of "
                         f"the chunk {chunk}")
    if h % g:
        raise ValueError(f"chunk_ssd: {h} heads in {g} groups of B and C")
    with jax.named_scope("ds.ssd"):
        return ssd_scan(x, dt, A, B, C, chunk=chunk)


def sharded_chunk_ssd(act_sharding):
    """``chunk_ssd`` for a multi-device mesh: per shard of the batch under
    a shard_map, because GSPMD cannot partition the kernels' Mosaic calls
    (``parallel.mesh.per_batch_shard``, which see: heads and sequences are
    independent, so the per-shard result is exact). ``A`` [H] is
    replicated."""
    from ..parallel.mesh import per_batch_shard
    return per_batch_shard(chunk_ssd, act_sharding,
                           (True, True, False, True, True))
