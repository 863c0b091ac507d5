"""The chunked Mamba-2 scan in ``jax.numpy`` under autodiff, as
``ops/ssd.py`` held it until PR 37: the reference of the kernels
``ds_ssd_fwd`` / ``ds_ssd_bwd`` (``ops/pallas/ssd.py``), whose gradients
are this one's autodiff. The masked decay matrix [B, H, S/Q, Q, Q] goes
through HBM in float32 here (537 MB a layer at 64 heads x 8192 tokens).
``tests/test_ssd_kernels.py`` compares them; ``tools/ssd_kernel_bench.py``
times them side by side on the chip."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def chunk_ssd(x, dt, A, B, C, *, chunk: int):
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
