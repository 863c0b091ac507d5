"""LFM2's gated short convolution in ``jax.numpy`` under autodiff: the
reference of the kernels ``ds_gated_conv_fwd`` / ``ds_gated_conv_bwd``
(``ops/pallas/short_conv.py``), whose gradients are this one's autodiff,
and the form ``models/lfm2_moe.py`` ran before it had them. Float32 inside
and rounded once, as the kernels. ``tests/test_gated_short_conv.py``
compares them."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gated_short_conv(bcx, w):
    """``ops.layers.gated_short_conv``'s arguments and result: bcx
    [B, S, 3 C] = ``[B | Cg | X]``, taps w [n, C]; ``Cg * conv(B * X)``,
    causal and depthwise, zeros before the start."""
    n, c = w.shape
    s = bcx.shape[1]
    with jax.named_scope("ds.gconv_mix"):
        gate_b, gate_c, x = (bcx[..., r * c:(r + 1) * c].astype(jnp.float32)
                             for r in range(3))
        u = jnp.pad(gate_b * x, ((0, 0), (n - 1, 0), (0, 0)))
        conv = sum(u[:, i:i + s] * w[i].astype(jnp.float32)
                   for i in range(n))
        return (gate_c * conv).astype(bcx.dtype)
