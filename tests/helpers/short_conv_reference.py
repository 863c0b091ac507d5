"""The recurrent mixers' short convolution in ``jax.numpy`` under autodiff,
as ``ops/layers.py`` ``causal_conv`` and the mixers' own lines held it
until PR 43: the reference of the kernels ``ds_short_conv_fwd`` /
``ds_short_conv_bwd`` (``ops/pallas/short_conv.py``), whose gradients are
this one's autodiff. The taps' products, their sum and the SiLU are in
``x``'s dtype here (bf16 in a train step), the l2 norm in float32.
``tests/test_short_conv.py`` compares them; ``tools/short_conv_bench.py``
times them side by side on the chip."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(x, w, bias=None):
    """Causal depthwise convolution along the sequence: x [B, S, C],
    w [n, C], bias [C] or None; y_t = sum_i w[i] x_{t-(n-1)+i} (+ bias),
    zeros before the start."""
    n, s = w.shape[0], x.shape[1]
    with jax.named_scope("ds.conv"):
        xp = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
        y = sum(xp[:, i:i + s] * w[i] for i in range(n))
        return y if bias is None else y + bias


def short_conv(x, w, bias=None, *, norm_width=None, norm_scale=1.0):
    """``ops.layers.short_conv``'s arguments and result: the convolution,
    SiLU and, where ``norm_width`` is given, the float32 l2 norm of each
    run of ``norm_width`` channels times ``norm_scale``."""
    y = causal_conv(x, w, bias)
    with jax.named_scope("ds.mix_pre"):
        y = jax.nn.silu(y)
        if norm_width is None:
            return y
        heads = y.reshape(*y.shape[:2], -1, norm_width).astype(jnp.float32)
        heads = heads * jax.lax.rsqrt(
            jnp.sum(jnp.square(heads), axis=-1, keepdims=True) + 1e-6)
        return (heads * norm_scale).astype(x.dtype).reshape(y.shape)
