"""The gated per-head RMSNorm of Kimi-Linear's KDA layers and Qwen3-Next's
Gated DeltaNet layers in ``jax.numpy`` under autodiff: the reference of the
kernels ``ds_gated_norm_fwd`` / ``ds_gated_norm_bwd``
(``ops/pallas/gated_norm.py``), whose gradients are this one's autodiff,
and the two expressions ``models/kimi_linear.py`` ``_kda`` and
``models/qwen3_next.py`` ``_gdn`` ran under ``ds.mix_post`` before they had
them (PR 55), line for line. ``tests/test_gated_norm.py`` compares them."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import layers as L


def kimi_gated_norm(o, gate, w, b_g, eps):
    """``_kda``'s form: the gate's sigmoid with a bias, ``o_norm`` rounded
    to ``o``'s dtype, the product float32. o [B, S, H, d]; gate
    [B, S, H d]; returns [B, S, H d] in the gate's dtype."""
    f32 = jnp.float32
    b, s, nh, dk = o.shape
    with jax.named_scope("ds.mix_post"):
        g = jax.nn.sigmoid(gate.astype(f32) + b_g.astype(f32))
        o = L.rms_norm(o, w, eps).reshape(b, s, nh * dk)
        return (o.astype(f32) * g).astype(gate.dtype)


def qwen_gated_norm(o, z, w, eps):
    """``_gdn``'s form: float32 from ``o`` to the last cast, SiLU, no
    bias. o [B, S, H, d]; z [B, S, H d]."""
    f32 = jnp.float32
    b, s, nh, dv = o.shape
    with jax.named_scope("ds.mix_post"):
        o = L.rms_norm(o.astype(f32), w.astype(f32), eps).reshape(
            b, s, nh * dv)
        return (o * jax.nn.silu(z.astype(f32))).astype(z.dtype)
