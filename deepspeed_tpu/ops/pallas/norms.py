"""Fused normalization kernels (reference: csrc/transformer/normalize_kernels.cu,
csrc/transformer/inference/csrc/rms_norm.cu).

Forward is a single-pass Pallas kernel (one HBM read, fp32 stats);
backward is the jnp reference implementation via custom_vjp — XLA fuses
the backward chain well, so a hand-written backward kernel buys nothing on
TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..layers import layer_norm as _ln_ref
from ..layers import rms_norm as _rms_ref
from ._common import _interpret


def _rms_kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps) * s_ref[:]).astype(o_ref.dtype)


def _ln_kernel(x_ref, s_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    o_ref[:] = ((x - mean) * jax.lax.rsqrt(var + eps) * s_ref[:]
                + b_ref[:]).astype(o_ref.dtype)


def _rows(x):
    d = x.shape[-1]
    return x.reshape(-1, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, scale, eps: float = 1e-6):
    if _interpret() or x.shape[-1] % 128 != 0:
        return _rms_ref(x, scale, eps)
    rows = _rows(x)
    n, d = rows.shape
    blk = min(256, n)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(-(-n // blk),),
        in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((d,), lambda i: (0,),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(rows.shape, x.dtype),
        name="ds_rms_norm",
    )(rows, scale)
    return out.reshape(x.shape)


def _rms_fwd(x, scale, eps):
    return rms_norm(x, scale, eps), (x, scale)


def _rms_bwd(eps, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda x, s: _rms_ref(x, s, eps), x, scale)
    return vjp(g)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x, scale, bias, eps: float = 1e-5):
    if _interpret() or x.shape[-1] % 128 != 0:
        return _ln_ref(x, scale, bias, eps)
    rows = _rows(x)
    n, d = rows.shape
    blk = min(256, n)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(-(-n // blk),),
        in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((d,), lambda i: (0,),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((d,), lambda i: (0,),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(rows.shape, x.dtype),
        name="ds_layer_norm",
    )(rows, scale, bias)
    return out.reshape(x.shape)


def _ln_fwd(x, scale, bias, eps):
    return layer_norm(x, scale, bias, eps), (x, scale, bias)


def _ln_bwd(eps, res, g):
    x, scale, bias = res
    _, vjp = jax.vjp(lambda x, s, b: _ln_ref(x, s, b, eps), x, scale, bias)
    return vjp(g)


layer_norm.defvjp(_ln_fwd, _ln_bwd)
