"""Fused optimizer kernels (reference: csrc/adam/multi_tensor_adam.cu,
csrc/lion/*, fused_adam_frontend.cpp).

One Pallas kernel applies the whole Adam/Lion update (moments, bias
correction, weight decay, parameter write) per block — the role of the
reference's multi-tensor-apply fused CUDA kernels. XLA usually fuses the
optax update chain already; these kernels guarantee the fusion (single
HBM pass over params/grads/moments) and serve as the `FusedAdam` /
`FusedLion` op parity point.

Tensors are processed as flattened, 128-lane-padded 2D blocks. Exposed as
optax GradientTransformations so the engine can swap them in via
config optimizer.params.fused_kernel = true.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _interpret

BLOCK = 1024  # rows per program, x 128 lanes


def _pad_2d(x):
    n = x.size
    cols = 128
    rows = -(-n // cols)
    pad = rows * cols - n
    flat = jnp.pad(x.reshape(-1), (0, pad))
    return flat.reshape(rows, cols), n


def _unpad(x2d, n, shape, dtype):
    return x2d.reshape(-1)[:n].reshape(shape).astype(dtype)


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, hp_ref, p_out, m_out, v_out,
                 *, wd, adamw_mode):
    lr = hp_ref[0]
    b1 = hp_ref[1]
    b2 = hp_ref[2]
    eps = hp_ref[3]
    c1 = hp_ref[4]   # 1/(1-b1^t)
    c2 = hp_ref[5]   # 1/(1-b2^t)
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    if wd and not adamw_mode:
        g = g + wd * p  # classic L2: decay enters the moments
    m = b1 * m_ref[:] + (1 - b1) * g
    v = b2 * v_ref[:] + (1 - b2) * g * g
    update = (m * c1) / (jnp.sqrt(v * c2) + eps)
    if wd and adamw_mode:
        update = update + wd * p  # AdamW: decoupled decay
    p_out[:] = p - lr * update
    m_out[:] = m
    v_out[:] = v


class FusedAdamState(NamedTuple):
    count: jnp.ndarray
    mu: optax.Updates
    nu: optax.Updates


def fused_adam(learning_rate, b1=0.9, b2=0.999, eps=1e-8,
               weight_decay=0.0,
               adamw_mode=True) -> optax.GradientTransformation:
    """AdamW with the update applied by one Pallas kernel per tensor.

    Returned `updates` are deltas (new_p - p) so it composes like any optax
    transform with apply_updates.
    """

    def init(params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
        return FusedAdamState(jnp.zeros((), jnp.int32),
                              jax.tree.map(z, params),
                              jax.tree.map(z, params))

    def update(grads, state, params):
        if params is None:
            raise ValueError("fused_adam requires params")
        # lr evaluated at the pre-increment count (optax scale_by_schedule
        # convention: first step uses lr(0)); bias correction at t=count+1
        # (optax scale_by_adam convention)
        lr = (learning_rate(state.count) if callable(learning_rate)
              else learning_rate)
        count = state.count + 1
        t = count.astype(jnp.float32)
        hp = jnp.stack([
            jnp.asarray(lr, jnp.float32),
            jnp.asarray(b1, jnp.float32),
            jnp.asarray(b2, jnp.float32),
            jnp.asarray(eps, jnp.float32),
            1.0 / (1.0 - b1 ** t),
            1.0 / (1.0 - b2 ** t),
        ])

        def one(p, g, m, v):
            p2, n = _pad_2d(p)
            g2, _ = _pad_2d(g.astype(jnp.float32))
            m2, _ = _pad_2d(m)
            v2, _ = _pad_2d(v)
            rows = p2.shape[0]
            blk = min(BLOCK, rows)
            grid = (-(-rows // blk),)
            spec = pl.BlockSpec((blk, 128), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)
            new_p, new_m, new_v = pl.pallas_call(
                functools.partial(_adam_kernel, wd=weight_decay,
                                  adamw_mode=adamw_mode),
                grid=grid,
                in_specs=[spec, spec, spec, spec,
                          pl.BlockSpec(memory_space=pltpu.SMEM)],
                out_specs=[spec, spec, spec],
                out_shape=[jax.ShapeDtypeStruct(p2.shape, jnp.float32)] * 3,
                interpret=_interpret(),
                name="ds_fused_adam",
            )(p2.astype(jnp.float32), g2, m2, v2, hp)
            delta = _unpad(new_p - p2.astype(jnp.float32), n, p.shape, p.dtype)
            return delta, _unpad(new_m, n, p.shape, jnp.float32), \
                _unpad(new_v, n, p.shape, jnp.float32)

        out = jax.tree.map(one, params, grads, state.mu, state.nu)
        # out is a tree of (delta, m, v) tuples; split
        deltas = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda t: isinstance(t, tuple))
        mus = jax.tree.map(lambda t: t[1], out,
                           is_leaf=lambda t: isinstance(t, tuple))
        nus = jax.tree.map(lambda t: t[2], out,
                           is_leaf=lambda t: isinstance(t, tuple))
        return deltas, FusedAdamState(count, mus, nus)

    return optax.GradientTransformation(init, update)


def _lion_kernel(p_ref, g_ref, m_ref, hp_ref, p_out, m_out, *, wd):
    lr = hp_ref[0]
    b1 = hp_ref[1]
    b2 = hp_ref[2]
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:]
    update = jnp.sign(b1 * m + (1 - b1) * g)
    if wd:
        update = update + wd * p
    p_out[:] = p - lr * update
    m_out[:] = b2 * m + (1 - b2) * g


class FusedLionState(NamedTuple):
    count: jnp.ndarray
    mu: optax.Updates


def fused_lion(learning_rate, b1=0.9, b2=0.99,
               weight_decay=0.0) -> optax.GradientTransformation:
    """Lion (reference: csrc/lion) as a single-pass Pallas kernel."""

    def init(params):
        return FusedLionState(
            jnp.zeros((), jnp.int32),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))

    def update(grads, state, params):
        lr = (learning_rate(state.count) if callable(learning_rate)
              else learning_rate)
        count = state.count + 1
        hp = jnp.stack([jnp.asarray(lr, jnp.float32),
                        jnp.asarray(b1, jnp.float32),
                        jnp.asarray(b2, jnp.float32)])

        def one(p, g, m):
            p2, n = _pad_2d(p)
            g2, _ = _pad_2d(g.astype(jnp.float32))
            m2, _ = _pad_2d(m)
            rows = p2.shape[0]
            blk = min(BLOCK, rows)
            spec = pl.BlockSpec((blk, 128), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)
            new_p, new_m = pl.pallas_call(
                functools.partial(_lion_kernel, wd=weight_decay),
                grid=(-(-rows // blk),),
                in_specs=[spec, spec, spec,
                          pl.BlockSpec(memory_space=pltpu.SMEM)],
                out_specs=[spec, spec],
                out_shape=[jax.ShapeDtypeStruct(p2.shape, jnp.float32)] * 2,
                interpret=_interpret(),
                name="ds_fused_lion",
            )(p2.astype(jnp.float32), g2, m2, hp)
            delta = _unpad(new_p - p2.astype(jnp.float32), n, p.shape, p.dtype)
            return delta, _unpad(new_m, n, p.shape, jnp.float32)

        out = jax.tree.map(one, params, grads, state.mu)
        deltas = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda t: isinstance(t, tuple))
        mus = jax.tree.map(lambda t: t[1], out,
                           is_leaf=lambda t: isinstance(t, tuple))
        return deltas, FusedLionState(count, mus)

    return optax.GradientTransformation(init, update)
