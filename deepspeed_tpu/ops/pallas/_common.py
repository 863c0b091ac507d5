"""What the kernel modules of this package share: the interpret-mode gate,
the float32 product, the bf16 pieces of a float32 value, a byte count for
``cost_estimate`` and the once-a-shape trace of a ``pallas_call``."""

from __future__ import annotations

import jax
import jax.extend
import jax.numpy as jnp


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _pieces(v, dt):
    """``v`` (float32) as three pieces in ``dt`` whose sum is ``v``."""
    f32 = jnp.float32
    hi = v.astype(dt)
    rest = v - hi.astype(f32)
    mid = rest.astype(dt)
    return hi, mid, (rest - mid.astype(f32)).astype(dt)


def _nbytes(*arrays):
    return sum(x.size * jnp.dtype(x.dtype).itemsize for x in arrays)


_TRACED: dict = {}


def _bind(call, scope, key, *args):
    """``call(*args)`` under ``scope``, the ``pallas_call`` traced ONCE a
    ``key`` (with the operands' types and the mesh they are typed on) and
    bound from that jaxpr ever after. A step holds each kernel many times
    (a layer unrolled, a scan's body, remat's rerun, the agreement check's
    forward); every ``pallas_call`` traces its kernel anew, and an equation
    with a new jaxpr is lowered anew: 12 s of a warm ``setup_s`` (PR 37).
    Equal equations share one lowering, and each keeps its own place's
    scope."""
    key = (key, _interpret(), tuple(jax.typeof(x) for x in args))
    if key not in _TRACED:
        _TRACED[key] = jax.make_jaxpr(call)(*args)
    with jax.named_scope(scope):
        return jax.extend.core.jaxpr_as_fun(_TRACED[key])(*args)
