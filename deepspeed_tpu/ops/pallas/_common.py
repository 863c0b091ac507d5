"""What the kernel modules of this package share: the interpret-mode gate,
the float32 product, the bf16 pieces of a float32 value, a byte count for
``cost_estimate``, the registry of the trace-time gauges, the once-a-shape
trace of a ``pallas_call`` and the name under which a kernel's forward rule declares the residuals a rematted
region keeps."""

from __future__ import annotations

import jax
import jax.extend
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# What a kernel pair's forward rule passes through `_keep` stays alive
# across a `jax.checkpoint` whose policy comes from
# models/transformer.py `_remat_policy`: every policy it returns saves this
# name. For residuals of O(S) bytes whose rerun is O(S^2) work.
KEPT_RESIDUAL = "kernel_kept"


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


def _registry():
    """The metrics registry where telemetry is on, else None: what a
    kernel module's trace-time gauges (a function of shapes, said where the
    kernel is built; host only) are set on."""
    from ...utils.telemetry_probe import active_telemetry
    tel = active_telemetry()
    return tel.get_registry() if tel is not None else None


def _keep(kernel: str, *arrays):
    """``arrays`` named `KEPT_RESIDUAL`: a forward rule returns them both
    as its output and inside its residuals, and a rematted region's
    backward then reads them back where it would have rerun the kernel.
    Outside a ``jax.checkpoint`` the name does nothing. Trace time, host
    only: gauge ``ds_kernel_kept_bytes`` says what ONE call declares."""
    reg = _registry()
    if reg is not None:
        reg.gauge("ds_kernel_kept_bytes",
                  "bytes of the residuals one call of the kernel last "
                  "traced declares kept across a rematted region"
                  ).set(_nbytes(*arrays), kernel=kernel)
    return tuple(checkpoint_name(x, KEPT_RESIDUAL) for x in arrays)


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
