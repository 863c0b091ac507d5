"""The short convolution's kernels alone, on the chip: time and results of
this checkout's ``ops/pallas/short_conv.py`` against the ``jax.numpy`` form
they replaced (``tests/helpers/short_conv_reference.py``).

    chiprun -- python tools/short_conv_bench.py [ROWS_A_CHUNK ...]

Sizes a change to the kernels before the cells are run (PR 43). The shapes
are the two cells': ``train-kda-s16k-1chip`` (one sequence of 16384, 32
heads of 128: q and k with the head's l2 norm, v without) and
``train-ssm-s8k-1chip`` (8192 x 4352 with a bias, read as the model reads
it: a slice of the fused projection's [1, 8192, 8512] at column 4096, so
XLA's copy of the slice is in the call). One line a ``ROWS_A_CHUNK`` (the
module's ``_CHUNK``: rows a pass in registers; the module's own if none is
given), a stream each:

- ``fwd_ms``, ``bwd_ms``: ``ds_short_conv_fwd`` / ``ds_short_conv_bwd``,
  the mean duration of the ``tpu_custom_call`` events of a profiler trace
  of 10 calls, ``*_gbs`` the GB/s of the operands' one trip (x and y; x,
  dy and dx), ``*_busy`` the device busy time of the call each sits in;
- ``layer_ms``: ``jax.grad`` of the rematted op under a linear loss (so
  the forward, remat's rerun and the backward of a train step) with the
  kernels (device busy time a call, each kernel's part and the rest) and
  ``jnp_ms`` with the reference in their place; ``jnp_fwd_ms`` its forward;
- ``err``: ``y`` and the gradients against the reference's IN FLOAT32 on
  the same bf16 inputs, and ``jnp_err`` the bf16 reference's own: largest
  difference over the largest value.

A device number, so only on a TPU. Not the yardstick: what a user feels
is ``benchmark/run.py``.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "tests"))   # helpers/
sys.path.insert(2, os.path.join(ROOT, "tools"))
# the trace of CALLS calls and its reductions: one definition for the tools
from kda_kernel_bench import busy_ms, rel_err, traced  # noqa: E402

KERNELS = ("ds_short_conv_fwd", "ds_short_conv_bwd")
# stream: (sequence, channels, the projection's width, the slice's start,
# bias, norm_width, norm_scale)
STREAMS = {
    "kda_qk": (16384, 4096, 4096, 0, False, 128, 128 ** -0.5),
    "kda_v": (16384, 4096, 4096, 0, False, None, 1.0),
    "mamba_xbc": (8192, 4352, 8512, 4096, True, None, 1.0),
}


def inputs(stream: str, seed: int = 43):
    """The projection, the taps, the bias (or None) and a cotangent, bf16:
    taps and bias uniform in [-0.5, 0.5] as the models draw them."""
    import jax.numpy as jnp
    s, c, wide, _, bias, _, _ = STREAMS[stream]
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    return (jnp.asarray(rng.normal(size=(1, s, wide)), bf),
            jnp.asarray(rng.uniform(-0.5, 0.5, size=(4, c)), bf),
            jnp.asarray(rng.uniform(-0.5, 0.5, size=(c,)), bf) if bias
            else None,
            jnp.asarray(rng.normal(size=(1, s, c)), bf))


def kernel_ms(events, kernel: str) -> float:
    return busy_ms(events, rf"^%?{kernel}[.\d]* = ")


def by_kernel(events) -> dict:
    """Device busy time a call, each kernel's part and the rest."""
    out = {"busy": busy_ms(events)}
    for k in KERNELS:
        out[k] = kernel_ms(events, k)
    out["rest"] = 2 * out["busy"] - sum(out.values())
    return out


def bench(stream: str) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import layers as L
    from helpers import short_conv_reference
    s, c, _, start, _, width, scale = STREAMS[stream]
    proj, w, bias, dy = inputs(stream)
    f32 = jnp.float32
    kw = dict(norm_width=width, norm_scale=scale)
    part = lambda p: p[..., start:start + c]  # noqa: E731

    def forward(fn):
        return jax.jit(lambda p, w, b: fn(part(p), w, b, **kw))

    def grads(fn):
        layer = jax.checkpoint(lambda p, w, b: fn(part(p), w, b, **kw))
        loss = lambda p, w, b, dy: jnp.sum(  # noqa: E731
            layer(p, w, b).astype(f32) * dy.astype(f32))
        grad = jax.jit(jax.grad(loss, argnums=(0, 1) if bias is None
                                else (0, 1, 2)))
        return lambda *a: grad(*a, dy)      # an argument, not a constant

    args = (proj, w, bias)
    nbytes = proj[..., :c].size * 2
    fwd_ev = traced(jax, forward(L.short_conv), args)
    layer_ev = traced(jax, grads(L.short_conv), args)
    fwd_ms = kernel_ms(fwd_ev, KERNELS[0])
    bwd_ms = kernel_ms(layer_ev, KERNELS[1])
    line = {
        "fwd_ms": fwd_ms, "fwd_gbs": 2 * nbytes / fwd_ms * 1e-6,
        "fwd_busy": busy_ms(fwd_ev),
        "bwd_ms": bwd_ms, "bwd_gbs": 3 * nbytes / bwd_ms * 1e-6,
        "layer_ms": by_kernel(layer_ev),
        "jnp_ms": busy_ms(traced(
            jax, grads(short_conv_reference.short_conv), args)),
        "jnp_fwd_ms": busy_ms(traced(
            jax, forward(short_conv_reference.short_conv), args)),
    }
    del fwd_ev, layer_ev
    exact = tuple(None if v is None else v.astype(f32) for v in args)
    want = (forward(short_conv_reference.short_conv)(*exact),
            *grads(short_conv_reference.short_conv)(*exact))
    names = ("y", "dx", "dw", "dbias")
    for tag, fn in (("err", L.short_conv),
                    ("jnp_err", short_conv_reference.short_conv)):
        got = (forward(fn)(*args), *grads(fn)(*args))
        line[tag] = dict(zip(names, map(rel_err, got, want)))
    return line


def main(argv) -> int:
    from deepspeed_tpu.ops.pallas import short_conv as kernels
    for rows in [int(a) for a in argv] or [kernels._CHUNK]:
        kernels._CHUNK = rows
        line = {"rows_a_chunk": rows}
        for stream, spec in STREAMS.items():
            line[stream] = {"geometry": list(kernels._geometry(
                spec[0], spec[1], "bfloat16", spec[5])), **bench(stream)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
