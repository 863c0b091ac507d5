"""The gated norm's kernels alone, on the chip: time and results of this
checkout's ``ops/pallas/gated_norm.py`` against the ``jax.numpy`` forms
they replaced (``tests/helpers/gated_norm_reference.py``).

    chiprun -- python tools/gated_norm_bench.py [FWD_ROWS:BWD_ROWS ...]

Sizes a change to the kernels before the cells are run (PR 55). The shapes
are the two cells', one sequence of 16384 and 32 heads of 128 each:
``train-kda-s16k-1chip`` (``kimi``: sigmoid with a bias, the norm rounded
to bf16, ``o`` as four head groups' stack [4, 1, 8, 16384, 128]) and
``train-gdn-s16k-1chip`` (``qwen``: SiLU, float32 to the last cast, one
group [1, 1, 32, 16384, 128]). One line a ``FWD_ROWS:BWD_ROWS`` (the
module's ``_ROWS_FWD`` and ``_ROWS_BWD``: rows a grid step of each
kernel; the module's own if none is given), a form each:

- ``fwd_ms``, ``bwd_ms``: ``ds_gated_norm_fwd`` / ``ds_gated_norm_bwd``,
  the mean duration of the ``tpu_custom_call`` events of a profiler trace
  of 10 calls, ``*_gbs`` the GB/s of the operands' one trip (o, the gate
  and y; o, the gate, dy, do and dgate), ``fwd_busy`` the device busy time
  of the call it sits in;
- ``layer_ms``: ``jax.value_and_grad`` of the op under a linear loss: one
  forward and one backward (the pair's residuals are its inputs, so a
  checkpoint round it alone reruns nothing; a train step's rerun, for the
  output matmul's sake, is one more ``fwd_ms``) with the kernels (device
  busy time a call, each kernel's part and the rest) and ``jnp_ms`` with
  the reference in their place (``o`` [1, 16384, 32, 128] as the scan
  handed it over until PR 55); ``jnp_fwd_ms`` its forward;
- ``err``: ``y`` and the gradients against the reference's IN FLOAT32 on
  the same bf16 inputs, and ``jnp_err`` the bf16 reference's own: largest
  difference over the largest value.

A device number, so only on a TPU. Not the yardstick: what a user feels
is ``benchmark/run.py``.
"""

from __future__ import annotations

import functools
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

KERNELS = ("ds_gated_norm_fwd", "ds_gated_norm_bwd")
SEQ, HEADS, DIM = 16384, 32, 128
# form: (head groups, the op's keywords, a bias)
FORMS = {
    "kimi": (4, dict(act="sigmoid", eps=1e-5, round_norm=True), True),
    "qwen": (1, dict(act="silu", eps=1e-6), False),
}


def inputs(form: str, seed: int = 55):
    """o [1, S, H, d], the gate's pre-activation, the norm's weight, the
    bias (or None) and a cotangent, bf16."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    wide = (1, SEQ, HEADS * DIM)
    return (jnp.asarray(rng.normal(size=(1, SEQ, HEADS, DIM)), bf),
            jnp.asarray(2 * rng.normal(size=wide), bf),
            jnp.asarray(1 + 0.1 * rng.normal(size=(DIM,)), bf),
            jnp.asarray(rng.normal(size=wide[-1:]), bf) if FORMS[form][2]
            else None,
            jnp.asarray(rng.normal(size=wide), bf))


def by_head(o, groups: int):
    """[B, S, H, d] as the heads' stack [G, B, H / G, S, d]."""
    b, s, h, d = o.shape
    return o.reshape(b, s, groups, h // groups, d).transpose(2, 0, 3, 1, 4)


def kernel_ms(events, kernel: str) -> float:
    return busy_ms(events, rf"^%?{kernel}[.\d]* = ")


def by_kernel(events) -> dict:
    """Device busy time a call, each kernel's part and the rest."""
    out = {"busy": busy_ms(events)}
    for k in KERNELS:
        out[k] = kernel_ms(events, k)
    out["rest"] = 2 * out["busy"] - sum(out.values())
    return out


def bench(form: str) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import layers as L
    from helpers import gated_norm_reference as reference
    groups, kw, _ = FORMS[form]
    o, gate, w, bias, dy = inputs(form)
    f32 = jnp.float32
    kernels = functools.partial(L.gated_norm, **kw)
    if form == "kimi":
        plain = functools.partial(reference.kimi_gated_norm, eps=kw["eps"])
    else:
        plain = lambda o, z, w, _: reference.qwen_gated_norm(  # noqa: E731
            o, z, w, kw["eps"])

    def grads(fn):
        loss = lambda o, g, w, b, dy: jnp.sum(  # noqa: E731
            fn(o, g, w, b).astype(f32) * dy.astype(f32))
        grad = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2) if bias is None else (0, 1, 2, 3)))
        return lambda *a: grad(*a, dy)[1]   # an argument, not a constant

    stacked = (by_head(o, groups), gate, w, bias)
    args = (o, gate, w, bias)
    nbytes = gate.size * 2
    fwd_ev = traced(jax, jax.jit(kernels), stacked)
    layer_ev = traced(jax, grads(kernels), stacked)
    fwd_ms = kernel_ms(fwd_ev, KERNELS[0])
    bwd_ms = kernel_ms(layer_ev, KERNELS[1])
    line = {
        "fwd_ms": fwd_ms, "fwd_gbs": 3 * nbytes / fwd_ms * 1e-6,
        "fwd_busy": busy_ms(fwd_ev),
        "bwd_ms": bwd_ms, "bwd_gbs": 5 * nbytes / bwd_ms * 1e-6,
        "layer_ms": by_kernel(layer_ev),
        "jnp_ms": busy_ms(traced(jax, grads(plain), args)),
        "jnp_fwd_ms": busy_ms(traced(jax, jax.jit(plain), args)),
    }
    del fwd_ev, layer_ev
    exact = tuple(None if v is None else v.astype(f32) for v in args)
    want = (jax.jit(plain)(*exact), *grads(plain)(*exact))
    names = ("y", "do", "dgate", "dw", "dbias")

    def tokens(do):     # the stack's cotangent as [B, S, H, d]
        return do.transpose(1, 3, 0, 2, 4).reshape(o.shape)

    got = [jax.jit(kernels)(*stacked), *grads(kernels)(*stacked)]
    got[1] = tokens(got[1])
    line["err"] = dict(zip(names, map(rel_err, got, want)))
    got = (jax.jit(plain)(*args), *grads(plain)(*args))
    line["jnp_err"] = dict(zip(names, map(rel_err, got, want)))
    return line


def main(argv) -> int:
    from deepspeed_tpu.ops.pallas import gated_norm as kernels
    for rows in [tuple(int(r) for r in a.split(":")) for a in argv] or [
            (kernels._ROWS_FWD, kernels._ROWS_BWD)]:
        kernels._ROWS_FWD, kernels._ROWS_BWD = rows
        line = {"rows_a_step": list(rows)}
        for form in FORMS:
            line[form] = {"geometry": [list(kernels._geometry(
                SEQ, DIM, "bfloat16", r)) for r in rows], **bench(form)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
