"""The Mamba-2 scan's kernels alone, on the chip: time and results of this
checkout's ``ops/pallas/ssd.py`` against the ``jax.numpy`` form they
replaced (``tests/helpers/ssd_reference.py``).

    chiprun -- python tools/ssd_kernel_bench.py

Sizes a change to the kernels before the cell is run (PR 37). The shape is
the cell's (``train-ssm-s8k-1chip``: one sequence of 8192, 64 heads of 64,
one group of B and C, state 128, chunk 256). One line:

- ``kernel_ms``: ``ds_ssd_fwd``, its states-only form and ``ds_ssd_bwd``,
  each the mean duration of the ``tpu_custom_call`` events of a profiler
  trace of 10 calls, and ``*_busy``: the device busy time of the call
  each sits in (with XLA's copy of ``x`` into the [B, S, H P] tiles);
- ``layer_ms``: ``jax.grad`` of a rematted layer's ``chunk_ssd`` (a
  quadratic loss, so the forward, the rerun and the backward of a train
  step) with the kernels (``kernels``: device busy time a call, each
  kernel's part and the rest) and with ``ssd_reference`` in their place
  (``jnp``), and the same of the forward alone (``fwd``, ``jnp_fwd``);
- ``err``: ``y`` and the five gradients of that loss against the
  reference's: largest difference over the largest value.

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
sys.path.insert(1, os.path.join(ROOT, "tests"))   # helpers/ssd_reference.py
sys.path.insert(2, os.path.join(ROOT, "tools"))
# the trace of CALLS calls and its reductions: one definition for both tools
from kda_kernel_bench import busy_ms, kernel_ms, rel_err, traced  # noqa: E402

SEQ, HEADS, P, GROUPS, STATE, CHUNK = 8192, 64, 64, 1, 128, 256
KERNELS = ("ds_ssd_fwd", "ds_ssd_bwd")


def inputs(seed: int = 37):
    """x, B, C (bf16) and dt, A (float32) as the model's mixer makes them:
    steps log-uniform in [1e-3, 0.1], decay rates in [-16, -1]."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(1, SEQ, HEADS, P))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                            size=(1, SEQ, HEADS)))
    A = -rng.uniform(1.0, 16.0, size=(HEADS,))
    B, C = rng.normal(size=(2, 1, SEQ, GROUPS, STATE))
    bf, f32 = jnp.bfloat16, jnp.float32
    return (jnp.asarray(x, bf), jnp.asarray(dt, f32), jnp.asarray(A, f32),
            jnp.asarray(B, bf), jnp.asarray(C, bf))


def by_kernel(events) -> dict:
    """Device busy time a call, each kernel's part and the rest."""
    out = {"busy": busy_ms(events)}
    for k in KERNELS:
        out[k] = busy_ms(events, rf"^%?{k}[.\d]* = ")
    out["rest"] = 2 * out["busy"] - sum(out.values())
    return out


def main() -> int:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import ssd
    from deepspeed_tpu.ops.pallas import ssd as kernels
    from helpers import ssd_reference
    args = inputs()
    f32 = jnp.float32
    ops, dims = jax.jit(
        lambda *a: kernels._operands(*a, CHUNK)[0])(*args), (
            CHUNK, HEADS, P, GROUPS, STATE)
    prep = lambda f: jax.jit(lambda *a: f(  # noqa: E731
        *kernels._operands(*a, CHUNK)[0]))
    fwd = prep(lambda *o: kernels._forward(*o, dims, states=False))
    states = prep(lambda *o: kernels._forward(*o, dims, states=True))
    ck = states(*args)
    dy = jnp.asarray(np.random.default_rng(1).normal(size=ops[0].shape),
                     jnp.bfloat16)
    bwd = jax.jit(lambda ck, dy, *a: kernels._backward(
        *kernels._operands(*a, CHUNK)[0], ck, dy, dims))
    ev = {"fwd": traced(jax, fwd, args),
          "states": traced(jax, states, args),
          "bwd": traced(jax, bwd, (ck, dy, *args))}
    line = {"shape": [SEQ, HEADS, P, GROUPS, STATE, CHUNK],
            "heads_a_step": kernels._geometry(HEADS, GROUPS, P)[0],
            "kernel_ms": {
                **{k: kernel_ms(v) for k, v in ev.items()},
                **{f"{k}_busy": busy_ms(v) for k, v in ev.items()}}}
    del ck, dy, ops, ev

    def layer_grad(fn):
        layer = jax.checkpoint(lambda *a: fn(*a, chunk=CHUNK))
        return jax.jit(jax.value_and_grad(
            lambda *a: 0.5 * jnp.sum(layer(*a).astype(f32) ** 2),
            argnums=(0, 1, 2, 3, 4)))

    forms = {"kernels": ssd.chunk_ssd, "jnp": ssd_reference.chunk_ssd}
    line["layer_ms"] = {
        name: by_kernel(traced(jax, layer_grad(f), args))
        for name, f in forms.items()}
    for name, f in forms.items():
        tag = "fwd" if name == "kernels" else "jnp_fwd"
        line["layer_ms"][tag] = busy_ms(traced(
            jax, jax.jit(lambda *a, _f=f: _f(*a, chunk=CHUNK)), args))
    got, want = (jax.jit(lambda *a, _f=f: (_f(*a, chunk=CHUNK),
                                           *layer_grad(_f)(*a)[1]))(*args)
                 for f in forms.values())
    line["err"] = dict(zip(("y", "dx", "ddt", "dA", "dB", "dC"),
                           map(rel_err, got, want)))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
