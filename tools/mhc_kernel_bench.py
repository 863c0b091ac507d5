"""The hyper-connections' six kernels alone, on the chip: time and results
of this checkout's ``ops/pallas/mhc.py`` against the ``jax.numpy`` forms of
``ops/mhc.py``.

    chiprun -- python tools/mhc_kernel_bench.py

Sizes a change to the kernels before the cell is run (PR 57). The shapes
are ``train-mhc-s8k-1chip``'s: 8192 tokens of 4 streams of 3584 bf16, 24
coefficients a token in one 128-lane float32 row. One JSON line:

- ``kernel_ms``: each of ``ds_mhc_pre_fwd``, ``ds_mhc_pre_bwd``,
  ``ds_mhc_coef_fwd``, ``ds_mhc_coef_bwd``, ``ds_mhc_post_fwd``,
  ``ds_mhc_post_bwd`` in ms a call (its ``tpu_custom_call`` events in a
  profiler trace of 10 gradients of a rematted sublayer, ``mhc_pre`` -> a
  scaling -> ``mhc_post``) and ``kernel_gbs`` the GB/s of its operands' and
  results' one trip;
- ``sublayer_ms``: that gradient's device busy time a call, each kernel's
  part and the rest (what XLA does between and behind them), ``handed_on``
  (``mhc_post`` reads what ``mhc_pre`` handed on: ``ds_mhc_pre_bwd`` adds
  the post pass's ``dX`` inside) and ``own_x`` (the caller's own ``x`` a
  second time: two consumers, XLA adds the two [8192, 14336] cotangents;
  the kernel then reads zeros in the handed-on one's place);
- ``coef_ms``: the coefficients alone on [8192, 128] float32 rows, forward
  and forward + backward, the kernel pair and ``ops/mhc.py``
  ``coefficients`` (the ``jax.numpy`` form every other backend runs);
- ``err``: the pair's ``H_post``, ``H_res``, residual and ``draw`` against
  that form (largest difference over the largest value), and the sublayer's
  output and four gradients through the kernels against the ``jax.numpy``
  path in float32 on the same bf16 inputs.

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
sys.path.insert(1, os.path.join(ROOT, "tools"))
# the trace of CALLS calls and its reductions: one definition for the tools
from kda_kernel_bench import busy_ms, rel_err, traced  # noqa: E402

KERNELS = ("ds_mhc_pre_fwd", "ds_mhc_pre_bwd", "ds_mhc_coef_fwd",
           "ds_mhc_coef_bwd", "ds_mhc_post_fwd", "ds_mhc_post_bwd")
TOKENS, STREAMS, WIDTH = 8192, 4, 3584
HYPER = dict(eps=1e-6, clamp=(-30.0, 30.0), iters=20)


def inputs(seed: int = 57):
    """x [1, T, n, C], phi, b, alpha bf16 at the cell's seeded spreads."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    n, c, bf = STREAMS, WIDTH, jnp.bfloat16
    k = n * (n + 2)
    b = rng.normal(size=(k,)) * np.repeat([2, 2, 0.5], [n, n, n * n])
    b[2 * n:] += np.eye(n).reshape(-1)
    return (jnp.asarray(rng.normal(size=(1, TOKENS, n, c)), bf),
            jnp.asarray(rng.normal(size=(n * c, k)) * (n * c) ** -0.5, bf),
            jnp.asarray(b, bf), jnp.asarray([2.0, 2.0, 0.5], bf))


def kernel_events(events, kernel: str):
    return [(a, b) for name, a, b in events
            if name.lstrip("%").startswith(kernel)]


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
    from deepspeed_tpu.ops import mhc
    from deepspeed_tpu.ops.pallas import mhc as kernels
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    f32 = jnp.float32
    n, c, t = STREAMS, WIDTH, TOKENS
    k = n * (n + 2)
    args = inputs()

    def sublayer(flow):
        def run(x, phi, b, alpha):
            u, h_post, h_res, _, on = mhc.mhc_pre(x, phi, b, alpha, **HYPER)
            return mhc.mhc_post(on if flow == "handed_on" else x, u * 0.5,
                                h_post, h_res)
        layer = jax.checkpoint(run)
        return jax.jit(jax.value_and_grad(
            lambda *a: 0.5 * jnp.sum(layer(*a).astype(f32) ** 2),
            argnums=(0, 1, 2, 3)))

    line = {"shape": [t, n, c], "sublayer_ms": {}}
    for flow in ("handed_on", "own_x"):
        events = traced(jax, sublayer(flow), args)
        line["sublayer_ms"][flow] = by_kernel(events)
        if flow == "handed_on":
            ms = {}
            for kernel in KERNELS:
                spans = kernel_events(events, kernel)
                ms[kernel] = 1e-6 * sum(b - a for a, b in spans) / max(
                    len(spans), 1)
            line["kernel_ms"] = ms
    x_b, u_b, row_b = t * n * c * 2, t * c * 2, t * 128 * 4
    phi_b, dphi_b = n * c * 128 * 2, n * c * 128 * 4
    trip = {"ds_mhc_pre_fwd": x_b + phi_b + u_b + row_b,
            "ds_mhc_pre_bwd": 3 * x_b + phi_b + u_b + row_b + dphi_b,
            "ds_mhc_coef_fwd": 2 * row_b, "ds_mhc_coef_bwd": 3 * row_b,
            "ds_mhc_post_fwd": 2 * x_b + u_b + row_b,
            "ds_mhc_post_bwd": 3 * x_b + 2 * u_b + 2 * row_b}
    line["kernel_gbs"] = {kernel: trip[kernel] / ms * 1e-6
                          for kernel, ms in line["kernel_ms"].items() if ms}

    # the coefficients alone
    rng = np.random.default_rng(5)
    raw = jnp.asarray(np.pad(2 * rng.normal(size=(t, k)),
                             ((0, 0), (0, 128 - k))), f32)
    d_row = jnp.asarray(np.pad(rng.normal(size=(t, n + n * n)),
                               ((0, 0), (0, 128 - n - n * n))), f32)
    pair = lambda r: kernels.coefficients(  # noqa: E731
        r, n, HYPER["eps"], HYPER["clamp"], HYPER["iters"])
    plain = lambda r: mhc.coefficients(r[:, n:k], n, **HYPER)  # noqa: E731

    def pair_grad(r, d):
        return jax.vjp(lambda r: pair(r)[0], r)[1](d)[0]

    def plain_grad(r, d):
        return jax.vjp(lambda r: plain(r)[:2], r)[1](
            (d[:, :n], d[:, n:n + n * n]))[0]

    line["coef_ms"] = {
        "kernels_fwd": busy_ms(traced(jax, jax.jit(pair), (raw,))),
        "kernels_fwd_bwd": busy_ms(traced(jax, jax.jit(pair_grad),
                                          (raw, d_row))),
        "jnp_fwd": busy_ms(traced(jax, jax.jit(plain), (raw,))),
        "jnp_fwd_bwd": busy_ms(traced(jax, jax.jit(plain_grad),
                                      (raw, d_row)))}
    (row, residual), want = jax.jit(pair)(raw), jax.jit(plain)(raw)
    line["err"] = {
        "h_post": rel_err(row[:, :n], want[0]),
        "h_res": rel_err(row[:, n:n + n * n], want[1]),
        "residual": [float(residual), float(want[2])],
        "draw": rel_err(jax.jit(pair_grad)(raw, d_row)[:, :k],
                        jax.jit(plain_grad)(raw, d_row)[:, :k])}

    # the sublayer through the kernels against the jax.numpy path, float32
    exact = tuple(v.astype(f32) for v in args)
    mhc._use_kernels = lambda: False
    want = sublayer("own_x")(*exact)
    mhc._use_kernels = lambda: jax.default_backend() == "tpu"
    for flow in ("handed_on", "own_x"):
        got = sublayer(flow)(*args)
        line["err"][flow] = dict(zip(
            ("loss", "dx", "dphi", "db", "dalpha"),
            map(rel_err, jax.tree.leaves(got), jax.tree.leaves(want))))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
