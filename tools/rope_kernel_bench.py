"""The rotation's kernels alone, on the chip: time and results of this
checkout's ``ops/pallas/rope.py`` against the XLA form they replace
(``ops/layers.py`` ``apply_rotary`` and the flash wrapper's transpose).

    chiprun -- python tools/rope_kernel_bench.py [ROWS:WIDTH:CHUNK ...]

Sizes a change to the kernels before the cells are run (PR 62). A call is
what one attention layer does in one direction: q and k of one sequence,
[1, S, H D] bf16 (a projection's output) to the flash kernels' [H, S, D],
at the six cells' shapes (``SHAPES``: query / key heads, head width, rotated width,
sequence). One line a ``ROWS:WIDTH:CHUNK`` (the module's ``_ROWS``,
``_WIDTH`` and ``_CHUNK``: rows and lanes a grid step, rows a pass in
registers; the module's own if none is given), a shape each:

- ``fwd_ms``, ``bwd_ms``: ``ds_rope_fwd`` / ``ds_rope_bwd`` for q and k
  together, the device busy time a call of a profiler trace of 10 calls;
  ``*_gbs`` the GB/s of the operands' one trip (q and k read and written
  once, the two tables read once a kernel) against the chip's 819;
- ``xla_fwd_ms``, ``xla_bwd_ms``: the same call as ``apply_rotary`` and the
  transpose, and their transposes under ``jax.vjp``, as XLA fuses them
  alone (in a step it fuses them with their neighbours: ``PERF.md``
  section 6, PR 62, has both);
- ``equal``: whether the kernels' q, k, dq and dk equal the XLA form's in
  every bit.

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
from kda_kernel_bench import busy_ms, traced  # noqa: E402

PEAK_GBS = 819.0
# shape: (query heads, key heads, head width, rotated width, sequence)
SHAPES = {
    "laguna_swa": (72, 8, 128, 128, 8192),
    "laguna_full": (48, 8, 128, 64, 8192),
    "mellum": (32, 4, 128, 128, 16384),
    "mistral": (32, 8, 128, 128, 8192),
    "ouro": (16, 16, 128, 128, 8192),
    "qwen3_next": (16, 2, 256, 64, 16384),
}


def bench(shape: str) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import layers as L
    from deepspeed_tpu.ops.pallas.rope import rotate_to_heads
    hq, hk, d, rot, s = SHAPES[shape]
    rng = np.random.default_rng(62)
    bf = jnp.bfloat16
    # q and k as a projection hands them over, [1, S, H D]: the layout of a
    # jitted function's 4-D argument would cost either form a copy
    q, k = (jnp.asarray(rng.normal(size=(1, s, h * d)), bf) for h in (hq, hk))
    dq, dk = (jnp.asarray(rng.normal(size=(h, s, d)), bf) for h in (hq, hk))
    tables = L.rotary_tables(*L.rotary_embedding(s, rot), d)
    heads = lambda x: x.reshape(1, s, -1, d)  # noqa: E731

    def pair(q, k):
        return tuple(rotate_to_heads(heads(x), tables.wide, rot)
                     for x in (q, k))

    def xla(q, k):
        return tuple(x.transpose(0, 2, 1, 3).reshape(-1, s, d)
                     for x in L.rotate(heads(q), heads(k), tables))

    def back(fn):       # the forward is dead there: the pair keeps nothing
        return jax.jit(lambda q, k, dq, dk: jax.vjp(fn, q, k)[1]((dq, dk)))

    # q and k read and written once, cos_w and sin_w once a kernel
    nbytes = 2 * (q.size + k.size) * 2 + 2 * 2 * s * d * 4
    line = {}
    for name, fn in (("", pair), ("xla_", xla)):
        fwd = busy_ms(traced(jax, jax.jit(fn), (q, k)))
        bwd = busy_ms(traced(jax, back(fn), (q, k, dq, dk)))
        line.update({f"{name}fwd_ms": fwd, f"{name}bwd_ms": bwd})
        if not name:
            line.update(fwd_gbs=nbytes / fwd * 1e-6,
                        bwd_gbs=nbytes / bwd * 1e-6,
                        least_ms=nbytes / PEAK_GBS * 1e-6)
    got = (*jax.jit(pair)(q, k), *back(pair)(q, k, dq, dk))
    want = (*jax.jit(xla)(q, k), *back(xla)(q, k, dq, dk))
    line["equal"] = [bool(jnp.array_equal(a, b)) for a, b in zip(got, want)]
    return line


def main(argv) -> int:
    from deepspeed_tpu.ops.pallas import rope
    for geometry in [tuple(int(n) for n in a.split(":")) for a in argv] or [
            (rope._ROWS, rope._WIDTH, rope._CHUNK)]:
        rope._ROWS, rope._WIDTH, rope._CHUNK = geometry
        line = {"rows_width_chunk": list(geometry)}
        for shape, (hq, hk, d, _, s) in SHAPES.items():
            line[shape] = {"geometry": [list(rope._geometry(s, h, d))
                                        for h in (hq, hk)], **bench(shape)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
