"""The rotation's kernels alone, on the chip: time and results of this
checkout's ``ops/pallas/rope.py`` against the XLA form they replace
(``ops/layers.py`` ``apply_rotary`` and the flash wrapper's transpose).

    chiprun -- python tools/rope_kernel_bench.py [latent] [ROWS:WIDTH:CHUNK ...]

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

Then the latent pass (PR 65, ``latent_to_heads``: ``ds_latent_fwd`` /
``ds_latent_bwd``) at the three latent cells' shapes (``LATENT``: heads,
nope, rope, value, sequence, the rotation's form) against
``ops/layers.py`` ``latent_attention``'s XLA form and the transposes, the
same keys, and ``*_kernel_ms`` / ``*_gbs`` of the kernels alone (XLA
copies this jitted function's 192-wide arguments and results round them:
``ops``, the longest device ops); its bytes are the three projections read
once and q, k, v written once at their STORED widths (a head of 192 takes
256 lanes), the tables once; ``equal``: the forward bit for bit once interleaved pairs are
laid back, the cotangents within one rounding of bf16 (``dk_pe`` is summed
over the heads in float32 where XLA rounds twice).

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


# shape: (heads, nope, rope, value, sequence, pairs | halves | none)
LATENT = {
    "kanana": (32, 128, 64, 128, 32768, "pairs"),
    "xing4": (32, 128, 64, 128, 8192, "halves"),
    "kimi": (32, 128, 64, 128, 16384, "none"),
}


def bench_latent(shape: str) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import layers as L
    from deepspeed_tpu.ops.pallas import rope as kernels
    h, nope, rope, dv, s, form = LATENT[shape]
    w, pairs = nope + rope, form == "pairs"
    rng = np.random.default_rng(65)
    bf = jnp.bfloat16
    draw = lambda *dims: jnp.asarray(rng.normal(size=dims), bf)  # noqa: E731
    args = (draw(1, s, h * w), draw(1, s, h * (nope + dv)), draw(1, s, rope))
    cts = (draw(h, s, w), draw(h, s, w), draw(h, s, dv))
    tables = None if form == "none" else L.latent_rotary_tables(
        *L.rotary_embedding(s, rope), pairs=pairs)
    heads = lambda x: x.reshape(1, s, h, -1)  # noqa: E731

    def pair(q, kv, k_pe):
        return kernels.latent_to_heads(heads(q), heads(kv), k_pe,
                                       tables and tables.wide, pairs=pairs)

    def xla(q, kv, k_pe):
        laid = []
        L.latent_attention(
            lambda q, k, v: laid.extend(
                x.transpose(0, 2, 1, 3).reshape(h, s, -1)
                for x in (q, k, v)) or v,
            heads(q), heads(kv), k_pe, tables, pairs=pairs)
        return tuple(laid)

    def back(fn):
        return jax.jit(lambda *a: jax.vjp(fn, *a[:3])[1](a[3:]))

    pad = lambda n: -(-n // 128) * 128  # noqa: E731
    nbytes = 2 * s * (h * (w + nope + dv) + pad(rope)
                      + h * (2 * pad(w) + pad(dv))) + (
                          0 if tables is None else 2 * s * 128 * 4)
    line = {"geometry": list(kernels._latent_geometry(s, h, nope, dv, 2))}
    for name, fn in (("", pair), ("xla_", xla)):
        events = (traced(jax, jax.jit(fn), args),
                  traced(jax, back(fn), (*args, *cts)))
        fwd, bwd = (busy_ms(e) for e in events)
        line.update({f"{name}fwd_ms": fwd, f"{name}bwd_ms": bwd})
        if not name:
            # the kernels alone: beside them stand XLA's copies of this
            # jitted function's 192-wide arguments and results to the
            # layouts a matmul and a Mosaic call hand over without one
            fwd, bwd = (busy_ms(e, "ds_latent_") for e in events)
            line.update(fwd_kernel_ms=fwd, bwd_kernel_ms=bwd,
                        fwd_gbs=nbytes / fwd * 1e-6,
                        bwd_gbs=nbytes / bwd * 1e-6,
                        least_ms=nbytes / PEAK_GBS * 1e-6,
                        ops=[longest_ops(e) for e in events])

    def laid_back(x):       # interleaved pairs as pairs_to_halves has them
        return x if not pairs or x.shape[-1] != w else jnp.concatenate(
            [x[..., :nope], L.pairs_to_halves(x[..., nope:])], axis=-1)

    def interleaved(x):     # and halves as the pairs they came from
        r = x[..., nope:]
        return x if not pairs or x.shape[-1] != w else jnp.concatenate(
            [x[..., :nope], jnp.stack(jnp.split(r, 2, axis=-1), -1).reshape(
                r.shape)], axis=-1)

    got = jax.jit(pair)(*args)
    want = jax.jit(xla)(*args)
    line["equal"] = [bool(jnp.array_equal(laid_back(a), b))
                     for a, b in zip(got, want)]
    got = back(pair)(*args, *(interleaved(c) for c in cts))
    want = back(xla)(*args, *cts)
    line["cotangents_within"] = [
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
                      / (jnp.abs(b.astype(jnp.float32)) + 1.0)))
        for a, b in zip(got, want)]
    return line


def longest_ops(events, n: int = 4) -> dict:
    """{op: ms a call} of the ``n`` longest device ops of a trace: whether
    anything of XLA's stands beside the kernel."""
    from kda_kernel_bench import CALLS
    total = {}
    for name, a, b in events:
        name = name.split(" = ")[0]
        total[name] = total.get(name, 0.0) + 1e-6 * (b - a) / CALLS
    return dict(sorted(total.items(), key=lambda kv: -kv[1])[:n])


def main(argv) -> int:
    from deepspeed_tpu.ops.pallas import rope
    # "latent" alone: the latent pass and not the six shapes of PR 62;
    # "latent=ROWS:CHUNK:MIB": at that geometry (``_ROWS``, ``_CHUNK`` and
    # ``_LATENT_BLOCKS`` in MiB)
    latent = [a for a in argv if a.startswith("latent")]
    argv = [a for a in argv if a not in latent]
    for geometry in [] if latent and not argv else [
            tuple(int(n) for n in a.split(":")) for a in argv] or [
            (rope._ROWS, rope._WIDTH, rope._CHUNK)]:
        rope._ROWS, rope._WIDTH, rope._CHUNK = geometry
        line = {"rows_width_chunk": list(geometry)}
        for shape, (hq, hk, d, _, s) in SHAPES.items():
            line[shape] = {"geometry": [list(rope._geometry(s, h, d))
                                        for h in (hq, hk)], **bench(shape)}
        print(json.dumps(line), flush=True)
    for a in latent or ["latent"]:
        if "=" in a:
            rows, chunk, mib = (int(n) for n in a.split("=")[1].split(":"))
            rope._ROWS, rope._CHUNK = rows, chunk
            rope._LATENT_BLOCKS = mib * 2 ** 20
        print(json.dumps({
            "rows_chunk_blocks": [rope._ROWS, rope._CHUNK,
                                  rope._LATENT_BLOCKS],
            **{shape: bench_latent(shape) for shape in LATENT}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
