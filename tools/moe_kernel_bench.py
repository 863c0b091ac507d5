"""The held experts' sweep alone, on the chip: time and results of this
checkout's ``moe/sharded_moe.py`` ``held_experts_ffn`` (the grouped-matmul
kernels and the add to tokens of ``ops/pallas/grouped_matmul.py`` and what
XLA does round them) against the ``jax.numpy`` block loop it replaced
(``tests/helpers/held_reference.py``).

    chiprun -- python tools/moe_kernel_bench.py [ROW_TILE ...]
    chiprun -- python tools/moe_kernel_bench.py router

Sizes a change to the kernels before a cell is run (PRs 41, 48). The
shapes are the three cells' (16384 tokens): at top 8 and hidden 2304,
``mellum`` holds 16 of
64 experts of 896 at a balanced 2048 rows each (ONE chunk of 36,864 rows),
``mellum_over`` the same with eight of them at 2688: 5120 rows over the
share's even total, so a second chunk (what that costs: ``held_chunk``);
``kimi`` holds 8 of 256 of 1024 at 288 rows each, ``kimi_skew`` the same
with one expert at 1163 (a chunk of 6144 rows holds either); at top 10
and hidden 2048, ``qnext`` holds 32 of 512 experts of 512 at a balanced
320 rows each (a chunk of 14,336 rows, a row tile of 128). A line a
shape and ROW_TILE (default
128, 256, 512: the largest multiple of 128 up to it that divides the
block is the kernels' row tile), each number the device busy time of one
call in ms, from a profiler trace of 10 calls:

- ``fwd``: one layer's forward sweep, ``busy``, each kernel's part (the
  add to tokens, ``ds_moe_add_rows``, beside the matmuls') and the
  ``rest``, which is XLA's;
- ``grad``: ``jax.grad`` of a rematted layer's sweep (a loss linear in
  the result, so the backward sweep alone, as in a train step: the
  backward rule keeps nothing but the inputs and the compiler drops the
  rerun's forward sweep), ``busy``, each kernel's part and the ``rest``
  (the sorts, the gathers, the loop);
- ``ops``: the longest device ops of ``grad`` outside the kernels;
- ``router_grad``: whether the routing weights' gradient is made;
- with the default row tile also ``jnp``: the same two of the block loop,
  and ``err``: the result and the gradients against the loop's, largest
  difference over the largest value.

``router``: the routers' selection alone (``moe/sharded_moe.py``
``sigmoid_top_k`` / ``softmax_top_k`` on float32 logits [tokens, experts]),
at the eight routed cells' shapes (``ROUTERS``), as the kernel pair of
``ops/pallas/router.py`` and as XLA's ``lax.top_k``, gather and
``bincount`` (``sharded_moe._top_k_xla``). A line a shape and form, device
busy time of one call in ms from a trace of 10: ``fwd`` (the experts
chosen, their weights, the load), ``grad`` (``jax.grad`` of a weighted sum
of the weights: the forward and the weights' gradient back to the
logits), each kernel's part of them, ``ops`` the longest device ops of
``grad`` outside the kernels; on the kernel's line ``same``: idx, weights,
load and the gradient equal XLA's element for element.

A device number, so only on a TPU. Not the yardstick: what a user feels
is ``benchmark/run.py``.
"""

from __future__ import annotations

import collections
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "tests"))   # helpers/held_reference.py
sys.path.insert(2, os.path.join(ROOT, "tools"))
# the trace of CALLS calls and its reductions: one definition for the tools
from kda_kernel_bench import CALLS, busy_ms, rel_err, traced  # noqa: E402

TOKENS = 16384
KERNELS = ("ds_moe_gmm_fwd", "ds_moe_gmm_bwd", "ds_moe_add_rows")
# name: (experts, held, expert width, rows of expert 0, router_grad,
#        top k, hidden width)
SHAPES = {"mellum": (64, 16, 896, None, False, 8, 2304),
          "mellum_over": (64, 16, 896, 2688, False, 8, 2304),
          "kimi": (256, 8, 1024, None, True, 8, 2304),
          "kimi_skew": (256, 8, 1024, 1163, True, 8, 2304),
          "qnext": (512, 32, 512, None, False, 10, 2048)}


ROUTER_KERNELS = ("ds_router_fwd", "ds_router_bwd")
# cell's family: (tokens a step, experts, top k, router)
ROUTERS = {"nemotron": (8192, 512, 22, "sigmoid"),
           "kanana": (32768, 128, 6, "sigmoid"),
           "qnext": (16384, 512, 10, "softmax"),
           "kimi": (16384, 256, 8, "sigmoid"),
           "mellum": (16384, 64, 8, "softmax"),
           "laguna": (8192, 256, 10, "softmax"),
           "lfm2": (16384, 64, 4, "sigmoid"),
           "xing": (8192, 64, 4, "sigmoid")}


def routing(experts: int, held: int, skew, top_k: int):
    """idx [TOKENS, top_k]: ``mellum`` and ``qnext`` send every expert its
    even rows (2048, 320), ``mellum`` with ``skew`` the first eight that
    many; the Kimi shapes send
    the held experts 9/16 of their even 512 (the cell reads 247-294),
    with ``skew`` expert 0 that many, from tokens that had not chosen
    it."""
    n, j = np.arange(TOKENS)[:, None], np.arange(top_k)[None, :]
    idx = (n + experts // top_k * j) % experts
    if experts == 256:
        away = held + (n + (experts // top_k - 1) * j) % (experts - held)
        sent = n // (experts // top_k) % 16 < 9
        idx = np.where(sent, idx, away)
        if skew:
            more = np.flatnonzero(~sent[:, 0])[:skew - 288]
            idx[more, 0] = 0
    elif skew:      # a choice of an absent expert to one more held one
        more = np.arange(8 * (skew - 2048))
        idx[more] = np.where(idx[more] == more[:, None] % 8 + held,
                             (more[:, None] % 8 + 1) % 8, idx[more])
    return idx.astype(np.int32)


def inputs(name: str, seed: int = 41):
    import jax.numpy as jnp
    experts, held, width, skew, _, top_k, hidden = SHAPES[name]
    rng = np.random.default_rng(seed)
    bf, f32 = jnp.bfloat16, jnp.float32
    normal = lambda *s: rng.standard_normal(s, dtype=np.float32)  # noqa: E731
    ex = {"w_gate": normal(held, hidden, width) / hidden ** 0.5,
          "w_up": normal(held, hidden, width) / hidden ** 0.5,
          "w_down": normal(held, width, hidden) / width ** 0.5}
    return (jnp.asarray(normal(TOKENS, hidden), bf),
            jnp.asarray(routing(experts, held, skew, top_k)),
            jnp.asarray(rng.uniform(0.05, 0.3, (TOKENS, top_k)), f32),
            {k: jnp.asarray(v, bf) for k, v in ex.items()},
            jnp.asarray(normal(TOKENS, hidden), bf))


def split(events, kernels=KERNELS) -> dict:
    out = {"busy": busy_ms(events)}
    for k in kernels:
        out[k] = busy_ms(events, rf"^%?{k}[.\d]* = ")
    out["rest"] = 2 * out["busy"] - sum(out.values())
    return {k: round(v, 3) for k, v in out.items()}


def longest(events, n: int = 6) -> dict:
    """The n leaf ops outside the kernels with the most time, ms a call."""
    total = collections.Counter()
    for name, a, b in events:
        op = name.split(" = ")[0].lstrip("%")
        if not op.startswith(("while", "ds_moe_", "ds_router_",
                              "conditional")):
            total[op] += b - a
    return {op: round(1e-6 * ns / CALLS, 3) for op, ns in total.most_common(n)}


def routers() -> int:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops.pallas import router
    fits = router.fits

    def forms(kind, k):
        def route(logits, bias):
            if kind == "sigmoid":
                out = sharded_moe.sigmoid_top_k(logits, bias, k, scaling=2.5)
            else:
                out = sharded_moe.softmax_top_k(logits, k)
            return out[0], out[1], out[3]

        def loss(logits, bias, ct):
            return jnp.sum(route(logits, bias)[1] * ct)
        return jax.jit(route), jax.jit(jax.grad(loss))

    for name, (tokens, experts, k, kind) in ROUTERS.items():
        rng = np.random.default_rng(67)
        logits = jnp.asarray(rng.standard_normal((tokens, experts),
                                                 dtype=np.float32))
        bias = jnp.asarray(0.01 * rng.standard_normal(experts,
                                                      dtype=np.float32))
        ct = jnp.asarray(rng.standard_normal((tokens, k), dtype=np.float32))
        got = {}
        for form in ("xla", "kernel"):
            router.fits = fits if form == "kernel" else (lambda *a: False)
            fwd, grad = forms(kind, k)
            ev = traced(jax, grad, (logits, bias, ct))
            line = {"router": name, "tokens": tokens, "experts": experts,
                    "k": k, "kind": kind, "form": form,
                    "fwd": split(traced(jax, fwd, (logits, bias)),
                                 ROUTER_KERNELS),
                    "grad": split(ev, ROUTER_KERNELS), "ops": longest(ev)}
            got[form] = (*fwd(logits, bias), grad(logits, bias, ct))
            if form == "kernel":
                line["same"] = [bool(jnp.array_equal(a, b)) for a, b in
                                zip(got["xla"], got["kernel"])]
            print(json.dumps(line), flush=True)
        router.fits = fits
    return 0


def main(argv) -> int:
    if argv == ["router"]:
        return routers()
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops.pallas import grouped_matmul
    from helpers import held_reference
    tiles = [int(a) for a in argv] or [128, 256, 512]
    f32 = jnp.float32

    def forms(fn, block, *more):     # more: router_grad, rows a chunk
        def layer(x, idx, w, ex):
            return fn(x, idx, w, ex, 0, block, *more)[0]

        def loss(x, w, ex, idx, ct):
            return jnp.sum(jax.checkpoint(layer)(x, idx, w, ex).astype(f32)
                           * ct.astype(f32))
        return jax.jit(layer), jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    for name, (experts, held, _, _, router_grad, top_k, _) in SHAPES.items():
        x, idx, w, ex, ct = inputs(name)
        block = sharded_moe.held_block(TOKENS, top_k, experts)
        chunk = sharded_moe.held_chunk(TOKENS, top_k, experts, held, block)
        rows = np.bincount(np.asarray(idx).ravel(),
                           minlength=experts)[:held]
        default = grouped_matmul.ROW_TILE
        for tile in tiles:
            grouped_matmul.ROW_TILE = tile
            fwd, grad = forms(sharded_moe.held_experts_ffn, block,
                              router_grad, chunk)
            ev = traced(jax, grad, (x, w, ex, idx, ct))
            line = {"shape": name, "block": block, "chunk": chunk,
                    "row_tile": grouped_matmul.row_tile(block),
                    "rows": [int(rows.min()), int(rows.max())],
                    "router_grad": router_grad,
                    "fwd": split(traced(jax, fwd, (x, idx, w, ex))),
                    "grad": split(ev), "ops": longest(ev)}
            if tile == default:
                ref_fwd, ref_grad = forms(held_reference.held_experts_ffn,
                                          block)
                line["jnp"] = {
                    "fwd": round(busy_ms(traced(
                        jax, ref_fwd, (x, idx, w, ex))), 3),
                    "grad": round(busy_ms(traced(
                        jax, ref_grad, (x, w, ex, idx, ct))), 3)}
                got = (fwd(x, idx, w, ex), *grad(x, w, ex, idx, ct))
                want = (ref_fwd(x, idx, w, ex),
                        *ref_grad(x, w, ex, idx, ct))
                if not router_grad:     # the kernel leaves that product out
                    got, want = (got[:2] + got[3:]), (want[:2] + want[3:])
                line["err"] = [round(rel_err(a, b), 5) for a, b in zip(
                    jax.tree.leaves(got), jax.tree.leaves(want))]
            print(json.dumps(line), flush=True)
        grouped_matmul.ROW_TILE = default
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
