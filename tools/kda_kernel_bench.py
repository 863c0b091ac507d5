"""The four KDA kernels alone, on the chip: time and results of this
checkout's ``ops/pallas/kda.py`` against the forms they replaced.

    chiprun -- python tools/kda_kernel_bench.py [--key-heads N]
        [--gate channel|head] [HEADS ...]

Sizes a change to the kernels before a cell is run (PRs 32, 35). Shapes
are the cell's (one sequence of 16384, heads of 128, chunks of 64) at each
HEADS (default 8, a head group of the Kimi cell, and 32, a layer).
``--key-heads`` gives q and k fewer heads than v, g and beta (default: as
many), ``--gate head`` a log-decay a head (default: a channel):
``--key-heads 16 --gate head 32`` is a Gated DeltaNet layer of the
Qwen3-Next cell. A line a head count:

- ``kernel_ms``: the recurrence's forward kernel, its checkpoint form and
  its backward kernel, each the mean duration of the ``tpu_custom_call``
  events of a profiler trace of 10 calls;
- ``prep_ms``: the preparation's forward and backward kernel the same way
  (``fwd``, ``bwd``) and the device busy time of the call they sit in
  (``fwd_busy``, ``bwd_busy``: with XLA's copies of the inputs into the
  [B, S, H d] tiles the kernels read); ``fwd_no_inverse``: the forward
  kernel with ``_inverse_unit_lower`` left out (``T = a_kk``: wrong
  operands, the same blocks and stores), so ``inverse`` = ``fwd`` less it
  is the inverse's own time, the heads' placement side by side and their
  taking apart in it (PR 44); ``jnp_fwd`` / ``jnp_fwd_bwd``: the
  ``jax.numpy`` preparation they replaced (``tests/helpers/
  kda_reference.py``; q and k repeated to the value heads and a gate a head
  widened to the channels for it: this tool's decays are mild enough for
  its row blocks) and its autodiff, device busy time a call; with fewer key
  heads ``repeated_fwd_busy`` / ``repeated_bwd_busy``: the form PR 53
  replaced, ``jnp.repeat`` of q and k and then the kernel at equal head
  counts, the backward with XLA's sum over a key head's copies;
- ``scan_ms``: the recurrence in PR 31's form (``scan_recurrence`` below,
  a copy: ``jax.lax.scan`` over the chunks, autodiff backward) on the same
  operands, forward alone and forward with backward, device busy time;
- ``chunk_kda_ms`` (at 8 heads): ``jax.grad`` of ``ops.kda.chunk_kda`` on
  one head group's heads (so the backward with the preparation run again;
  the loss is linear in ``o``, so the first forward is dead): device busy
  time a call, each kernel's part, the rest;
- ``layer_ms`` (at 32 heads): ``jax.grad`` of a layer's ``chunk_kda`` (four
  head groups with a gate a channel, ONE with a gate a head, as the two
  cells run it, under the layer's remat with the models' policy, which
  keeps ``o`` of four; the loss is quadratic: what a step runs of a layer,
  the forward, the backward's preparation and the backward), split the
  same way: ``outside_kernels`` (``rest`` until PR 59, the same reading)
  is the layer's time outside the four kernels, what XLA moves round
  them, and ``moves`` the part of it in ops named copy, transpose, slice
  or dynamic-update-slice;
- ``err`` / ``prep_err``: the recurrence's ``o`` and six cotangents against
  the scan's, the preparation's six operands and five gradients against
  the ``jax.numpy`` form's: largest difference over the largest value.

A device number, so only on a TPU. Not the yardstick: what a user feels
is ``benchmark/run.py``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "tests"))   # helpers/kda_reference.py
SEQ, D, CHUNK, CALLS = 16384, 128, 64, 10
TRACE_DIR = os.path.join(ROOT, ".bench_trace", "kda_kernel_bench")


def scan_recurrence(u_v, w, q_in, a_qk, k_out, shrink, *, out_dtype):
    """``_chunk_kda``'s recurrence as it stood before PR 32: operands
    [B, H, N, C, .], one scan step a chunk, the state [B, H, dk, dv]."""
    import jax
    import jax.numpy as jnp
    dt = w.dtype
    mm = lambda x, y: jnp.matmul(  # noqa: E731
        x.astype(dt), y.astype(dt), preferred_element_type=jnp.float32)

    def step(state, xs):
        u_v, w, q_in, a_qk, k_out, shrink = xs
        u = u_v - mm(w, state)
        o = mm(q_in, state) + mm(a_qk, u)
        state = state * shrink[..., None] + mm(
            jnp.swapaxes(k_out, -1, -2), u)
        return state, o.astype(out_dtype)

    b, h, _, _, dk = w.shape
    xs = tuple(jnp.moveaxis(x, 2, 0)
               for x in (u_v, w, q_in, a_qk, k_out, shrink))
    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, u_v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2)


def inputs(heads: int, key_heads: int, head_gate: bool, seed: int = 32):
    """q, k [1, SEQ, key_heads, D], v [1, SEQ, heads, D] (bf16), g (a
    channel's [1, SEQ, heads, D] or a head's [1, SEQ, heads]) and beta
    (float32) as the model's mixer makes them: unit keys, decays from 1e-3
    to 1.6 a token."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    shape = (1, SEQ, heads, D)
    keys = (1, SEQ, key_heads, D)
    unit = lambda x: x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(rng.normal(size=keys)) / np.sqrt(D)
    k = unit(rng.normal(size=keys))
    v = rng.normal(size=shape)
    g = -np.exp(rng.uniform(-7, 0.5, size=shape[:3] if head_gate else shape))
    beta = 1 / (1 + np.exp(-rng.normal(size=shape[:3])))
    return ([jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)]
            + [jnp.asarray(x, jnp.float32) for x in (g, beta)])


def traced(jax, fn, args):
    """[(name, start, end)] of device 0's ops over CALLS calls of fn."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    newest = sorted(glob.glob(os.path.join(
        TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in jax.profiler.ProfileData.from_file(newest).planes
            if plane.name.startswith("/device:TPU:0")
            for line in plane.lines if line.name == "XLA Ops"
            for e in line.events]


def busy_ms(events, pattern: str = "") -> float:
    """Union of the matching events' intervals, ms a call."""
    rx, total, end = re.compile(pattern), 0, 0
    for _, a, b in sorted((e for e in events if rx.search(e[0])),
                          key=lambda e: e[1]):
        total += max(b, end) - max(a, end)
        end = max(b, end)
    return 1e-6 * total / CALLS


def kernel_ms(events) -> float:
    ds = [b - a for name, a, b in events if "tpu_custom_call" in name]
    return 1e-6 * sum(ds) / max(len(ds), 1)


def by_kernel(events) -> dict:
    """Device busy time a call, each KDA kernel's part, the time outside
    them and the part of that in ops that only move data."""
    out = {"busy": busy_ms(events)}
    for k in ("ds_kda_prep_fwd", "ds_kda_prep_bwd", "ds_kda_fwd",
              "ds_kda_bwd"):
        out[k] = busy_ms(events, rf"^%?{k}[.\d]* = ")
    out["outside_kernels"] = 2 * out["busy"] - sum(out.values())
    out["moves"] = busy_ms(
        events, r"^%?(copy|transpose|slice|dynamic-slice|dynamic_slice"
        r"|dynamic-update-slice|dynamic_update_slice)[.\w\-]* = ")
    return out


def rel_err(a, b) -> float:
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("heads", nargs="*", type=int, default=[8, 32])
    ap.add_argument("--key-heads", type=int, default=None)
    ap.add_argument("--gate", choices=("channel", "head"), default="channel")
    opts = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import kda
    from deepspeed_tpu.ops.pallas import _common, kda as kernels
    from helpers import kda_reference
    bf = jnp.bfloat16
    kernel_prepare = kernels.kda_prepare
    head_gate = opts.gate == "head"
    for heads in opts.heads:
        key_heads = opts.key_heads or heads
        rep = heads // key_heads
        args = inputs(heads, key_heads, head_gate)
        repeat = lambda x: jnp.repeat(x, rep, axis=2)  # noqa: E731

        def jnp_prepare(q, k, v, g, beta, *, chunk):
            """The ``jax.numpy`` form at what it knows: equal head counts,
            a gate a channel."""
            if head_gate:
                g = jnp.broadcast_to(g[..., None], v.shape[:3] + (D,))
            return kda_reference.prepare(repeat(q), repeat(k), v, g, beta,
                                         chunk=chunk)
        ops = jax.jit(lambda *a: kernel_prepare(*a, chunk=CHUNK))(*args)
        rng = np.random.default_rng(1)
        do = jnp.asarray(rng.normal(size=ops[0].shape), bf)
        flat = tuple(x.reshape(-1, *x.shape[2:]) for x in ops)
        # cotangents of the six operands, as ds_kda_bwd would hand them
        cts = tuple(jnp.asarray(rng.normal(size=x.shape), x.dtype)
                    for x in flat)
        fwd = jax.jit(lambda *o: kernels._forward(o, bf, states=False))
        states = jax.jit(lambda *o: kernels._forward(o, bf, states=True))
        bwd = jax.jit(lambda *a: kernels._backward(a[:6], a[6], a[7]))
        prep_fwd = jax.jit(lambda *a: kernels._prepare_forward(
            *kernels._prep_inputs(*a, CHUNK)))
        prep_bwd = jax.jit(lambda *a: (
            lambda ins, p: kernels._prep_gradients(
                kernels._prepare_backward(ins, a[5:], p), *a[:5]))(
                    *kernels._prep_inputs(*a[:5], CHUNK)))
        ref_fwd = jax.jit(lambda *a: jnp_prepare(*a, chunk=CHUNK))
        pull = lambda f: jax.jit(lambda *a: jax.vjp(  # noqa: E731
            lambda *x: tuple(y.reshape(-1, *y.shape[2:])
                             for y in f(*x, chunk=CHUNK)),
            *a[:5])[1](tuple(a[5:])))
        ck = states(*flat)
        vjp = lambda f: jax.jit(lambda *a: (  # noqa: E731
            lambda o, pull: (o, *pull(a[6])))(
                *jax.vjp(lambda *x: f(*x, out_dtype=bf), *a[:6])))
        scan_fwd = jax.jit(lambda *o: scan_recurrence(*o, out_dtype=bf))
        ev_pf = traced(jax, prep_fwd, args)
        prep_fwd_ms = kernel_ms(ev_pf)
        ev_pb = traced(jax, prep_bwd, (*args, *cts))
        # a new jit and no kept trace (_common._bind keeps one a shape): the
        # kernel is traced again, without its inverse
        inverse = kernels._inverse_unit_lower
        kernels._inverse_unit_lower = lambda mats: mats
        _common._TRACED.clear()
        no_inverse = kernel_ms(traced(jax, jax.jit(
            lambda *a: kernels._prepare_forward(
                *kernels._prep_inputs(*a, CHUNK))), args))
        kernels._inverse_unit_lower = inverse
        _common._TRACED.clear()
        line = {"heads": heads, "key_heads": key_heads, "gate": opts.gate,
                "seg": kernels.SEG,
                "heads_a_step": kernels.HEADS,
                "prep_chunks_a_step": kernels.NCK,
                "prep_heads_a_step": kernels.PREP_HEADS,
                "kernel_ms": {
                    "fwd": kernel_ms(traced(jax, fwd, flat)),
                    "states": kernel_ms(traced(jax, states, flat)),
                    "bwd": kernel_ms(traced(
                        jax, bwd, (*flat, ck, do.reshape(flat[0].shape))))},
                "prep_ms": {
                    "fwd": prep_fwd_ms, "fwd_busy": busy_ms(ev_pf),
                    "fwd_no_inverse": no_inverse,
                    "inverse": prep_fwd_ms - no_inverse,
                    "bwd": kernel_ms(ev_pb), "bwd_busy": busy_ms(ev_pb),
                    "jnp_fwd": busy_ms(traced(jax, ref_fwd, args)),
                    "jnp_fwd_bwd": busy_ms(traced(
                        jax, pull(jnp_prepare), (*args, *cts)))},
                "scan_ms": {
                    "fwd": busy_ms(traced(jax, scan_fwd, ops)),
                    "fwd_bwd": busy_ms(traced(jax, vjp(scan_recurrence),
                                              (*ops, do)))},
                "err": dict(zip(
                    ("o", "du_v", "dw", "dq_in", "da_qk", "dk_out",
                     "dshrink"),
                    map(rel_err, vjp(kernels.kda_recurrence)(*ops, do),
                        vjp(scan_recurrence)(*ops, do)))),
                "prep_err": dict(zip(
                    ("u_v", "w", "q_in", "a_qk", "k_out", "shrink",
                     "dq", "dk", "dv", "dg", "dbeta"),
                    map(rel_err,
                        (*ops, *pull(kernel_prepare)(*args, *cts)),
                        (*ref_fwd(*args),
                         *pull(jnp_prepare)(*args, *cts)))))}
        if rep > 1:     # what PR 53 replaced: the repeat, then equal heads
            repeated = lambda q, k, *rest, chunk: kernel_prepare(  # noqa: E731
                repeat(q), repeat(k), *rest, chunk=chunk)
            line["prep_ms"]["repeated_fwd_busy"] = busy_ms(traced(
                jax, jax.jit(lambda *a: repeated(*a, chunk=CHUNK)), args))
            line["prep_ms"]["repeated_bwd_busy"] = busy_ms(traced(
                jax, pull(repeated), (*args, *cts)))
        del ops, flat, ck, do, cts
        if heads == 8:      # a head group's heads of the cell, alone
            wgt = jnp.asarray(np.random.default_rng(2).normal(
                size=args[2].shape), bf)
            grad = jax.jit(jax.grad(
                lambda *a: jnp.sum(
                    kda.chunk_kda(*a).astype(jnp.float32) * wgt),
                argnums=(0, 1, 2, 3, 4)))
            line["chunk_kda_ms"] = {"kernels": by_kernel(
                traced(jax, grad, args))}
        if heads == 32:     # a layer of a cell: Kimi's four head groups,
            #                     Qwen3-Next's one
            from deepspeed_tpu.models.transformer import _remat_policy
            layer = jax.checkpoint(
                lambda *a: kda.chunk_kda(
                    *a, head_groups=1 if head_gate else 4),
                policy=_remat_policy("nothing_saveable"))
            grad = jax.jit(jax.grad(
                lambda *a: 0.5 * jnp.sum(
                    layer(*a).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2, 3, 4)))
            line["layer_ms"] = by_kernel(traced(jax, grad, args))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
