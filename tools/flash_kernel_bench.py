"""The two flash kernels alone, on the chip: time and bits of this
checkout's ``ops/pallas/flash_attention.py`` against copies of that file.

    chiprun -- python tools/flash_kernel_bench.py [COPY.py ...] [S:WINDOW ...]

Sizes a change to the kernels before a cell is run (PR 30: the ceiling
run, the ablations, the bit-equality proof). Each COPY.py is another
version of the kernel file (the parent's, one with a block deleted);
the first variant is this checkout's and the others are compared with it
bit for bit (o, lse, dq, dk, dv). Shapes are the benchmark cells' per-chip
ones (32 q / 8 kv heads of 128) at each ``S:WINDOW`` (default 8192:4096;
``none`` for full causal; ``8192:512`` and ``16384:1024`` are the Laguna
and the Mellum cells' window layers, whose sweep is the band's: time a
head is what carries over to their 72 / 32 heads). Kernel time is the mean duration of the
``tpu_custom_call`` events of a profiler trace of 10 calls; it is a
device number and exists only on a TPU. Not the yardstick: what a user
feels is ``benchmark/run.py``.
"""

from __future__ import annotations

import glob
import hashlib
import importlib
import importlib.util
import json
import os
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HQ, HKV, D, CALLS = 32, 8, 128, 10


def load(path: str | None):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    checkout = importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention")
    if path is None:
        return checkout
    # a name inside the package, so that the copy's relative imports hold
    spec = importlib.util.spec_from_file_location(
        "deepspeed_tpu.ops.pallas._flash_copy_"
        + hashlib.sha1(path.encode()).hexdigest()[:8], path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def kernel_ms(jax, fn, args, trace_dir: str) -> float:
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    newest = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    durations = [e.duration_ns
                 for plane in jax.profiler.ProfileData.from_file(newest).planes
                 if plane.name.startswith("/device:TPU:0")
                 for line in plane.lines if line.name == "XLA Ops"
                 for e in line.events if "tpu_custom_call" in e.name]
    return 1e-6 * sum(durations) / max(len(durations), 1)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    copies = [a for a in argv if ":" not in a]
    shapes = [a.split(":") for a in argv if ":" in a] or [["8192", "4096"]]
    trace_root = os.path.join(ROOT, ".bench_trace", "flash_kernel_bench")
    for s_, w_ in shapes:
        s, window = int(s_), (None if w_ == "none" else int(w_))
        ks = jax.random.split(jax.random.PRNGKey(30), 4)
        q, do = (jax.random.normal(k, (HQ, s, D), jnp.bfloat16)
                 for k in (ks[0], ks[3]))
        k, v = (jax.random.normal(k, (HKV, s, D), jnp.bfloat16)
                for k in ks[1:3])
        kw = dict(causal=True, sc=1.0 / np.sqrt(D), window=window,
                  rep=HQ // HKV)
        first = None
        for path in [None, *copies]:
            m = load(path)
            fwd = jax.jit(lambda q, k, v, m=m: m._flash_fwd(q, k, v, **kw))
            bwd = jax.jit(lambda q, k, v, o, lse, do, m=m: m._flash_bwd(
                q, k, v, o, lse, do, **kw))
            o, lse = fwd(q, k, v)
            outs = [np.asarray(x)
                    for x in (o, lse, *bwd(q, k, v, o, lse, do))]
            first = first or outs
            print(json.dumps({
                "kernels": path or "this checkout", "s": s, "window": window,
                "fwd_ms": kernel_ms(jax, fwd, (q, k, v),
                                    os.path.join(trace_root, "fwd")),
                "bwd_ms": kernel_ms(jax, bwd, (q, k, v, o, lse, do),
                                    os.path.join(trace_root, "bwd")),
                "bit_equal_to_checkout": [bool(np.array_equal(a, b))
                                          for a, b in zip(outs, first)],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
