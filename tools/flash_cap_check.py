"""What the flash kernels' residency cap admits, compiled and run on the
chip (PR 64): forward and backward at the longest whole-block row at or
under ``_resident_max_seq`` for a key of 64, 128, 192 (value 128) and 256,
two (batch x head) rows each, bf16; then the first row of whole blocks the
rule this replaced admitted and Mosaic refuses (65536 x 64), which has to
be cut in spans now. One JSON line; exits 1 where a cap does not compile.

    chiprun -- python3 tools/flash_cap_check.py
"""

import importlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
F = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")


def run(s: int, d: int, dv: int) -> dict:
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(x, (2, s, d), jnp.bfloat16) for x in keys[:2])
    v, do = (jax.random.normal(x, (2, s, dv), jnp.bfloat16) for x in keys[2:])
    sc = d ** -0.5
    out = {"s": s, "d": d, "dv": dv, "cap": F._resident_max_seq(d, dv)}
    try:
        t0 = time.perf_counter()
        o, lse = jax.jit(lambda q, k, v: F._flash_fwd(
            q, k, v, causal=True, sc=sc))(q, k, v)
        grads = jax.jit(lambda q, k, v, o, lse, do: F._flash_bwd(
            q, k, v, o, lse, do, causal=True, sc=sc))(q, k, v, o, lse, do)
        out["finite"] = bool(all(jnp.all(jnp.isfinite(g.astype(jnp.float32)))
                                 for g in (o, *grads)))
        out["seconds"] = round(time.perf_counter() - t0, 1)
    except Exception as e:      # Mosaic's refusal is the finding
        out["refused"] = str(e)[:300]
    return out


if __name__ == "__main__":
    lines = [run(F._resident_max_seq(d, dv) // 512 * 512, d, dv)
             for d, dv in ((64, 64), (128, 128), (192, 128), (256, 256))]
    past = run(65536, 64, 64)
    ok = all(line.get("finite") for line in lines) and (
        F.segments(65536, 64) > 1)
    print(json.dumps({"ok": ok, "at_the_caps": lines,
                      "the_old_rule_admitted": past,
                      "device": jax.devices()[0].device_kind}), flush=True)
    sys.exit(0 if ok else 1)
