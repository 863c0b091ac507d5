"""Probe: does XLA stream scan-over-layers weights from pinned_host?

The ZeRO-Infinity-class single-chip design (runtime/infinity.py) rests on
one XLA behavior: a `lax.scan` whose xs live in host memory should fetch
one layer slice per step (H2D DMA pipelined against compute) instead of
materializing the whole stacked array in HBM. This probe measures HBM
high-water directly via device memory_stats to confirm.

Run on the real chip: python examples/probe_host_streaming.py
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()
dev = jax.devices()[0]
mesh = Mesh([dev], ("x",))
host = NamedSharding(mesh, P(), memory_kind="pinned_host")
hbm = NamedSharding(mesh, P())

L, D, B = 64, 2048, 8          # 64 layers x (2048x2048 + 2048x2048) bf16
# stacked "weights": L * 2 * D*D * 2B = 2.1 GiB — would be visible in HBM
w1 = jax.device_put(
    jax.random.normal(jax.random.PRNGKey(0), (L, D, D), jnp.bfloat16)
    * (1.0 / D ** 0.5), host)
w2 = jax.device_put(
    jax.random.normal(jax.random.PRNGKey(1), (L, D, D), jnp.bfloat16)
    * (1.0 / D ** 0.5), host)
x = jax.device_put(
    jax.random.normal(jax.random.PRNGKey(2), (B, D), jnp.bfloat16), hbm)


def stats(tag):
    s = dev.memory_stats()
    peak = s.get("peak_bytes_in_use", 0) / 2 ** 30
    cur = s.get("bytes_in_use", 0) / 2 ** 30
    print(f"{tag}: peak={peak:.2f} GiB in_use={cur:.2f} GiB")
    return peak


@jax.jit
def fwd(x, w1, w2):
    def body(h, ws):
        a, b = ws
        h = jnp.tanh(h @ a) @ b + h
        return h, ()
    h, _ = jax.lax.scan(body, x, (w1, w2))
    return jnp.sum(h.astype(jnp.float32))


@jax.jit
def fwd_bwd(x, w1, w2):
    def loss(w1, w2):
        def body(h, ws):
            a, b = ws
            h = jnp.tanh(h @ a) @ b + h
            return h, ()
        h, _ = jax.lax.scan(jax.checkpoint(body), x, (w1, w2))
        return jnp.sum(h.astype(jnp.float32))
    l, grads = jax.value_and_grad(loss, argnums=(0, 1))(w1, w2)
    # grads written back to host memory: the D2H half of the stream
    return l, jax.tree.map(
        lambda g: jax.device_put(g, host), grads)


base = stats("baseline")
out = fwd(x, w1, w2)
print("fwd:", float(out))
p1 = stats("after fwd")
l, g = fwd_bwd(x, w1, w2)
print("fwd_bwd:", float(l))
p2 = stats("after fwd_bwd")
t0 = time.perf_counter()
for _ in range(5):
    l, g = fwd_bwd(x, w1, w2)
float(l)
dt = (time.perf_counter() - t0) / 5
gb = (2 * L * D * D * 2) / 2 ** 30
print(f"fwd_bwd step: {dt*1e3:.1f} ms "
      f"(weights {gb:.2f} GiB H2D + grads {gb:.2f} GiB D2H per step -> "
      f"{2*gb/dt:.1f} GiB/s effective)")
full = 2 * L * D * D * 2 / 2 ** 30
print(f"stacked weights total: {full:.2f} GiB; HBM peak grew "
      f"{max(p1, p2) - base:.2f} GiB -> "
      f"{'STREAMED (per-layer)' if max(p1, p2) - base < full * 0.6 else 'MATERIALIZED (full fetch)'}")
