"""Domino overlap: HLO-level evidence via AOT TPU compilation
(VERDICT r4 #10; reference: runtime/domino/transformer.py:19).

AOT-compiles the chunked tensor-parallel layer for a v5e-2x4 topology
(no hardware needed) and reports what the TPU compiler actually does
with the per-chunk all-reduces, with and without the async-collective
fusion flags. Findings this tool reproduces (r5):

- typical payloads (<32 MiB/chunk): XLA MERGES the per-chunk
  all-reduces into one per reduction point — the compiled comm pattern
  is identical to the unchunked layer, i.e. Domino's restructuring is
  SUBSUMED BY XLA's collective combiner;
- large payloads (>=32 MiB/chunk): per-chunk all-reduces survive and
  sit between the chunk GEMM fusions in the instruction schedule, but
  the textual TPU HLO exposes NO async all-reduce-start/done pairs
  (even with --xla_tpu_enable_async_collective_fusion*), so
  compute/comm overlap cannot be proven at the HLO level on this
  backend — it is the TPU runtime's decision.

Prints one JSON line with the all-reduce counts per configuration.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from deepspeed_tpu.utils.jax_compat import shard_map  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from deepspeed_tpu.runtime.domino import DominoTransformerLayer  # noqa: E402
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

ASYNC_FLAGS = {
    "xla_tpu_enable_async_collective_fusion": "true",
    "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": "true",
}


def compile_counts(rows: int, n_micro: int = 4, d: int = 4096,
                   opts: dict | None = None) -> dict:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x4")
    mesh = Mesh(np.array(topo.devices).reshape(8), ("tp",))

    def attn_fn(p, xc):   # col-parallel then row-parallel: reduce pending
        return (xc @ p["a_in"]) @ p["a_out"]

    def mlp_fn(p, xc):
        return (xc @ p["m_in"]) @ p["m_out"]

    layer = DominoTransformerLayer(attn_fn, mlp_fn,
                                   lambda x: jax.lax.psum(x, "tp"),
                                   n_micro=n_micro)

    def step(p, x):
        return shard_map(
            lambda p, x: layer(p, x), mesh=mesh,
            in_specs=({"a_in": P(None, "tp"), "a_out": P("tp", None),
                       "m_in": P(None, "tp"), "m_out": P("tp", None)},
                      P()),
            out_specs=P(), check_vma=False)(p, x)

    pa = {k: jax.ShapeDtypeStruct((d, d), jnp.bfloat16)
          for k in ("a_in", "a_out", "m_in", "m_out")}
    xa = jax.ShapeDtypeStruct((rows, d), jnp.bfloat16)
    lowered = jax.jit(step).lower(pa, xa)
    compiled = (lowered.compile(compiler_options=opts) if opts
                else lowered.compile())
    hlo = compiled.as_text()
    chunk_mib = rows // n_micro * d * 2 / 2 ** 20
    return {
        "chunk_payload_mib": round(chunk_mib, 1),
        "logical_reduces": 2 * n_micro,
        "all_reduce": hlo.count("all-reduce("),
        "async_start": hlo.count("all-reduce-start"),
        "async_done": hlo.count("all-reduce-done"),
    }


def main() -> dict:
    enable_compile_cache()
    small = compile_counts(rows=4096)
    big = compile_counts(rows=32768)
    big_async = compile_counts(rows=32768, opts=ASYNC_FLAGS)
    return {
        "metric": "domino_aot_hlo_evidence",
        "small_payload": small,
        "big_payload": big,
        "big_payload_async_flags": big_async,
        "merged_at_small": small["all_reduce"] < small["logical_reduces"],
        "chunked_at_big": big["all_reduce"] == big["logical_reduces"],
        "async_pairs_exposed": big_async["async_start"] > 0,
        "conclusion": (
            "subsumed-by-XLA at typical sizes (collective combiner "
            "restores the unchunked comm pattern); per-chunk reduces "
            "survive only at >=32MiB payloads and the TPU HLO never "
            "exposes async start/done pairs, so overlap is the "
            "runtime's call — Domino chunking is free but its overlap "
            "claim is closed as unverifiable-by-construction here"),
    }


if __name__ == "__main__":
    print(json.dumps(main()))
