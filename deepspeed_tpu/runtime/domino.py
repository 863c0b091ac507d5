"""Domino — tensor parallelism with communication/compute overlap
(reference: runtime/domino/transformer.py DominoModule:19,
DominoTransformerLayer; the handle-dict + NoOper autograd fences :56-112).

The reference splits each batch into micro-chunks so the row-parallel
all-reduce of chunk *i* overlaps the attention/MLP compute of chunk
*i+1*, hand-scheduling CUDA streams around NCCL handles. On TPU the
same schedule is expressed structurally: the layer processes the batch
as ``n_micro`` chunks inside one compiled region, and each chunk's tp
all-reduce has no data dependence on the next chunk's GEMMs, leaving
XLA free to interleave them.

CLOSED as subsumed-by-XLA (r5; evidence: tools/domino_aot_evidence.py,
AOT v5e-2x4 compilation). At typical payloads (<32 MiB/chunk) XLA's
collective combiner MERGES the per-chunk all-reduces back into one per
reduction point — the compiled comm pattern is identical to the
unchunked layer, so Domino's restructuring adds nothing the compiler
doesn't already do. At >=32 MiB/chunk the per-chunk reduces survive and
sit between the chunk GEMM fusions in the instruction schedule, but the
textual TPU HLO exposes no async all-reduce-start/done pairs even with
the --xla_tpu_enable_async_collective_fusion flag family: whether those
reduces overlap compute is the TPU runtime's scheduling decision and
cannot be asserted at the HLO level. Chunking itself cost nothing in a
CPU run before the chip (chunked over unchunked step time ~=1; not
measured on the chip), so enabling Domino should never hurt —
but its overlap benefit should be attributed to XLA, not this module.

``DominoTransformerLayer`` here is a functional layer usable standalone
or as a template: given attention/mlp callables whose outputs need a tp
all-reduce (row-parallel linears), it runs them chunk-wise.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

PyTree = Any


class DominoModule:
    """Marker base (reference: domino/transformer.py:19)."""


def _chunks(x: jax.Array, n: int):
    return jnp.split(x, n, axis=0)


class DominoTransformerLayer(DominoModule):
    """reference: DominoTransformerLayer — batch-dim micro-chunking.

    attn_fn/mlp_fn: (params, x) -> partial output whose tp reduction is
    still pending; reduce_fn performs the row-parallel reduction (psum
    over "tp" inside shard_map, or a sharding-constraint under jit).
    """

    def __init__(self, attn_fn: Callable, mlp_fn: Callable,
                 reduce_fn: Callable | None = None, n_micro: int = 2):
        self.attn_fn = attn_fn
        self.mlp_fn = mlp_fn
        self.reduce_fn = reduce_fn or (lambda x: x)
        self.n_micro = n_micro

    def __call__(self, params: PyTree, x: jax.Array) -> jax.Array:
        n = self.n_micro if x.shape[0] % self.n_micro == 0 else 1
        outs = []
        for xc in _chunks(x, n):
            # chunk i's reduce is independent of chunk i+1's compute;
            # XLA overlaps them (the role of Domino's handle waits)
            h = xc + self.reduce_fn(self.attn_fn(params, xc))
            outs.append(h + self.reduce_fn(self.mlp_fn(params, h)))
        return jnp.concatenate(outs, axis=0)
