"""Ulysses-style sequence parallelism (reference: deepspeed/sequence/layer.py).

``DistributedAttention`` wraps any local attention: the sequence-sharded
q/k/v ``[B, S/P, H, D]`` are all-to-all'd into head-sharded, full-sequence
``[B, S, H/P, D]`` (reference ``_SeqAllToAll``/``single_all_to_all``,
layer.py:153,216), local attention runs, and the output is all-to-all'd
back. On TPU the all-to-all is a single XLA collective along the ``sp``
mesh axis inside ``shard_map`` — comm volume O(S/P) per device, riding ICI.

Composes with tensor parallelism: heads may additionally be sharded over
``tp`` (in/out specs carry both axes); the all-to-all only trades the sp
axis. Uneven head counts (reference layer.py:43): GQA kv-heads that don't
divide sp are replicated up front, and q-head counts not divisible by sp
are zero-padded to the next sp multiple and sliced back after the reverse
all-to-all.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.layers import dot_product_attention
from ..utils.jax_compat import shard_map


def _seq_all_to_all(x, axis_name: str, *, scatter_idx: int, gather_idx: int):
    """single_all_to_all equivalent: scatter `scatter_idx` dim, gather
    `gather_idx` dim along the sp axis (reference layer.py:153)."""
    return lax.all_to_all(x, axis_name, split_axis=scatter_idx,
                          concat_axis=gather_idx, tiled=True)


def _shard_map_sp(body, mesh, sp_axis, n_args):
    """Partial-manual shard_map over just the sp axis. Batch/tp/fsdp
    sharding stays under GSPMD, which also makes the wrapper nestable
    inside other manual regions (e.g. the compiled pipeline): when an
    abstract mesh is already active (inside jit), it is used instead of the
    concrete one so nested shard_maps agree."""
    active = jax.sharding.get_abstract_mesh()
    use = active if active.shape else mesh
    spec = P(*([None] * 1), sp_axis)  # [B, S(sp), H, D]: dim1 manual
    specs = tuple([spec] * n_args)
    return shard_map(body, mesh=use, axis_names={sp_axis},
                     in_specs=specs, out_specs=spec, check_vma=False)


class DistributedAttention:
    """reference: sequence/layer.py:271 DistributedAttention.

    Args mirror the reference: a local attention callable, the sequence
    "process group" (mesh + sp axis name), and the scatter/gather dims
    (default: scatter heads=2, gather seq=1 on [B, S, H, D]).
    """

    def __init__(self, local_attention: Callable | None = None,
                 mesh: Mesh | None = None, sp_axis: str = "sp",
                 scatter_idx: int = 2, gather_idx: int = 1,
                 batch_axes=("dp", "fsdp"), tp_axis: str = "tp"):
        self.local_attn = local_attention or dot_product_attention
        self.mesh = mesh
        self.sp_axis = sp_axis
        self.scatter_idx = scatter_idx
        self.gather_idx = gather_idx
        self.batch_axes = batch_axes
        self.tp_axis = tp_axis

    def __call__(self, q, k, v, *, causal: bool = True, **kw):
        mesh = self.mesh
        sp = mesh.shape.get(self.sp_axis, 1)
        if sp <= 1:
            return self.local_attn(q, k, v, causal=causal, **kw)

        nq, nkv = q.shape[2], k.shape[2]
        tp = mesh.shape.get(self.tp_axis, 1)
        if nq % tp != 0:
            raise ValueError(
                f"DistributedAttention: q heads ({nq}) must be divisible "
                f"by the tensor-parallel degree ({tp}); the uneven-head "
                f"padding only supports head counts uneven in sp")
        local_q = nq // tp
        if nkv != nq:
            if nq % nkv != 0:
                raise ValueError(
                    f"DistributedAttention: GQA needs q heads ({nq}) to "
                    f"be a multiple of kv heads ({nkv})")
            if nkv % tp != 0 or (nkv // tp) % sp != 0:
                # kv heads don't shard evenly over tp*sp: replicate kv
                # up to the q head count (reference supports uneven head
                # counts; full replication is the TPU-simple equivalent
                # for GQA, and nq is already tp-divisible)
                k = jnp.repeat(k, nq // nkv, axis=2)
                v = jnp.repeat(v, nq // nkv, axis=2)
        pad = 0
        if local_q % sp != 0:
            # uneven q heads (reference layer.py:43 supports head counts
            # not divisible by the SP degree): pad zero heads up to the
            # next sp multiple per tp shard; the all-to-alls stay even
            # and the pad heads are sliced off after the reverse
            # all-to-all (head order is preserved across the round trip,
            # so the pad stays at the tail). Overhead = pad/H compute.
            if k.shape[2] != nq:
                k = jnp.repeat(k, nq // k.shape[2], axis=2)
                v = jnp.repeat(v, nq // v.shape[2], axis=2)
            target = -(-local_q // sp) * sp * tp
            pad = target - nq
            widths = [(0, 0), (0, 0), (0, pad), (0, 0)]
            q = jnp.pad(q, widths)
            k = jnp.pad(k, widths)
            v = jnp.pad(v, widths)

        def body(q, k, v):
            # local in: [B, S/P, H_local, D]; scatter heads, gather seq
            q = _seq_all_to_all(q, self.sp_axis,
                                scatter_idx=self.scatter_idx,
                                gather_idx=self.gather_idx)
            k = _seq_all_to_all(k, self.sp_axis,
                                scatter_idx=self.scatter_idx,
                                gather_idx=self.gather_idx)
            v = _seq_all_to_all(v, self.sp_axis,
                                scatter_idx=self.scatter_idx,
                                gather_idx=self.gather_idx)
            o = self.local_attn(q, k, v, causal=causal, **kw)
            # back: scatter seq, gather heads
            return _seq_all_to_all(o, self.sp_axis,
                                   scatter_idx=self.gather_idx,
                                   gather_idx=self.scatter_idx)

        out = _shard_map_sp(body, mesh, self.sp_axis, 3)(q, k, v)
        return out[:, :, :nq] if pad else out


def ulysses_attention(mesh: Mesh, local_attention: Callable | None = None,
                      **kw) -> Callable:
    """Convenience: an attn_fn for DecoderLM.apply(..., attn_fn=...)."""
    da = DistributedAttention(local_attention, mesh, **kw)
    return lambda q, k, v, causal=True: da(q, k, v, causal=causal)
