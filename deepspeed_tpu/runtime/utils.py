"""Runtime utilities (reference: deepspeed/runtime/utils.py — ~1,100 LoC
of grad-norm/overflow/alignment helpers used across the engine and ZeRO
optimizers).

Functional ports over pytrees; all usable inside jit. The engine's
compiled step inlines the same math (engine.py _step_parts); these
standalone versions serve user code and the reference API surface."""

from __future__ import annotations

from typing import Any, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.memory import see_memory_usage  # noqa: F401  (reference re-export)

PyTree = Any


def get_global_norm_of_tensors(tensors: Iterable[jax.Array],
                               norm_type: float = 2.0) -> jax.Array:
    """reference: runtime/utils.py get_global_norm_of_tensors."""
    leaves = list(tensors)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    if norm_type == float("inf"):
        return jnp.max(jnp.stack([jnp.max(jnp.abs(t)) for t in leaves]))
    acc = sum(jnp.sum(jnp.abs(t.astype(jnp.float32)) ** norm_type)
              for t in leaves)
    return acc ** (1.0 / norm_type)


def get_grad_norm(tree: PyTree, norm_type: float = 2.0) -> jax.Array:
    return get_global_norm_of_tensors(jax.tree.leaves(tree), norm_type)


def clip_grad_norm_(tree: PyTree, max_norm: float,
                    norm_type: float = 2.0) -> tuple[PyTree, jax.Array]:
    """reference: runtime/utils.py clip_grad_norm_ — returns the clipped
    tree and the pre-clip global norm (functional: no in-place mutate)."""
    norm = get_grad_norm(tree, norm_type)
    coef = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda g: g * coef.astype(g.dtype), tree), norm


class CheckOverflow:
    """reference: runtime/utils.py CheckOverflow — scans grads for
    non-finite values (the fp16 skip-step trigger)."""

    def __init__(self, param_groups=None, mpu=None, zero_reduce_scatter=False,
                 deepspeed=None):
        self.params = param_groups

    @staticmethod
    def has_overflow(grads: PyTree) -> jax.Array:
        leaves = jax.tree.leaves(grads)
        if not leaves:
            return jnp.array(False)
        finite = [jnp.isfinite(g).all() for g in leaves]
        return ~jnp.stack(finite).all()

    @staticmethod
    def check_using_norm(norm_list: Sequence[jax.Array]) -> jax.Array:
        total = sum(jnp.asarray(n) for n in norm_list)
        return ~jnp.isfinite(total)

    check = has_overflow


def _has_inf_or_nan(x: jax.Array) -> jax.Array:
    """reference: stage_1_and_2.py:2022 _has_inf_or_nan."""
    return ~jnp.isfinite(x).all()


def align_dense_tensors(tensor_list: Sequence[jax.Array],
                        alignment: int) -> list[jax.Array]:
    """reference: runtime/utils.py align_dense_tensors — pad the LAST
    tensor so the flattened total is a multiple of ``alignment`` (flat
    buffers must tile evenly across ranks)."""
    total = sum(t.size for t in tensor_list)
    pad = (-total) % alignment
    if pad == 0 or not tensor_list:
        return list(tensor_list)
    out = list(tensor_list)
    out[-1] = jnp.pad(out[-1].reshape(-1), (0, pad))
    return out


def all_gather_dp_groups(tree: PyTree,
                         groups=("dp", "fsdp", "zps")) -> PyTree:
    """reference: runtime/utils.py all_gather_dp_groups — materialize the
    full tensors from data-parallel shards. Gathers ONLY over the data
    axes in ``groups``; other axes (tp etc.) keep their sharding. Outside
    jit this is a resharding device_put (XLA performs the all-gather)."""
    from jax.sharding import NamedSharding, PartitionSpec
    from ..parallel.mesh import get_topology

    mesh = get_topology().mesh
    drop = set(groups)

    def regather(x):
        spec = getattr(x.sharding, "spec", PartitionSpec())
        out = []
        for entry in spec:
            axes = entry if isinstance(entry, tuple) else (entry,)
            keep = tuple(a for a in axes
                         if a is not None and a not in drop)
            out.append(keep if len(keep) > 1
                       else (keep[0] if keep else None))
        return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*out)))

    return jax.tree.map(regather, tree)


def empty_cache() -> None:
    """reference calls get_accelerator().empty_cache(); XLA's allocator
    has no user-facing cache drop — provided for API parity."""


def noop_decorator(func):
    return func


def partition_uniform(num_items: int, num_parts: int):
    from .pipe.module import partition_uniform as _pu
    return _pu(num_items, num_parts)


def partition_balanced(weights, num_parts: int):
    from .pipe.module import partition_balanced as _pb
    return _pb(weights, num_parts)
