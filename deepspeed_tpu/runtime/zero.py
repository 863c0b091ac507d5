"""ZeRO as a sharding plan (reference: deepspeed/runtime/zero/).

The reference implements ZeRO with flat buffers, grad hooks, and explicit
collectives (stage_1_and_2.py, stage3.py, partition_parameters.py —
~8k LoC of bookkeeping). On TPU the same memory math falls out of *which
pytrees carry the fsdp mesh axis*:

  stage 0: nothing sharded over fsdp (plain DP; grads pmean'd by XLA)
  stage 1: optimizer state + fp32 master sharded       (osP)
  stage 2: + gradients sharded (XLA emits reduce-scatter instead of
            all-reduce at the grad boundary)                (os+gP)
  stage 3: + parameters sharded (XLA all-gathers each layer slice inside
            the scan-over-layers, overlapping gather with compute — the
            static-schedule version of the prefetch coordinator)  (os+g+pP)

Stage 3's promise (activations stay on the chip that owns their sequences,
each layer's weights are gathered to them and its gradients reduce-scattered
from them) holds because the engine pins the layer scan's carry to
``[B(batch axes), S(sp), D]`` on every mesh of more than one device
(``engine._configure_sequence_parallel`` -> ``DecoderLM._final_hidden``).
The plan shards the FFN weights on the FFN dimension (``overlay_axis``
takes the largest divisible one), and with the carry free GSPMD moved the
activations to the weights instead: on ``fsdp=4`` the TPU compile ran the
MLP's backward tensor-parallel, five 224 MiB all-to-alls a layer (PR 28).

The planner computes PartitionSpec trees per stage on top of the model's
tensor-parallel rules, so ZeRO composes with TP/SP/PP exactly like the
reference's hybrid topologies (§2.3).
"""

from __future__ import annotations

from typing import Any

import numpy as np
from jax.sharding import Mesh, PartitionSpec

from ..parallel.partition import (filter_spec_for_mesh, match_rules,
                                  named_shardings)

PyTree = Any


def overlay_axis(spec_tree: PyTree, tree: PyTree, mesh: Mesh,
                 axis: str | tuple[str, ...] = "fsdp",
                 min_size: int = 2 ** 11) -> PyTree:
    """Add `axis` sharding (a mesh axis name or tuple of names, e.g.
    ``("fsdp", "zps")`` for hpZ-split meshes) to each leaf's largest
    still-unsharded divisible dim (ZeRO's 1/N partitioning; composes with
    existing tp dims)."""
    import jax

    new_axes = (axis,) if isinstance(axis, str) else tuple(axis)
    new_axes = tuple(a for a in new_axes if mesh.shape.get(a, 1) > 1)
    n = int(np.prod([mesh.shape[a] for a in new_axes])) if new_axes else 1

    def fix(spec, leaf):
        shape = np.shape(leaf)
        if n <= 1 or int(np.prod(shape)) < min_size:
            return spec
        flat_axes = [a for e in spec if e is not None
                     for a in (e if isinstance(e, tuple) else (e,))]
        if any(a in flat_axes for a in new_axes):
            return spec
        spec_l = list(spec) + [None] * (len(shape) - len(spec))
        candidates = [d for d in range(len(shape))
                      if spec_l[d] is None and shape[d] % n == 0]
        if not candidates:
            return spec
        best = max(candidates, key=lambda d: shape[d])
        spec_l[best] = new_axes if len(new_axes) > 1 else new_axes[0]
        return PartitionSpec(*spec_l)

    return jax.tree.map(fix, spec_tree, tree,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def pin_pipeline_axis(spec_tree: PyTree, tree: PyTree, mesh: Mesh,
                      path_regex: str = r"(^|/)layers/",
                      axis: str = "pp") -> PyTree:
    """Put the ``pp`` axis on dim 0 of per-layer stacks (``[L, ...]``), so
    the pipeline engine's ``[pp, L/pp, ...]`` reshape is shard-local.
    Applies to any tree whose leaf paths embed the layer path (params,
    grads, optimizer moments)."""
    import re

    import jax

    from ..parallel.partition import _path_str

    n = mesh.shape.get(axis, 1)
    if n <= 1:
        return spec_tree

    def fix(path, spec, leaf):
        shape = np.shape(leaf)
        if (not re.search(path_regex, _path_str(path))
                or len(shape) == 0 or shape[0] % n != 0):
            return spec
        spec_l = list(spec) + [None] * (len(shape) - len(spec))
        if spec_l[0] is not None:
            raise ValueError(
                f"layer-stack dim 0 of {_path_str(path)} already sharded by "
                f"{spec_l[0]}; cannot pin pipeline axis")
        spec_l[0] = axis
        return PartitionSpec(*spec_l)

    return jax.tree_util.tree_map_with_path(
        fix, spec_tree, tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


class ZeroShardingPlan:
    """Spec trees for params / grads / master+optimizer state.

    ``rules`` are the model's TP partition rules; they are also applied to
    the optimizer-state tree (optax moment paths embed the parameter path,
    so the same regexes match). When the mesh has a pipeline axis, layer
    stacks are pinned to it first (dim 0), then ZeRO overlays fsdp on the
    remaining dims.

    **ZeRO++ hpZ** (``hpz=True``, reference ``partition_parameters.py:1664``
    ``_partition_param_sec`` + ``zero/config.py:41``): the mesh's sharded-DP
    dimension is split fsdp×zps; gradients/master/optimizer state shard over
    both (full 1/N memory), while *parameters* shard only over the inner
    ``zps`` subgroup and replicate across ``fsdp`` — forward/backward weight
    all-gathers ride the fast intra-group links, the reference's secondary
    intra-node partition.

    **MiCS** (``mics=True``, reference ``zero/mics.py:64 MiCS_Init``):
    everything — params, grads, optimizer state — shards only within the
    ``zps`` sub-cluster and replicates across ``fsdp``. Gradients then need
    summing across the replica groups: because grad specs carry only
    ``zps``, XLA emits reduce-scatter within the sub-cluster plus all-reduce
    across clusters — exactly MiCS's hierarchical gradient comm
    (``mics.py:362 MiCS_Optimizer``).
    """

    def __init__(self, stage: int, mesh: Mesh, rules, params: PyTree,
                 offload_optimizer: bool = False, pipeline: bool = False,
                 hpz: bool = False, mics: bool = False):
        if stage not in (0, 1, 2, 3):
            raise ValueError(f"ZeRO stage must be 0-3, got {stage}")
        self.stage = stage
        self.mesh = mesh
        self.rules = rules
        self.offload_optimizer = offload_optimizer
        self.pipeline = pipeline and mesh.shape.get("pp", 1) > 1
        has_zps = mesh.shape.get("zps", 1) > 1
        if (hpz or mics) and not has_zps:
            raise ValueError(
                "hpZ/MiCS need the mesh's zps axis > 1 (set "
                "zero_hpz_partition_size / mics_shard_size in the config)")
        self.hpz = hpz
        self.mics = mics
        # full sharded-DP extent vs the inner subgroup only
        full = ("fsdp", "zps") if has_zps else "fsdp"
        inner = "zps" if has_zps else "fsdp"
        param_axes = inner if (hpz or mics) else full
        state_axes = inner if mics else full

        base = self._base_specs(params)
        self.param_specs = (overlay_axis(base, params, mesh, axis=param_axes)
                            if stage >= 3 else base)
        self.grad_specs = (overlay_axis(base, params, mesh, axis=state_axes)
                           if stage >= 2 else self.param_specs)
        self.master_specs = (overlay_axis(base, params, mesh, axis=state_axes)
                             if stage >= 1 else self.param_specs)
        self._state_axes = state_axes

    def _base_specs(self, tree: PyTree) -> PyTree:
        base = filter_spec_for_mesh(match_rules(self.rules, tree), self.mesh, tree)
        if self.pipeline:
            base = pin_pipeline_axis(base, tree, self.mesh)
        return base

    def spec_for_tree(self, tree: PyTree, sharded: bool) -> PyTree:
        """Specs for an arbitrary tree (e.g. optax state) whose leaf paths
        embed parameter paths."""
        base = self._base_specs(tree)
        return (overlay_axis(base, tree, self.mesh, axis=self._state_axes)
                if sharded else base)

    def opt_specs(self, opt_state: PyTree) -> PyTree:
        return self.spec_for_tree(opt_state, sharded=self.stage >= 1)

    def shardings(self, spec_tree: PyTree, memory_kind: str | None = None):
        if memory_kind is None:
            return named_shardings(self.mesh, spec_tree)
        import jax
        from jax.sharding import NamedSharding
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s, memory_kind=memory_kind),
            spec_tree, is_leaf=lambda x: isinstance(x, PartitionSpec))
