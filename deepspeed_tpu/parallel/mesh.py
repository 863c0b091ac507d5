"""Device-mesh topology for the TPU-native runtime.

This is the substrate every parallelism strategy rides on. Where the
reference builds explicit process groups (``deepspeed/utils/groups.py``,
``runtime/pipe/topology.py``), the TPU build names mesh axes and lets XLA
insert collectives along them. The canonical axes are:

  - ``dp``   : pure data parallelism (replicated params)
  - ``fsdp`` : ZeRO-style sharded data parallelism (params/grads/opt state
               sharded; the reference's ZeRO-1/2/3 over the DP group)
  - ``tp``   : tensor (model) parallelism
  - ``sp``   : sequence parallelism (Ulysses / ring attention)
  - ``pp``   : pipeline parallelism
  - ``ep``   : expert parallelism for MoE

Reference: ``deepspeed/runtime/pipe/topology.py`` (ProcessTopology axes),
``deepspeed/utils/groups.py:68-531`` (group factories). Here a "process
group" is simply a mesh axis name (or tuple of names).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis order: outermost (slowest-varying, crosses DCN first) to
# innermost (fastest-varying, rides ICI). Pipeline crosses slices cheaply
# because p2p volume is small; fsdp/tp want the fastest links.
#
# ``zps`` (ZeRO param-shard subgroup) subdivides the sharded-DP dimension
# for ZeRO++ hpZ (`zero_hpz_partition_size`, reference zero/config.py:41)
# and MiCS sub-cluster sharding (reference zero/mics.py:64): total sharded
# DP degree = fsdp × zps, with zps innermost so the param all-gathers it
# carries ride the fastest ICI links while fsdp spans nodes/slices.
AXIS_ORDER = ("pp", "dp", "fsdp", "zps", "ep", "sp", "tp")

# Axes along which *data* (the batch) is split.
BATCH_AXES = ("dp", "fsdp", "zps")


def active_mesh(mesh):
    """``(mesh, free axes)`` as seen from where the caller is traced:
    inside a ``shard_map`` the active abstract mesh, whose manual axes a
    nested spec may not name (the compiled pipeline's ``pp`` map, ZeRO++'s
    fully manual gradient map), else ``mesh`` itself with every axis
    free."""
    active = jax.sharding.get_abstract_mesh()
    use = active if active.shape else mesh
    return use, tuple(a for a in use.axis_names
                      if a not in use.manual_axes)


def per_batch_shard(fn, act_sharding: NamedSharding, batched):
    """``fn`` per shard of the batch under a shard_map, for a function that
    holds Mosaic kernels (GSPMD cannot partition their calls; as
    ``ops.pallas.flash_attention.sharded_flash_attention``, which see).
    ``act_sharding`` is the layout the model's activations are pinned to,
    ``[B(batch axes), S, D]``; ``batched[i]`` says whether argument ``i``
    (and the result, like argument 0) leads with the batch. The batch is
    split over the batch axes where they divide it, every other axis and
    argument is replicated: exact for a function whose batch rows are
    independent and whose other dimensions ``act_sharding`` never splits.
    Keywords reach ``fn`` as they are."""
    import functools

    from ..utils.jax_compat import shard_map

    entry = act_sharding.spec[0] if len(act_sharding.spec) else None
    batch_axes = (entry,) if isinstance(entry, str) else tuple(entry or ())

    def per_shard(*args, **kw):
        use, free = active_mesh(act_sharding.mesh)
        b_ax = tuple(a for a in batch_axes
                     if a in free and use.shape[a] > 1)
        if args[0].shape[0] % math.prod(use.shape[a] for a in b_ax):
            b_ax = ()       # uneven batch: replicate, still exact
        rows = lambda x: PartitionSpec(  # noqa: E731
            b_ax or None, *[None] * (x.ndim - 1))
        return shard_map(
            functools.partial(fn, **kw), mesh=use, axis_names=set(free),
            in_specs=tuple(rows(x) if lead else PartitionSpec()
                           for x, lead in zip(args, batched, strict=True)),
            out_specs=rows(args[0]), check_vma=False)(*args)

    return per_shard


def constrain_free(x: jax.Array, sharding: NamedSharding) -> jax.Array:
    """``with_sharding_constraint(x, sharding)`` that holds wherever the
    model is traced. Axes that are manual in an enclosing region are
    dropped from the spec (a ``NamedSharding`` naming them does not lower
    there), and so are axes of extent 1. A dimension its remaining axes
    do not divide (an uneven batch) leaves ``x`` unconstrained, as the
    flash wrapper leaves such a batch replicated; so does a spec with
    nothing left to say."""
    use, free = active_mesh(sharding.mesh)
    spec = []
    for dim, entry in zip(x.shape, sharding.spec):
        names = (entry,) if isinstance(entry, str) else tuple(entry or ())
        keep = tuple(a for a in names if a in free and use.shape[a] > 1)
        if dim % math.prod(use.shape[a] for a in keep):
            return x
        spec.append(keep or None)
    if not any(spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(use, PartitionSpec(*spec)))


def build_device_array(axis_order: Sequence[str], shape: Sequence[int],
                       dcn_sizes: dict, devices: Sequence) -> np.ndarray:
    """Physical-topology-aware device placement (reference:
    runtime/pipe/topology.py:1 ProcessTopology — rank order encodes
    which links each axis rides; SURVEY §7.1 "ICI vs DCN aware").

    - multi-slice (``dcn_sizes`` gives per-axis DCN degrees):
      ``mesh_utils.create_hybrid_device_mesh`` puts those axes across
      slice boundaries (grouping devices by ``slice_index``) and every
      other axis on intra-slice ICI;
    - single-slice TPU: ``mesh_utils.create_device_mesh`` maps the
      logical axes onto the physical torus coordinates (a raw
      ``reshape`` need not — e.g. on a v5p-128 it can put ``tp`` on
      non-adjacent chips);
    - CPU/virtual devices (tests) and single-device: plain reshape —
      there is no physical topology to honor.
    """
    unknown = set(dcn_sizes) - set(axis_order)
    if unknown:
        raise ValueError(f"dcn axes {sorted(unknown)} are not mesh axes")
    if dcn_sizes:
        dcn_shape, ici_shape = [], []
        for a, s in zip(axis_order, shape):
            d = int(dcn_sizes.get(a, 1))
            if s % d != 0:
                raise ValueError(
                    f"mesh axis {a}={s} not divisible by its dcn degree {d}")
            dcn_shape.append(d)
            ici_shape.append(s // d)
        if hasattr(devices[0], "slice_index"):
            from jax.experimental import mesh_utils
            return mesh_utils.create_hybrid_device_mesh(
                tuple(ici_shape), tuple(dcn_shape), devices=devices,
                allow_split_physical_axes=True)
        if getattr(devices[0], "platform", None) == "tpu":
            import warnings
            warnings.warn(
                "mesh.dcn was configured but these TPU devices report no "
                "slice_index (single-slice runtime?) — falling back to "
                "sequential-block placement; DCN axes will NOT span "
                "slices and torus-aware placement is skipped")
        # CPU/virtual devices carry no slice_index: emulate the hybrid
        # layout (each axis's dcn factor outermost over contiguous
        # "slices" of sequential devices) so dcn configs stay testable
        # on the virtual mesh
        arr = np.asarray(devices).reshape(tuple(dcn_shape) + tuple(ici_shape))
        k = len(ici_shape)
        perm: list[int] = []
        for i in range(k):
            perm += [i, k + i]
        return arr.transpose(perm).reshape(tuple(shape))
    if getattr(devices[0], "platform", None) == "tpu" and len(devices) > 1:
        from jax.experimental import mesh_utils
        try:
            return mesh_utils.create_device_mesh(
                tuple(shape), devices=devices,
                allow_split_physical_axes=True)
        except Exception as e:  # odd subsets: fall back with a warning
            import warnings
            warnings.warn(
                f"create_device_mesh failed ({e}); falling back to raw "
                "device order — logical axes may not map onto the "
                "physical torus")
    return np.asarray(devices).reshape(shape)


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Degrees for each parallelism axis. -1 for fsdp means "absorb all
    remaining devices" (the common ZeRO-style default)."""

    pp: int = 1
    dp: int = 1
    fsdp: int = -1
    zps: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        fixed = math.prod(v for v in sizes.values() if v != -1)
        n_auto = sum(1 for v in sizes.values() if v == -1)
        if n_auto > 1:
            raise ValueError("at most one axis may be -1 (auto)")
        if n_auto == 1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by fixed axis product {fixed}")
            auto = n_devices // fixed
            sizes = {a: (auto if v == -1 else v) for a, v in sizes.items()}
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"axis product {sizes} != device count {n_devices}")
        return sizes


class MeshTopology:
    """A named device mesh plus helpers for group-style queries.

    Plays the role of the reference's ``ProcessTopology``
    (``runtime/pipe/topology.py``) and the group registry in
    ``deepspeed/utils/groups.py`` — but groups are axis names.
    """

    def __init__(self, config: TopologyConfig | None = None,
                 devices: Optional[Sequence[jax.Device]] = None,
                 axis_order: Sequence[str] = AXIS_ORDER,
                 dcn: Optional[dict] = None):
        self.config = config or TopologyConfig()
        devices = list(devices if devices is not None else jax.devices())
        self.sizes = self.config.resolve(len(devices))
        self.axis_order = tuple(axis_order)
        self.dcn_sizes = {a: int(v) for a, v in (dcn or {}).items()
                          if int(v) > 1}
        shape = tuple(self.sizes[a] for a in self.axis_order)
        dev_array = build_device_array(self.axis_order, shape,
                                       self.dcn_sizes, devices)
        self.mesh = Mesh(dev_array, axis_names=self.axis_order)

    # -- group-style queries (reference: groups.py getters) ---------------
    def axis_size(self, axis: str) -> int:
        return self.sizes[axis]

    @property
    def data_parallel_size(self) -> int:
        return self.sizes["dp"] * self.sizes["fsdp"] * self.sizes["zps"]

    @property
    def model_parallel_size(self) -> int:
        return self.sizes["tp"]

    @property
    def expert_parallel_size(self) -> int:
        return self.sizes["ep"]

    @property
    def pipe_parallel_size(self) -> int:
        return self.sizes["pp"]

    @property
    def sequence_parallel_size(self) -> int:
        return self.sizes["sp"]

    @property
    def world_size(self) -> int:
        return math.prod(self.sizes.values())

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def batch_sharding(self) -> NamedSharding:
        """Sharding for a [batch, ...] array split over all data axes."""
        return NamedSharding(self.mesh, PartitionSpec(self.batch_axes()))

    def batch_axes(self) -> tuple[str, ...]:
        return tuple(a for a in BATCH_AXES if self.sizes[a] > 1) or ("dp",)

    def __repr__(self):
        axes = ", ".join(f"{a}={self.sizes[a]}" for a in self.axis_order)
        return f"MeshTopology({axes})"


_GLOBAL_TOPOLOGY: MeshTopology | None = None


def set_topology(topo: MeshTopology) -> None:
    global _GLOBAL_TOPOLOGY
    _GLOBAL_TOPOLOGY = topo


def get_topology() -> MeshTopology:
    global _GLOBAL_TOPOLOGY
    if _GLOBAL_TOPOLOGY is None:
        _GLOBAL_TOPOLOGY = MeshTopology()
    return _GLOBAL_TOPOLOGY


def reset_topology() -> None:
    global _GLOBAL_TOPOLOGY
    _GLOBAL_TOPOLOGY = None
