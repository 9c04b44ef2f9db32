"""Device-mesh construction and sharding-spec plumbing.

This is the framework's "distributed communication backend" in the TPU idiom
(SURVEY.md §2 parallelism table): instead of an NCCL/MPI library, communication is
expressed as sharding annotations over a ``jax.sharding.Mesh``; XLA lowers them to ICI
collectives within a slice and DCN collectives across slices. Nothing here issues a
collective directly — the mesh + ``PartitionSpec`` layout IS the backend.

Axis convention (used across models/, parallel/, and the Dataset batch axis):

- ``"data"`` — batch sharding (DP)
- ``"fsdp"`` — parameter sharding along the data axis (ZeRO-style)
- ``"tensor"`` — tensor parallelism within attention/MLP blocks
- ``"sequence"`` — sequence/context parallelism (ring attention)
"""

from dataclasses import dataclass, field
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from unionml_tpu._logging import logger

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
TENSOR_AXIS = "tensor"
SEQUENCE_AXIS = "sequence"


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape: ordered mapping of axis name -> size.

    A size of ``-1`` means "all remaining devices" (at most one axis may use it).
    """

    axes: Tuple[Tuple[str, int], ...] = ((DATA_AXIS, -1),)

    @classmethod
    def from_dict(cls, axes: Mapping[str, int]) -> "MeshSpec":
        return cls(tuple(axes.items()))

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(name for name, _ in self.axes)

    def resolve_shape(self, n_devices: int) -> Tuple[int, ...]:
        sizes = [size for _, size in self.axes]
        wildcards = [i for i, s in enumerate(sizes) if s == -1]
        if len(wildcards) > 1:
            raise ValueError(f"At most one mesh axis may be -1; got {self.axes}")
        fixed = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
        if wildcards:
            if n_devices % fixed != 0:
                raise ValueError(f"{n_devices} devices not divisible by fixed axes product {fixed}")
            sizes[wildcards[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"Mesh axes {self.axes} require {fixed} devices; found {n_devices}")
        return tuple(sizes)

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        return make_mesh(dict(self.axes), devices=devices)


def make_mesh(
    axis_sizes: Optional[Mapping[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``Mesh`` over the given (default: all) devices.

    ``axis_sizes=None`` produces a 1-D data-parallel mesh over every device. Device
    ordering uses ``mesh_utils.create_device_mesh`` so ICI-adjacent chips land adjacent
    in the mesh (collectives ride ICI, not DCN).
    """
    devices = list(devices) if devices is not None else jax.devices()
    if axis_sizes is None:
        axis_sizes = {DATA_AXIS: len(devices)}
    spec = MeshSpec.from_dict(axis_sizes)
    shape = spec.resolve_shape(len(devices))
    try:
        device_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except (ValueError, AssertionError) as exc:
        # non-TPU or irregular topologies: plain reshape is still a valid mesh,
        # but its neighbours are no longer ICI neighbours — say so
        logger.warning("make_mesh: no topology-aware layout for %s (%s); using device order", shape, exc)
        device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, spec.axis_names)


def make_hybrid_mesh(
    ici_axes: Mapping[str, int],
    dcn_axes: Mapping[str, int],
) -> Mesh:
    """Multi-slice mesh: ``dcn_axes`` shard across slices (DCN), ``ici_axes`` within (ICI).

    Each logical axis may live in either (or both) domains; its total size is the
    product of its ICI and DCN extents. ``create_hybrid_device_mesh`` requires the two
    shape vectors to have equal rank, so both are expanded over the union of axis names
    with 1s where an axis is absent. Requires ``jax.distributed`` to be initialized (see
    :func:`unionml_tpu.parallel.distributed.initialize_distributed`).
    """
    names = list(dict.fromkeys([*dcn_axes, *ici_axes]))
    ici_shape = tuple(ici_axes.get(name, 1) for name in names)
    dcn_shape = tuple(dcn_axes.get(name, 1) for name in names)
    try:
        # TPU slices: slice_index is the DCN granule
        device_array = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=ici_shape,
            dcn_mesh_shape=dcn_shape,
        )
    except (ValueError, AssertionError):
        try:
            # multi-process CPU/GPU fleets: the PROCESS is the DCN granule, so the
            # dcn axes still land on real host boundaries (honest placement)
            device_array = mesh_utils.create_hybrid_device_mesh(
                mesh_shape=ici_shape,
                dcn_mesh_shape=dcn_shape,
                process_is_granule=True,
            )
        except (ValueError, AssertionError):
            if jax.process_count() > 1:
                # never silently reshape a real multi-host fleet: a wrong layout
                # would put "DCN" axes across arbitrary devices and hide the
                # placement bug the hybrid mesh exists to prevent
                raise
            # single-process emulation (unit tests): plain reshape with the same
            # logical shape; there is no host boundary to misplace
            total = tuple(i * d for i, d in zip(ici_shape, dcn_shape))
            device_array = np.asarray(jax.devices()[: int(np.prod(total))]).reshape(total)
    return Mesh(device_array, tuple(names))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard the leading (batch) dimension across ``axis``."""
    axes = tuple(a for a in (axis, FSDP_AXIS) if a in mesh.axis_names) if axis == DATA_AXIS else (axis,)
    present = tuple(a for a in axes if a in mesh.axis_names)
    return NamedSharding(mesh, PartitionSpec(present if len(present) > 1 else (present[0] if present else None)))


def batch_axis_size(mesh: Mesh, axis: str = DATA_AXIS) -> int:
    """Total device count the leading (batch) dim is sharded over under
    :func:`batch_sharding` — the data×fsdp product when both axes are present."""
    axes = tuple(a for a in (axis, FSDP_AXIS) if a in mesh.axis_names) if axis == DATA_AXIS else (axis,)
    size = 1
    for a in axes:
        if a in mesh.axis_names:
            size *= int(mesh.shape[a])
    return size


def wrapped_row_indices(n_rows: int, multiple: int):
    """Row indices that wrap-fill ``n_rows`` up to a multiple of ``multiple``.

    Returns ``None`` when already aligned. The fill repeats REAL rows (wrap-around)
    instead of fabricating zero rows, so a ragged batch rescued onto a mesh never
    trains or evaluates on fake data — a few examples are just slightly overweighted.
    Shared by every sharded-batch producer (``dp.batches``, ``dict_batches``, the
    prefetch path in ``fit``) so the rescue semantics cannot drift apart.
    """
    if multiple <= 1 or n_rows % multiple == 0:
        return None
    target = ((n_rows // multiple) + 1) * multiple
    return np.resize(np.arange(n_rows), target)


def shard_batch(batch: Any, mesh: Mesh, axis: str = DATA_AXIS) -> Any:
    """Lay a host batch (pytree) onto the mesh, sharded along the leading dim."""
    sharding = batch_sharding(mesh, axis)
    return jax.tree_util.tree_map(lambda leaf: jax.device_put(leaf, sharding), batch)


def logical_to_sharding(mesh: Mesh, *spec: Any) -> NamedSharding:
    """Convenience: ``PartitionSpec(*spec)`` bound to ``mesh``, dropping absent axes."""
    cleaned = tuple(s if (s is None or s in mesh.axis_names) else None for s in spec)
    return NamedSharding(mesh, PartitionSpec(*cleaned))


def named_sharding_tree(mesh: Mesh, spec_tree: Any) -> Any:
    """Bind a ``PartitionSpec`` pytree to ``mesh`` as a matching ``NamedSharding`` tree.

    The one place the spec->sharding tree_map lives: model ``param_shardings``
    tables produce spec trees, and every consumer (train-state layout in the
    driver, the sharded serving engine, the resident predictor) binds them to a
    concrete mesh through this helper.
    """
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
