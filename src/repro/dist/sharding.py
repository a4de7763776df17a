"""Data-axis placement for the engine's sharded ``execute_many`` waves.

A wave's stacked parameter axis goes over the mesh's data axes
(``pod`` and ``data``, whichever are present), divisibility-gated: an
axis that does not divide is replicated, never padded here (the session
pads a wave to a multiple of the data axis before it asks).  The catalog
is replicated on every device.  The rules read only ``mesh.shape`` (a
name→size mapping) and ``mesh.axis_types``.
"""
from __future__ import annotations

from jax.sharding import NamedSharding, PartitionSpec


def _axis_product(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _data_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def pick_data_axes(mesh, dim: int):
    """The PartitionSpec entry for sharding ``dim`` over the data axes:
    pod+data jointly when their product divides, data alone as fallback,
    None when neither divides.  The one divisibility-gating rule the
    engine's sharded ``execute_many`` waves are placed by."""
    present = _data_axes(mesh)
    for axes in (present, present[-1:]):
        if not axes:
            continue
        n = _axis_product(mesh, axes)
        if n > 1 and dim % n == 0:
            return axes if len(axes) > 1 else axes[0]
    return None


def data_axis_size(mesh) -> int:
    """Number of data-parallel shards the mesh offers a batch axis (the
    product of the present data axes; 1 on a data-free or absent mesh)."""
    if mesh is None:
        return 1
    return _axis_product(mesh, _data_axes(mesh))


def _auto_axes(mesh):
    """``mesh`` with every axis ``Auto``.  ``jax.make_mesh`` types axes
    ``Explicit`` by default, which makes every traced array carry its
    sharding in its type; the engine's plans (sorts, ``searchsorted``,
    gathers under ``vmap``) have no explicit-sharding rules, so the engine
    places its batches on Auto axes and lets the compiler propagate."""
    from jax.sharding import AxisType, Mesh

    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def batch_sharding(mesh, dim: int):
    """NamedSharding placing a leading ``dim``-sized batch axis over the
    data axes, or None when divisibility gating rejects it.  Used as a jit
    in-sharding prefix: trailing dims are implicitly replicated, so one
    spec serves every leaf of a stacked-parameter pytree."""
    entry = pick_data_axes(mesh, dim)
    if entry is None:
        return None
    return NamedSharding(_auto_axes(mesh), PartitionSpec(entry))


def replicated_sharding(mesh):
    """NamedSharding replicating a value on every device of ``mesh`` —
    how catalog tables broadcast under sharded batch execution."""
    return NamedSharding(_auto_axes(mesh), PartitionSpec())
