"""Sharding rules for the production meshes (16×16 single-pod,
2×16×16 multi-pod; axes ``data``/``model`` plus optional leading ``pod``).

Placement policy (divisibility-gated — a dim that doesn't divide its mesh
axes is replicated, never padded):

* **Params** — tensor-parallel on the trailing feature dim over ``model``,
  FSDP on the largest remaining dim over ``(pod, data)`` (falling back to
  ``data`` alone when the pod product doesn't divide).  1-D leaves (norm
  scales, gates) are replicated.
* **Batches** — leading (batch) dim over ``(pod, data)``.
* **Decode caches** — dim 1 (batch; dim 0 is the stacked-repeat axis) over
  ``(pod, data)``; the head axis (dim 2) over ``model`` when it divides.

All rules only read ``mesh.shape`` (a name→size mapping), so they work on
abstract stand-in meshes for layout validation without any devices.
"""
from __future__ import annotations

import jax
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
    None when neither divides.  The single divisibility-gating rule every
    data-axis placement in this package (and activation sharding, and the
    engine's sharded ``execute_many`` batches) uses."""
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


def _fsdp_entry(mesh, shape, taken: int | None):
    """(dim, spec entry) for the largest dim divisible by the data axes
    (preferring pod+data jointly), or (None, None)."""
    present = _data_axes(mesh)
    for axes in (present, present[-1:]):
        if not axes:
            continue
        n = _axis_product(mesh, axes)
        if n <= 1:
            continue
        cands = [d for d in range(len(shape))
                 if d != taken and shape[d] % n == 0 and shape[d] >= n]
        if cands:
            d = max(cands, key=lambda i: shape[i])
            return d, (axes if len(axes) > 1 else axes[0])
    return None, None


def _is_spec(x) -> bool:
    return isinstance(x, PartitionSpec)


def param_specs(tree, mesh, cfg):
    """PartitionSpec per leaf: TP over ``model`` on a trailing dim, FSDP
    over ``(pod, data)`` on the largest remaining dim."""
    model = mesh.shape.get("model", 1)

    def spec_for(leaf):
        shape = tuple(leaf.shape)
        nd = len(shape)
        if nd <= 1:
            return PartitionSpec()
        entries = [None] * nd
        model_dim = None
        if model > 1:
            for d in (nd - 1, nd - 2):
                if shape[d] % model == 0 and shape[d] >= model:
                    model_dim = d
                    entries[d] = "model"
                    break
        fsdp_dim, entry = _fsdp_entry(mesh, shape, model_dim)
        if fsdp_dim is not None:
            entries[fsdp_dim] = entry
        return PartitionSpec(*entries)

    return jax.tree.map(spec_for, tree)


def batch_specs(tree, mesh, cfg):
    """Shard the leading (batch) dim over the data(+pod) axes."""

    def spec_for(leaf):
        shape = tuple(leaf.shape)
        nd = len(shape)
        if nd == 0:
            return PartitionSpec()
        entry = pick_data_axes(mesh, shape[0])
        return PartitionSpec(entry, *(None,) * (nd - 1))

    return jax.tree.map(spec_for, tree)


def cache_specs(tree, mesh, cfg):
    """Decode-cache leaves are (repeats, batch, heads?, …): batch over the
    data(+pod) axes, the head-like dim 2 over ``model`` when it divides."""
    model = mesh.shape.get("model", 1)

    def spec_for(leaf):
        shape = tuple(leaf.shape)
        nd = len(shape)
        if nd < 2:
            return PartitionSpec(*(None,) * nd)
        entries = [None] * nd
        entries[1] = pick_data_axes(mesh, shape[1])
        if model > 1 and nd >= 4 and shape[2] % model == 0 and shape[2] >= model:
            entries[2] = "model"
        return PartitionSpec(*entries)

    return jax.tree.map(spec_for, tree)


def shardings_for(specs, mesh):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=_is_spec
    )
