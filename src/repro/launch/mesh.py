"""Production mesh construction (assignment spec).

Axes: ``data`` (DP/FSDP + MoE expert parallelism), ``model`` (TP), and for
multi-pod runs a leading ``pod`` axis (pure data parallel across the
data-center interconnect). Functions, not module constants — importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh",
           "data_parallel_axes", "data_parallel_size", "devices_per_pod",
           "tensor_parallel_size"]


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto: GSPMD partitions whatever
    the sharding rules leave unconstrained."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    return make_mesh((data, model), ("data", "model"))


def tensor_parallel_size(mesh) -> int:
    """Size of the ``model`` (TP) axis; 1 for ``mesh=None`` or meshes
    without one. THE predicate for the serving stack's sharded-decode
    dispatch (engine KV placement, decode_attention's partial-merge path):
    a 1-device mesh and no mesh are the same single-rank program."""
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return int(mesh.shape["model"])


def data_parallel_axes(mesh) -> tuple:
    """The mesh's batch axes (``pod``, ``data``) that it has; ``()`` for
    ``mesh=None``."""
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_parallel_size(mesh) -> int:
    """Ranks over the batch axes: how many ways a batch dim splits; 1 for
    ``mesh=None``."""
    size = 1
    for a in data_parallel_axes(mesh):
        size *= int(mesh.shape[a])
    return size


def devices_per_pod(mesh: jax.sharding.Mesh) -> int:
    """Device-id span of one pod (0 when the mesh has no pod axis)."""
    if "pod" not in mesh.axis_names:
        return 0
    return mesh.devices.size // mesh.shape["pod"]
