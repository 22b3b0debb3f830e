"""The one module that touches ``jax.experimental`` and the sharding API.

The repo runs on one installation (jax 0.9.0, jaxlib 0.9.0, libtpu 0.0.34),
so nothing here tests for a version: the names are plain aliases. They stay
in one module so that a move of any of them inside jax is a one-line change.

- ``shard_map``, ``axis_size``: ``jax.shard_map``, ``jax.lax.axis_size``.
- ``pcast_varying``: ``jax.lax.pcast(..., to="varying")`` -- scan carries
  must match their varying body outputs under the vma checker.
- ``shape_struct``: ``jax.ShapeDtypeStruct`` carrying the vma of a model
  array, so pallas out_shapes compose under ``shard_map(check_vma=True)``.
- ``pallas`` / ``pallas_tpu``: the Pallas modules, resolved through module
  ``__getattr__`` so importing this module stays cheap for callers that
  never touch Pallas (it pulls in the Mosaic lowering machinery).
- ``broadcast_one_to_all`` / ``process_allgather`` /
  ``create_hybrid_device_mesh``: lazy fronts for the multihost/mesh utils
  that live under ``jax.experimental``.

``pio check`` rule J001 enforces that every ``jax.experimental`` /
``jax.shard_map`` / ``pjit`` touch in the package routes through here.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def pcast_varying(x, axis_name):
    """Cast a fresh constant to a "varying" collective type."""
    return jax.lax.pcast(x, axis_name, to="varying")


def __getattr__(name: str):
    """Lazy module attributes (PEP 562): ``from ...jax_compat import
    pallas as pl`` works, but callers that never touch Pallas never pay
    its import."""
    if name == "pallas":
        from jax.experimental import pallas

        globals()[name] = pallas
        return pallas
    if name == "pallas_tpu":
        from jax.experimental.pallas import tpu as pallas_tpu

        globals()[name] = pallas_tpu
        return pallas_tpu
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def broadcast_one_to_all(x):
    """One value (array or pytree) from process 0 to every process."""
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(x)


def process_allgather(x, tiled: bool = False):
    """Gather per-process values onto every host as a numpy array."""
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(x, tiled=tiled)


def create_hybrid_device_mesh(mesh_shape, dcn_mesh_shape, devices=None, **kwargs):
    """ICI-adjacency-preserving device grid for multi-slice meshes."""
    from jax.experimental import mesh_utils

    return mesh_utils.create_hybrid_device_mesh(
        mesh_shape, dcn_mesh_shape, devices=devices, **kwargs
    )


def shape_struct(shape, dtype, like=None):
    """ShapeDtypeStruct inheriting ``like``'s varying-mesh-axes; plain
    (non-sharded) callers get the ordinary struct."""
    vma = jax.typeof(like).vma if like is not None else None
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)
