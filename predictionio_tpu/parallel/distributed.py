"""Multi-host runtime: the spark-submit/cluster-manager replacement.

The reference scales out through Spark's control plane -- spark-submit to a
cluster manager, driver-to-executor RPC, Netty block shuffle (SURVEY.md
section 2.7). The TPU-native control plane is ``jax.distributed``: one
Python process per host, a coordinator address, and after initialization a
single global device list over which GSPMD lays collectives -- all_gather /
psum / ppermute ride ICI inside a slice and DCN across slices. Nothing else
to build: there is no NCCL/MPI analogue to port, the XLA runtime IS the
communication backend.

What this module adds on top of the raw primitives:

- :func:`init_distributed`: idempotent `jax.distributed.initialize` from
  explicit args or ``PIO_COORDINATOR`` / ``PIO_NUM_PROCESSES`` /
  ``PIO_PROCESS_ID`` env (the launcher contract: set three env vars per
  host, run the same ``pio train`` command everywhere).
- :func:`build_mesh`: one entry point for both single-slice meshes and
  hybrid DCN x ICI meshes (``dcn_mesh_shape``), so engine.json's runtime
  section scales from one chip to a multi-slice pod without code changes.
  Per-axis sizes multiply: global axis = ici * dcn; ICI-contiguous devices
  stay adjacent so collectives on the fast axes never cross DCN.
- :func:`host_local_batch`: per-process data feeding -- each host loads its
  own shard of the batch and the pieces assemble into one global sharded
  array (`jax.make_array_from_process_local_data`), replacing the
  driver-scatters-partitions model of Spark with host-parallel reads.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("pio.distributed")

_INITIALIZED = False

#: runtime-conf keys that describe THIS launch, not the engine: they must
#: not be replayed from a persisted EngineInstance (a serving process would
#: try to join the long-dead training coordinator as the wrong rank)
LAUNCH_SCOPED_KEYS = ("pio.coordinator", "pio.num_processes", "pio.process_id")
LAUNCH_SCOPED_ENV = ("PIO_COORDINATOR", "PIO_NUM_PROCESSES", "PIO_PROCESS_ID")


def launch_process_id(runtime_conf=None) -> int:
    """This process's rank under the launcher contract, 0 when standalone.

    Usable BEFORE jax.distributed initializes (which happens lazily inside
    mesh construction): run_train needs the rank up front to decide which
    process owns the persistence side effects (lock, instance row, model
    blob, step checkpoints).
    """
    if runtime_conf and runtime_conf.get("pio.process_id") is not None:
        return int(runtime_conf["pio.process_id"])
    return int(os.environ.get("PIO_PROCESS_ID", "0") or 0)


def strip_launch_conf(runtime_conf: dict | None) -> dict:
    """Drop launch-scoped keys before persisting runtime conf."""
    return {
        k: v for k, v in (runtime_conf or {}).items()
        if k not in LAUNCH_SCOPED_KEYS
    }


def init_distributed(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Initialize the multi-host runtime (idempotent).

    Args fall back to ``PIO_COORDINATOR`` / ``PIO_NUM_PROCESSES`` /
    ``PIO_PROCESS_ID``. Returns True when running multi-process after the
    call, False for the single-process (no coordinator) case.
    """
    global _INITIALIZED
    coordinator = coordinator or os.environ.get("PIO_COORDINATOR")
    if not coordinator and not _INITIALIZED:
        return False
    import jax

    if _INITIALIZED:
        if coordinator:
            logger.warning(
                "distributed runtime already initialized; ignoring "
                "coordinator=%s", coordinator,
            )
        return jax.process_count() > 1
    num_processes = int(
        num_processes
        if num_processes is not None
        else os.environ.get("PIO_NUM_PROCESSES", "1")
    )
    process_id = int(
        process_id if process_id is not None else os.environ.get("PIO_PROCESS_ID", "0")
    )
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _INITIALIZED = True
    logger.info(
        "distributed runtime up: process %d/%d via %s",
        process_id, num_processes, coordinator,
    )
    return jax.process_count() > 1


def build_mesh(
    mesh_shape: list[int],
    axes: tuple[str, ...],
    dcn_mesh_shape: list[int] | None = None,
):
    """Build a Mesh over the global device list.

    ``mesh_shape`` is the per-slice (ICI) shape; a ``-1`` entry absorbs the
    remaining devices. ``dcn_mesh_shape``, when given, is the per-axis
    DCN replication factor (same rank; typically ``[num_slices, 1, ...]``):
    the global mesh axis sizes are the elementwise product and device order
    comes from ``mesh_utils.create_hybrid_device_mesh`` so ICI neighbors
    stay adjacent on the fast axes.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(mesh_shape) != len(axes):
        raise ValueError(
            f"mesh_shape {mesh_shape} and mesh_axes {axes} have different ranks"
        )
    if dcn_mesh_shape is not None:
        if len(dcn_mesh_shape) != len(axes):
            raise ValueError(
                f"dcn_mesh_shape {dcn_mesh_shape} and mesh_axes {axes} have "
                "different ranks"
            )
        from predictionio_tpu.utils.jax_compat import create_hybrid_device_mesh

        dcn_total = _prod(dcn_mesh_shape)
        if len(devices) % dcn_total:
            raise ValueError(
                f"dcn_mesh_shape {dcn_mesh_shape} (product {dcn_total}) does "
                f"not divide the {len(devices)}-device fleet"
            )
        resolved = _resolve_wildcard(mesh_shape, len(devices) // dcn_total)
        total = _prod(resolved) * dcn_total
        if total != len(devices):
            # create_hybrid_device_mesh requires the exact fleet; an under-
            # subscribed shape would die deep inside jax with no context
            raise ValueError(
                f"mesh shape {resolved} x dcn {dcn_mesh_shape} covers {total} "
                f"device(s) but the fleet has {len(devices)}; a hybrid mesh "
                "must use every device (use -1 wildcards to auto-fill)"
            )
        # TPU slices carry slice_index; CPU/virtual devices don't, so the
        # DCN granule degrades to the process there (the CI/test path)
        grid = create_hybrid_device_mesh(
            resolved,
            dcn_mesh_shape,
            devices=devices,
            process_is_granule=not hasattr(devices[0], "slice_index"),
        )
        mesh = Mesh(grid, axes)
        logger.info(
            "hybrid mesh: ici=%s x dcn=%s over %d %s device(s)",
            dict(zip(axes, resolved)), dcn_mesh_shape, grid.size,
            devices[0].platform,
        )
        return mesh

    resolved = _resolve_wildcard(mesh_shape, len(devices))
    total = _prod(resolved)
    if total > len(devices):
        raise ValueError(
            f"mesh shape {resolved} needs {total} devices, have {len(devices)}"
        )
    mesh = Mesh(np.array(devices[:total]).reshape(resolved), axes)
    logger.info(
        "mesh: %s over %d %s device(s)",
        dict(zip(axes, resolved)), total, devices[0].platform,
    )
    return mesh


def host_local_batch(mesh, spec, local_arrays):
    """Assemble per-process local batch shards into global sharded arrays.

    Each host passes the rows IT loaded (a pytree of numpy arrays); the
    result is a pytree of global jax.Arrays laid out per ``spec`` on
    ``mesh`` without any host ever holding the global batch. Single-process
    meshes degrade to a plain sharded device_put.
    """
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    put = lambda x: jax.make_array_from_process_local_data(sharding, x)
    return jax.tree_util.tree_map(put, local_arrays)


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _resolve_wildcard(shape: list[int], n_devices: int) -> list[int]:
    resolved = [int(s) for s in shape]
    if resolved.count(-1) > 1:
        raise ValueError(f"mesh shape {shape} has more than one -1")
    if -1 in resolved:
        known = _prod(s for s in resolved if s != -1)
        resolved[resolved.index(-1)] = max(n_devices // known, 1)
    return resolved
