"""Mesh + sharding helpers shared by algorithms.

Conventions: axes ``("data", "model")``. Batch-parallel arrays shard their
leading dim over ``data``; model-parallel factor blocks shard over ``model``;
replicated arrays use an empty PartitionSpec.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from predictionio_tpu.utils.jax_compat import shard_map


def cached_by_mesh(maxsize: int = 32):
    """LRU cache for ``build(mesh, *static_args)`` program builders.

    ``jax.sharding.Mesh`` hashes BY VALUE (axis names + devices + shape +
    axis types), so an lru_cache keyed on the mesh deduplicates the fresh-
    but-equivalent meshes that long-lived serving/eval processes construct
    per retrain: one compiled program per distinct topology. The retention
    this implies is deliberate and bounded -- at most ``maxsize`` compiled
    programs (plus the tiny Mesh keys; devices are process-lifetime
    singletons anyway), evicted LRU. Thread-safe (lru_cache's internal
    lock; serving is a threaded HTTP server)."""
    return functools.lru_cache(maxsize=maxsize)


def one_step_in_flight(mesh: Mesh, value):
    """Wait for ``value`` when ``mesh`` is several CPU devices; no-op on a
    TPU mesh and on one device. Call it on a training step's output before
    dispatching the next step.

    XLA's CPU backend runs the collectives of a multi-device program on
    host threads that rendezvous in-process. With two steps in flight and
    fewer cores than virtual devices, participants of step N+1 hold the
    threads the last participants of step N need; the rendezvous then
    aborts the whole process after 40 s ("Termination timeout ... Expected
    8 threads to join the rendezvous, but only 6 of them arrived"; seen in
    4 of 7 runs of the dp x tp NCF step pinned to two cores, with and
    without donation, never with one step in flight). A chip's collectives
    need no host thread, so a TPU mesh keeps JAX's asynchronous dispatch.
    """
    if mesh.devices.size > 1 and mesh.devices.flat[0].platform == "cpu":
        jax.block_until_ready(value)
    return value


def local_mesh(data: int | None = None, model: int = 1) -> Mesh:
    """Mesh over the local devices; ``data=None`` takes all remaining."""
    devices = jax.devices()
    if data is None:
        data = len(devices) // model
    grid = np.array(devices[: data * model]).reshape(data, model)
    return Mesh(grid, ("data", "model"))


def require_axes(mesh: Mesh, axes, what: str) -> None:
    """Fail fast when a spec/collective axis name is not bound by this
    mesh. The runtime twin of ``pio check``'s S001/S002: today every
    mesh is ``local_mesh()``'s ``("data", "model")`` singleton, but the
    MPMD slice directions mint per-engine meshes with their own axis
    sets -- an eager ValueError naming both sides beats jax's late
    unbound-axis-name error deep inside a trace."""
    missing = [a for a in axes if a is not None and a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"{what}: axis name(s) {missing} not bound by this mesh "
            f"(axes={list(mesh.axis_names)}) -- build the spec from the "
            f"mesh's own axis names or thread the intended mesh here"
        )


def row_sharded(mesh: Mesh, axis: str = "data") -> NamedSharding:
    require_axes(mesh, (axis,), "row_sharded")
    return NamedSharding(mesh, PartitionSpec(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def fetch_global(arr) -> np.ndarray:
    """Host copy of a (possibly multi-process) sharded array: allgathers
    across processes when local devices cannot address every shard."""
    if jax.process_count() > 1 and not arr.is_fully_replicated:
        from predictionio_tpu.utils.jax_compat import process_allgather

        return np.asarray(process_allgather(arr, tiled=True))
    return np.asarray(arr)


def put_global(a, sharding: NamedSharding):
    """Place a host array every process holds IN FULL (each read the same
    event store / initialized from the same seed) onto a possibly
    multi-process sharding: each process contributes exactly its
    addressable shards. The callback form handles ANY spec -- row shards,
    model-axis parameter shards, replicated, and meshes where a sharded
    axis does not span processes (per-process slicing by rank would feed
    those wrong-sized shards)."""
    if jax.process_count() == 1:
        return jax.device_put(a, sharding)
    host = np.asarray(a)
    return jax.make_array_from_callback(host.shape, sharding, lambda idx: host[idx])


def shard_examples(mesh: Mesh | None, x, y):
    """Shared dp entry for the full-batch trainers (NB, LogReg).

    Returns ``(x_j, y_j, w_j, mesh)``: examples row-sharded over ``data``
    with zero-weight padding rows (so weighted means and masked counts stay
    exact when n does not divide the axis), or plain host arrays --
    ``mesh`` comes back None -- when no mesh was given or it has no
    ``data`` axis (custom-axis configs train unsharded rather than crash).
    """
    import jax.numpy as jnp

    weights = np.ones(np.asarray(x).shape[0], dtype=np.float32)
    if mesh is not None and "data" not in mesh.axis_names:
        mesh = None
    if mesh is None:
        return jnp.asarray(x), jnp.asarray(y), jnp.asarray(weights), None
    x_j, y_j, w_j = shard_rows(
        mesh, np.asarray(x, np.float32), np.asarray(y), weights
    )
    return x_j, y_j, w_j, mesh


def check_steps_ran(steps: int, n_examples: int, data_axis_size: int, what: str):
    """Raise when a training loop completed without a single step: the data
    can't fill even one batch across the data axis (shared guard for the
    sharded model templates)."""
    if steps == 0:
        raise ValueError(
            f"no training steps ran: {n_examples} {what}(s) cannot fill even "
            f"one batch across the {data_axis_size}-way data axis -- use "
            "fewer devices or more data"
        )


def seq_parallel_shard_map(body, mesh: Mesh, axis_name: str, check_vma: bool = True):
    """shard_map wrapper shared by the sequence-parallel attention
    strategies: q,k,v [B, T, H, D] shard as (data?, axis_name, None, None),
    the [B, T] key mask as (data?, axis_name). Keeps ring and Ulysses on one
    contract (mask defaulting and batch-axis resolution live in the callers'
    shared entry, this is the spec plumbing).

    ``check_vma=False`` is needed when the body runs a pallas kernel in
    interpret mode (the interpreter's internal index constants trip the
    varying-mesh-axes checker); bodies relying on ``pcast`` must keep it on.
    """
    from jax.sharding import PartitionSpec as P

    require_axes(mesh, (axis_name,), "seq_parallel_shard_map")
    batch_axis = "data" if "data" in mesh.axis_names else None
    spec = P(batch_axis, axis_name, None, None)
    mspec = P(batch_axis, axis_name)
    return shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, mspec), out_specs=spec,
        check_vma=check_vma,
    )


def shard_rows(mesh: Mesh, *arrays, axis: str = "data"):
    """Pad rows to the axis size and device_put sharded on the leading dim."""
    require_axes(mesh, (axis,), "shard_rows")
    n_shards = mesh.shape[axis]
    out = []
    for arr in arrays:
        rows = arr.shape[0]
        padded = -(-rows // n_shards) * n_shards
        if padded != rows:
            pad_width = [(0, padded - rows)] + [(0, 0)] * (arr.ndim - 1)
            arr = np.pad(arr, pad_width)
        out.append(jax.device_put(arr, row_sharded(mesh, axis)))
    return out[0] if len(out) == 1 else tuple(out)
