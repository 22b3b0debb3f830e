"""What a trace of a program on several chips adds to ``scopes.py``: device
time under one nested scope component, and busy time plane by plane.

The model layout nests a scope ``exchange`` where its work crosses the chips
(``als.<side>_half_step/bucket<i>/gram/exchange`` around the exchange over
``model``, ``als.<side>_half_step/assemble/exchange`` around the re-layout of
the solved rows; ``predictionio_tpu/parallel/als.py`` holds the strings).
``scopes.parse_scope`` stops at the first stage it meets, so the stage metrics
still hold the exchange; this file finds the component itself. Same ``XLA
Ops`` line, same ``bench.window`` clip, same join of events to ``op_name`` as
``scopes.reduce_scopes``. A program that names no such scope gives nothing.
"""

from __future__ import annotations

import functools
import os

from benchmarks import scopes, trace_reduce


def has_component(op_name: str, component: str) -> bool:
    """Whether ``component`` follows an ``als.`` scope in ``op_name``."""
    parts = op_name.rstrip(":").split("/")
    for i, part in enumerate(parts):
        if part.startswith(scopes.SCOPE_PREFIX):
            return component in parts[i + 1:]
    return False


def reduce_planes(planes: dict, op_names: dict, component: str) -> dict:
    """For each device plane, in plane order: ``busy_s`` (the union of its
    operations' intervals clipped to the window) and ``component_s`` (the same
    over the operations under ``component``)."""
    device_ops = {
        name: lines.get(trace_reduce.OP_LINE, [])
        for name, lines in sorted(planes.items())
        if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
    }
    device_ops = {k: v for k, v in device_ops.items() if v}
    out = {"busy_s": [], "component_s": []}
    if not device_ops:
        return out
    window = trace_reduce.find_window(planes)
    if window is None:
        every = [iv for ops in device_ops.values() for iv in ops]
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = window

    def seconds(intervals) -> float:
        return trace_reduce.total(
            trace_reduce.union(trace_reduce.clip(intervals, lo, hi)))

    for plane, ops in device_ops.items():
        names = op_names.get(plane, {})
        out["busy_s"].append(seconds((s, e) for _, s, e in ops))
        out["component_s"].append(seconds(
            (s, e) for name, s, e in ops
            if has_component(names.get(name, ""), component)))
    return out


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float, component: str) -> dict:
    return reduce_planes(trace_reduce.read_planes(path),
                         scopes.read_op_names(path), component)


def of_run(run, component: str = "exchange") -> dict | None:
    """The reduction of this run's trace (the newest under
    ``benchmarks/.out``, as ``scopes.of_run`` finds it); None untraced."""
    if not run.get("trace") or not run.get("iterations"):
        return None
    path = scopes.newest_xplane()
    if path is None:
        return None
    return _reduced(path, os.path.getmtime(path), component)


def exchange_ms(run) -> float | None:
    """Device milliseconds an iteration under the ``exchange`` scopes, mean
    over the device planes; None where the program names none."""
    found = of_run(run)
    if found is None or not any(found["component_s"]):
        return None
    mean = sum(found["component_s"]) / len(found["component_s"])
    return 1000.0 * mean / run["iterations"]
