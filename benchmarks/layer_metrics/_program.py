"""Shared by the readers of the program's own objects in the driver's
process: a counter of ``utils.metrics.global_registry()`` and the newest
finished span of ``obs.trace.global_tracer()`` under one ``op``. Both give
None where the program has no such counter or span."""


def counter(name: str):
    from predictionio_tpu.utils.metrics import global_registry

    values = [value for found, labels, value
              in global_registry().snapshot()["counters"]
              if found == name and not labels]
    return values[0] if values else None


def span(op: str):
    """``{"durationMs": ..., "attrs": {...}}`` of the newest span ``op``."""
    from predictionio_tpu.obs.trace import global_tracer

    for trace in global_tracer().snapshot(limit=1000)["recent"]:  # newest first
        for found in trace["spans"]:
            if found["op"] == op:
                return found
    return None
