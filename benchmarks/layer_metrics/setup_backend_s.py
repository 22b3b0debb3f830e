"""Seconds of the span ``backend.init`` (``utils.platform.ensure_backend``:
importing JAX, placing the compile cache, the backend's start), before the
window."""

from benchmarks.layer_metrics._setup import before


def read(run, **made_up):
    found = before(run, **made_up)
    took = [end - start for op, start, end in found.spans
            if op == "backend.init"] if found else []
    return sum(took) if took else None
