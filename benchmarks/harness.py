"""What the drivers share: the shape of a check, the rehearsal's cut, and the
traced part of a window."""

from __future__ import annotations

import contextlib
import os
import shutil

import numpy as np

from benchmarks import trace_reduce

#: ``--rehearse 1`` divides a configuration's counts by this
REHEARSAL_CUT = 200


def check(name: str, value, limit) -> dict:
    """One number compared for ``correct``, beside its limit."""
    return {"name": name, "value": value, "limit": limit,
            "ok": bool(np.isfinite(value) and value <= limit)}


@contextlib.contextmanager
def traced_window(out_dir: str, enabled: bool):
    """Profile what runs inside into ``<out_dir>/trace``, under the host
    annotation the reduction takes for the window; yields that directory.
    Collecting the trace happens on exit, after the annotation has closed."""
    if not enabled:
        yield None
        return
    import jax

    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_NAME):
            yield trace_dir
    finally:
        jax.profiler.stop_trace()
