"""Shared by the ``setup_*`` readers: the part of the program's own timeline
that ended before the window opened. The timeline is the rows of
``utils.platform.compile_report()`` (one per program: its trace, lowering and
compile or load, what the cache said) and every finished span of
``obs.trace.global_tracer()`` (``backend.init``, ``als.pack``, ``seq.pack``,
``jit.*``); the window opened ``setup_s`` after ``run.py``'s ``T0``, which
``obs.trace.epoch_seconds`` puts on the same axis. Nothing that ended later is
counted, so the reference's programs are not, which the process totals
``pio_jit_*`` hold. All give None where the program has no such table or clock.
"""

import calendar
import sys
import time
from types import SimpleNamespace


def timeline(run, rows=None, traces=None):
    """The whole timeline of a run: ``start`` and ``opened`` (``run.py``'s ``T0``
    and the instant the driver took ``setup_s``, in the epoch seconds of the
    program's spans), the table's ``rows`` and the ``spans`` as ``(op, start_s,
    end_s)``. ``rows`` as ``compile_report()`` and ``traces`` as
    ``Tracer.snapshot()["recent"]`` give them stand in for the program's own.
    None where the program has no clock or table, where the run has no
    ``T0``, and where the table is full and may have dropped the set-up's rows."""
    try:
        from predictionio_tpu.obs.trace import epoch_seconds, global_tracer
        from predictionio_tpu.utils.platform import PROGRAM_ROWS, compile_report
    except ImportError:
        return None
    t0 = next((module.T0 for module in map(sys.modules.get, ("__main__", "benchmarks.run"))
               if hasattr(module, "T0")), None)
    setup_s = run.get("end_to_end", {}).get("setup_s")
    if t0 is None or not setup_s:
        return None
    start = epoch_seconds(t0)
    if rows is None:
        rows = compile_report()
        if len(rows) >= PROGRAM_ROWS:
            return None
    if traces is None:
        traces = global_tracer().snapshot(limit=1000)["recent"]
    spans = []
    for trace in traces:
        stamp = trace["startTime"]  # 2026-10-03T21:11:01.550Z
        began = calendar.timegm(time.strptime(stamp[:19], "%Y-%m-%dT%H:%M:%S"))
        began += int(stamp[20:23]) / 1000.0
        for span in trace["spans"]:
            begin = began + span["offsetMs"] / 1000.0
            spans.append((span["op"], begin, begin + span["durationMs"] / 1000.0))
    return SimpleNamespace(start=start, opened=start + setup_s, rows=rows, spans=spans)


def before(run, **made_up):
    """``timeline`` less what ended after the window opened."""
    found = timeline(run, **made_up)
    if found is not None:
        found.rows = [row for row in found.rows if row["end_s"] <= found.opened]
        found.spans = [span for span in found.spans if span[2] <= found.opened]
    return found


def union_s(intervals, low: float, high: float) -> float:
    """Seconds of ``[low, high]`` that at least one interval covers."""
    covered, reached = 0.0, low
    for start, end in sorted(intervals):
        start, end = max(start, reached), min(end, high)
        if end > start:
            covered += end - start
            reached = end
    return covered
