"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps and top ops.

Read with ``jax.profiler.ProfileData`` and nothing else. What the reduction
relies on, as seen in a v5e trace of jax 0.9.0 (PERF.md section 5):

- each chip is a plane named ``/device:TPU:<n>``; its line ``XLA Ops`` holds
  one event per device operation with a start and a duration, and ``XLA
  Modules`` one event per program run;
- host threads are lines of the plane ``/host:CPU``; a
  ``jax.profiler.TraceAnnotation`` shows there under its own name, on the
  same clock as the device lines;
- the measured window is the host annotation ``WINDOW_NAME`` that the driver
  wraps around the traced part of the run.

Intervals are ``(start, end)`` pairs of seconds.
"""

from __future__ import annotations

import glob
import os

WINDOW_NAME = "bench.window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"
OP_LINE = "XLA Ops"
UNATTRIBUTED = "unattributed"


# ---- interval arithmetic -------------------------------------------------

def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge overlapping, nested and touching intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval of the merged ``busy`` covers."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a: tuple[float, float], b: tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute(gap, spans) -> str:
    """Name of the span ``(name, start, end)`` that covers most of ``gap``."""
    best, best_cover = UNATTRIBUTED, 0.0
    for name, s, e in spans:
        cover = overlap(gap, (s, e))
        if cover > best_cover:
            best, best_cover = name, cover
    return best


# ---- the trace -----------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """A device operation's name as the trace prints it is its whole HLO
    text: keep the instruction's own name, and a custom call's target."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    head = head.lstrip("%")
    _, found, target = rest.partition('custom_call_target="')
    if found:
        head += " " + target.split('"', 1)[0]
    return head[:80]


def read_planes(path: str) -> dict:
    """``{plane name: {line name: [(event name, start_s, end_s), ...]}}``."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = ev.start_ns * 1e-9
                events.append((short_name(ev.name), start,
                               start + ev.duration_ns * 1e-9))
    return planes


def find_window(planes: dict) -> tuple[float, float] | None:
    """The driver's ``WINDOW_NAME`` annotation on a host line, if traced."""
    for plane_name, lines in planes.items():
        if not plane_name.startswith(HOST_PLANE_PREFIX):
            continue
        for events in lines.values():
            for name, s, e in events:
                if name == WINDOW_NAME:
                    return (s, e)
    return None


def reduce_planes(planes: dict, host_spans=(), top: int = 10,
                  longest: int = 5) -> dict:
    """Busy seconds, window, top operations and longest idle gaps.

    ``host_spans`` are ``(name, start_s, end_s)`` measured from the window's
    start on the host clock (the driver's own spans and the program's
    ``obs/trace.py`` spans); a gap they do not cover is named after the host
    thread event of the trace that covers most of it, else "unattributed".
    Busy time and operation times are averaged over the device planes; gaps
    are those of the first device.
    """
    device_ops = {
        name: lines.get(OP_LINE, [])
        for name, lines in sorted(planes.items())
        if name.startswith(DEVICE_PLANE_PREFIX)
    }
    device_ops = {k: v for k, v in device_ops.items() if v}
    if not device_ops:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": []}
    window = find_window(planes)
    if window is None:
        every = [iv for ops in device_ops.values() for iv in ops]
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = window
    n = len(device_ops)
    busy_total, by_name, first_busy = 0.0, {}, None
    for ops in device_ops.values():
        merged = union(clip(((s, e) for _, s, e in ops), lo, hi))
        if first_busy is None:
            first_busy = merged
        busy_total += total(merged)
        for name, s, e in ops:
            cover = overlap((s, e), (lo, hi))
            if cover > 0:
                by_name[name] = by_name.get(name, 0.0) + cover
    ops_table = sorted(
        ([name, secs / n] for name, secs in by_name.items()),
        key=lambda row: -row[1],
    )[:top]
    spans = [(name, lo + s, lo + e) for name, s, e in host_spans]
    host_events = [
        (f"host:{name}", s, e)
        for plane_name, lines in planes.items()
        if plane_name.startswith(HOST_PLANE_PREFIX)
        for events in lines.values()
        for name, s, e in events
        if name != WINDOW_NAME and e > s
    ]
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:longest]
    gap_table = []
    for gap in idle:
        who = attribute(gap, spans)
        if who == UNATTRIBUTED:
            who = attribute(gap, host_events)
        gap_table.append([who, gap[1] - gap[0]])
    return {
        "busy_s": busy_total / n,
        "window_s": hi - lo,
        "devices": n,
        "device_ops": ops_table,
        "idle_gaps": gap_table,
    }


def reduce_trace(trace_dir: str, host_spans=()) -> dict:
    return reduce_planes(read_planes(find_xplane(trace_dir)), host_spans)


def describe(path: str, events_per_line: int = 6) -> str:
    """Planes, lines and first events of a trace: for reading one by hand."""
    out = []
    for plane_name, lines in read_planes(path).items():
        out.append(f"PLANE {plane_name}")
        for line_name, events in lines.items():
            out.append(f"  LINE {line_name}: {len(events)} events")
            for name, s, e in events[:events_per_line]:
                out.append(f"    {name[:90]} start={s:.6f} dur={e - s:.6f}")
    return "\n".join(out)
