"""From a profiler trace to device time by the window backbone's scopes.

``predictionio_tpu/models/sequence/window_moe.py`` names a window layer's mixer
``seq.pass1/layers/window_attention`` (one component: ``scopes_seq`` and
``scopes_leaf`` look for ``attention`` whole and leave it to ``layers``, so
their readers count the full layers alone), with the leaves ``norm``, ``qkv``,
``rope``, ``kernel`` and ``out`` below it. Same ``.xplane.pb``, same ``XLA
Ops`` line, same ``bench.window`` clip and union of intervals as the accepted
readers, whose pieces are used as they are. A program that names no such scope
gives nothing.

    python benchmarks/scopes_window.py [trace.xplane.pb]
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import scopes, scopes_leaf, scopes_seq, trace_reduce  # noqa: E402

WINDOW = "window_attention"
LEAVES = ("norm", "qkv", "rope", "kernel", "out")
PROGRAMS = "programs"


def place_of(op_name: str) -> tuple[str, str | None] | None:
    """``("window", leaf)`` under ``window_attention`` (``leaf`` the last of
    its leaves among the name's components, None for the mixer's own), else
    None. The last component is the primitive's own name and is no scope."""
    scoped, _, _ = op_name.rstrip(":").rpartition("/")
    if scopes_seq.TOP.search(scoped) is None:
        return None
    parts = re.split(r"[/():]", scoped)
    if WINDOW not in parts:
        return None
    below = parts[len(parts) - 1 - parts[::-1].index(WINDOW):]
    return "window", next((p for p in reversed(below) if p in LEAVES), None)


def reduce_places(planes: dict, op_names: dict) -> dict:
    """Device seconds in the window (unions of intervals clipped to it, the
    mean over the device planes): ``window`` in all, ``leaves`` by leaf
    (``self`` for what lies under the mixer and no leaf), ``programs`` the
    banded attention programs (the device programs under ``kernel``)."""
    device_ops = {name: lines.get(trace_reduce.OP_LINE, [])
                  for name, lines in sorted(planes.items())
                  if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)}
    device_ops = {k: v for k, v in device_ops.items() if v}
    out = {"window": 0.0, PROGRAMS: 0.0, "leaves": {}}
    if not device_ops:
        return out
    window = trace_reduce.find_window(planes)
    if window is None:
        every = [iv for ops in device_ops.values() for iv in ops]
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = window
    n = len(device_ops)

    def seconds(intervals) -> float:
        return trace_reduce.total(
            trace_reduce.union(trace_reduce.clip(intervals, lo, hi))) / n

    for plane, ops in device_ops.items():
        names = op_names.get(plane, {})
        found: dict = {}
        for name, s, e in ops:
            if name.split(".")[0].lstrip("%") in scopes_leaf.CONTROL_FLOW:
                continue   # control flow holds its body's operations: those are added
            op_name = names.get(name, "")
            place = place_of(op_name)
            if place is None:
                continue
            found.setdefault("window", []).append((s, e))
            found.setdefault(("leaf", place[1] or "self"), []).append((s, e))
            if name.endswith(scopes_leaf.PROGRAM_TARGET) or scopes_seq.KERNEL in op_name:
                found.setdefault(PROGRAMS, []).append((s, e))
        for key, intervals in found.items():
            if isinstance(key, tuple):
                out["leaves"][key[1]] = out["leaves"].get(key[1], 0.0) + seconds(intervals)
            else:
                out[key] += seconds(intervals)
    return out


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float) -> dict:
    return reduce_places(trace_reduce.read_planes(path), scopes_seq.read_op_names(path))


def of_run(run) -> dict | None:
    """The reduction of this run's trace; None for an untraced run and for a
    program whose trace names no ``window_attention``."""
    if not run.get("trace") or not run.get("steps"):
        return None
    path = scopes.newest_xplane()
    if path is None:
        return None
    found = _reduced(path, os.path.getmtime(path))
    return found if found["window"] else None


def per_step_ms(run, what: str = "window") -> float | None:
    """Device milliseconds a step under ``window_attention``: all of it
    (``"window"``), its device programs (``"programs"``) or one leaf."""
    found = of_run(run)
    if found is None:
        return None
    seconds = found[what] if what in ("window", PROGRAMS) else found["leaves"].get(what, 0.0)
    return 1000.0 * seconds / run["steps"] if seconds else None


def programs_of(run) -> tuple[dict, dict, float] | None:
    """``(step_counts, dims, seconds)``: what the two shares of the banded
    programs are taken from, the seconds a step of their device time; None for
    a run of another backbone, an untraced run and a trace without them."""
    step, dims = run.get("step_counts"), run.get("dims") or {}
    if not step or "window_pairs" not in step or "sliding_window" not in dims:
        return None
    ms = per_step_ms(run, PROGRAMS)
    return (step, dims, ms / 1000.0) if ms else None


if __name__ == "__main__":
    xplane = sys.argv[1] if len(sys.argv) > 1 else scopes.newest_xplane()
    print(json.dumps(reduce_places(trace_reduce.read_planes(xplane),
                                   scopes_seq.read_op_names(xplane)), indent=1))
