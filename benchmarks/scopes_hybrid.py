"""From a profiler trace to device time by the hybrid backbone's scopes.

``predictionio_tpu/models/sequence/hybrid.py`` names a linear layer's mixer
``seq.pass1/layers/linear_attention`` (one component: ``scopes_seq`` and
``scopes_leaf`` look for ``attention`` whole and leave it to ``layers``), with
the leaves ``norm``, ``qkv``, ``conv``, ``gates``, ``delta``, ``gated_norm``
and ``out`` below it, and the shared expert ``moe/shared`` beside
``moe/route`` and ``moe/experts``. Same ``.xplane.pb``, same ``XLA Ops`` line,
same ``bench.window`` clip and union of intervals as the accepted readers,
whose pieces are used as they are. A program that names no such scope gives
nothing.

    python benchmarks/scopes_hybrid.py [trace.xplane.pb]
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

from benchmarks import scopes, scopes_seq, trace_reduce  # noqa: E402

LINEAR = "linear_attention"
LEAVES = ("norm", "qkv", "conv", "gates", "delta", "gated_norm", "out")
SHARED = ("moe", "shared")


def place_of(op_name: str) -> tuple[str, str | None] | None:
    """``("linear", leaf)`` under ``linear_attention`` (``leaf`` the last of
    its leaves among the name's components, None for the mixer's own),
    ``("shared", None)`` under ``moe/shared``, else None. The last component
    is the primitive's own name and is no scope."""
    scoped, _, _ = op_name.rstrip(":").rpartition("/")
    if scopes_seq.TOP.search(scoped) is None:
        return None
    parts = re.split(r"[/():]", scoped)
    if LINEAR in parts:
        below = parts[len(parts) - 1 - parts[::-1].index(LINEAR):]
        return "linear", next((p for p in reversed(below) if p in LEAVES), None)
    if set(SHARED).issubset(parts):
        return "shared", None
    return None


def reduce_places(planes: dict, op_names: dict) -> dict:
    """Device seconds in the window (unions of intervals clipped to it, the
    mean over the device planes): ``linear`` in all, ``leaves`` by leaf
    (``self`` for what lies under the mixer and no leaf), ``shared``."""
    device_ops = {name: lines.get(trace_reduce.OP_LINE, [])
                  for name, lines in sorted(planes.items())
                  if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)}
    device_ops = {k: v for k, v in device_ops.items() if v}
    out = {"linear": 0.0, "shared": 0.0, "leaves": {}}
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
            if name.split(".")[0].lstrip("%") in ("while", "conditional", "call"):
                continue   # control flow holds its body's operations: those are added
            place = place_of(names.get(name, ""))
            if place is None:
                continue
            found.setdefault(place[0], []).append((s, e))
            if place[0] == "linear":
                found.setdefault(("leaf", place[1] or "self"), []).append((s, e))
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
    program whose trace names none of these scopes."""
    if not run.get("trace") or not run.get("steps"):
        return None
    path = scopes.newest_xplane()
    if path is None:
        return None
    found = _reduced(path, os.path.getmtime(path))
    return found if found["linear"] or found["shared"] else None


def per_step_ms(run, *leaves: str) -> float | None:
    """Device milliseconds a step under ``linear_attention``: all of it, or
    the sum of the named leaves; ``"shared"`` alone is ``moe/shared``."""
    found = of_run(run)
    if found is None:
        return None
    if leaves == ("shared",):
        seconds = found["shared"]
    elif leaves:
        seconds = sum(found["leaves"].get(leaf, 0.0) for leaf in leaves)
    else:
        seconds = found["linear"]
    return 1000.0 * seconds / run["steps"] if seconds else None


if __name__ == "__main__":
    xplane = sys.argv[1] if len(sys.argv) > 1 else scopes.newest_xplane()
    print(json.dumps(reduce_places(trace_reduce.read_planes(xplane),
                                   scopes_seq.read_op_names(xplane)), indent=1))
