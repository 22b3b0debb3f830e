"""From a profiler trace to device time by the compressed-convolution
backbone's scopes.

``predictionio_tpu/models/sequence/cca_moe.py`` names, inside a layer's
``attention``, the stage between the projections and the attention's operands
``mix`` (the value's shift, the mean, both convolutions, the l2 norms and the
temperature) beside ``blocks``'s leaves, and inside ``moe/route`` the router's
own leaves ``down``, ``carry``, ``mlp`` and ``choose``. An operation's place is
the last of a family's leaves among its ``op_name``'s components, so a program
under ``attention/rope`` (``ops/rope_layout.py``'s) is never under ``kernel``.
Same ``.xplane.pb``, same ``XLA Ops`` line, same ``bench.window`` clip and
union of intervals as the accepted readers, whose pieces are used as they are.
A program that names no such scope gives nothing.

    python benchmarks/scopes_cca.py [trace.xplane.pb]
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

ATTENTION_LEAVES = ("norm", "qkv", "mix", "rope", "kernel", "out", "merge")
ROUTE_LEAVES = ("down", "carry", "mlp", "choose")
MIX = "mix"
#: the attention programs: the device programs whose leaf is ``kernel``
PROGRAMS = "attention_programs"
#: the router's own matmuls and its state, apart from ``choose``
ROUTER = ("down", "carry", "mlp")


def place_of(op_name: str) -> str | None:
    """``"mix"``, ``"kernel"`` or another of ``ATTENTION_LEAVES`` for an
    operation under a layer's ``attention``, ``"route/<leaf>"`` for one under
    ``moe/route`` and a leaf of its own, else None. The last component is the
    primitive's own name and is no scope."""
    scoped, _, _ = op_name.rstrip(":").rpartition("/")
    found = scopes_seq.TOP.search(scoped)
    if found is None:
        return None
    parts = re.split(r"[/():]", scoped[found.end():])
    if "route" in parts and "moe" in parts:
        below = parts[len(parts) - 1 - parts[::-1].index("route"):]
        leaf = next((p for p in reversed(below) if p in ROUTE_LEAVES), None)
        return f"route/{leaf}" if leaf else None
    if "attention" in parts:
        return next((p for p in reversed(parts) if p in ATTENTION_LEAVES), None)
    return None


def reduce_places(planes: dict, op_names: dict) -> dict:
    """Device seconds in the window (unions of intervals clipped to it, the
    mean over the device planes) by place, and ``attention_programs`` for the
    device programs under ``kernel``."""
    device_ops = {name: lines.get(trace_reduce.OP_LINE, [])
                  for name, lines in sorted(planes.items())
                  if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)}
    device_ops = {k: v for k, v in device_ops.items() if v}
    out: dict = {}
    if not device_ops:
        return out
    window = trace_reduce.find_window(planes)
    if window is None:
        every = [iv for ops in device_ops.values() for iv in ops]
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = window
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
            found.setdefault(place, []).append((s, e))
            if place == "kernel" and (name.endswith(scopes_leaf.PROGRAM_TARGET)
                                      or scopes_seq.KERNEL in op_name):
                found.setdefault(PROGRAMS, []).append((s, e))
        for place, intervals in found.items():
            out[place] = out.get(place, 0.0) + trace_reduce.total(trace_reduce.union(
                trace_reduce.clip(intervals, lo, hi))) / len(device_ops)
    return out


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float) -> dict:
    return reduce_places(trace_reduce.read_planes(path), scopes_seq.read_op_names(path))


def of_run(run) -> dict | None:
    """The reduction of this run's trace; None for an untraced run and for a
    program whose trace names no ``attention/mix``."""
    if not run.get("trace") or not run.get("steps"):
        return None
    path = scopes.newest_xplane()
    if path is None:
        return None
    found = _reduced(path, os.path.getmtime(path))
    return found if found.get(MIX) else None


def per_step_ms(run, *places: str) -> float | None:
    """Device milliseconds a step under the named places together (they do
    not overlap: an operation has one place, and ``attention_programs`` is
    asked for alone); None where the trace names none of them."""
    found = of_run(run)
    if found is None:
        return None
    seconds = sum(found.get(place, 0.0) for place in places)
    return 1000.0 * seconds / run["steps"] if seconds else None


def counted(run) -> tuple[dict, dict] | None:
    """``(step_counts, dims)`` of a run of this backbone's cell, else None."""
    step, dims = run.get("step_counts"), run.get("dims") or {}
    if not step or "causal_pairs" not in step or "cca_time0" not in dims:
        return None
    return step, dims


if __name__ == "__main__":
    xplane = sys.argv[1] if len(sys.argv) > 1 else scopes.newest_xplane()
    print(json.dumps(reduce_places(trace_reduce.read_planes(xplane),
                                   scopes_seq.read_op_names(xplane)), indent=1))
