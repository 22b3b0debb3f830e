"""From a profiler trace to device time by the sequence template's scopes.

The looped backbone names the work of a training step with ``jax.named_scope``
(``predictionio_tpu/models/sequence/looped.py`` holds the strings):
``seq.embed``, ``seq.pass<t>/layers/attention``, ``seq.pass<t>/layers/mlp``,
``seq.pass<t>/exit`` and ``seq.optimizer``. The backward pass wraps the first
component (``transpose(jvp(seq.pass3))/layers/while/body/closed_call/
checkpoint/rematted_computation/attention/dot_general``), so a name is taken
apart by search, not by position. Same ``XLA Ops`` line, same ``bench.window``
clip and same walk over the file's bytes as ``scopes.py``, whose reader keeps
``als.`` names only; this one keeps ``seq.`` names. A program that names no
such scope gives nothing.

    python benchmarks/scopes_seq.py [trace.xplane.pb]
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

from benchmarks import scopes, trace_reduce  # noqa: E402

TOP = re.compile(r"seq\.(pass\d+|embed|optimizer)")
STAGES = ("attention", "mlp", "exit")
KERNEL = "pallas_call"


def parse_scope(op_name: str) -> tuple[str, str | None] | None:
    """``(top, stage)``: ``top`` is ``pass<t>``, ``embed`` or ``optimizer``;
    ``stage`` of a pass is ``attention``, ``mlp`` or ``exit``, or ``layers``
    for the rest of the scan over the layers; None outside ``seq.``."""
    found = TOP.search(op_name)
    if found is None:
        return None
    parts = re.split(r"[/():]", op_name[found.end():])
    stage = next((p for p in parts if p in STAGES), None)
    if stage is None and "layers" in parts:
        stage = "layers"
    return found.group(1), stage


def kernel_kind(op_name: str) -> str | None:
    """``forward`` or ``backward`` for an attention kernel's call, else None.
    A forward call recomputed inside the backward pass is a forward call."""
    scope = parse_scope(op_name)
    if scope is None or scope[1] != "attention" or KERNEL not in op_name:
        return None
    backward = "transpose(" in op_name and "rematted_computation" not in op_name
    return "backward" if backward else "forward"


def read_op_names(path: str) -> dict[str, dict[str, str]]:
    """``{device plane: {instruction (short_name): op_name}}`` for the
    instructions under a ``seq.`` scope (``scopes.read_op_names`` for these)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for number, plane in scopes._fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, v in scopes._fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 4:
                events.append(scopes._map_value(v))
            elif n == 5:
                meta = dict(scopes._fields(scopes._map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        by_name: dict = out.setdefault(name, {})
        for event in events:
            hlo, op_name = "", None
            for n, v in scopes._fields(event):
                if n == 2:
                    hlo = bytes(v).decode()
                elif n == 5:
                    stat = dict(scopes._fields(v))
                    if stat_names.get(stat.get(1)) != scopes.SCOPE_STAT:
                        continue
                    if 5 in stat:
                        op_name = bytes(stat[5]).decode()
                    elif 7 in stat:
                        op_name = stat_names.get(stat[7])
            key = trace_reduce.short_name(hlo)
            if op_name is None or parse_scope(op_name) is None:
                op_name = ""
            if by_name.setdefault(key, op_name) != op_name and (
                    parse_scope(by_name[key]) != parse_scope(op_name)):
                by_name[key] = ""  # two programs, one name, two scopes
    return out


def reduce_scopes(planes: dict, op_names: dict, top: int = 20) -> dict:
    """Device seconds in the window under the template's scopes, as
    ``scopes.reduce_scopes`` adds them up (unions of intervals clipped to the
    window, the mean over the device planes): ``busy_s``; ``scoped_s`` under
    any ``seq.`` scope; ``stages`` (``layers`` is everything under a pass's
    scan, attention and mlp included; ``attention``, ``mlp``, ``exit``,
    ``embed``, ``optimizer``); ``passes`` by pass; ``kernel_s`` and
    ``kernel_calls`` of the attention kernel by kind; ``outside`` the
    operations under no scope, by name."""
    device_ops = {
        name: lines.get(trace_reduce.OP_LINE, [])
        for name, lines in sorted(planes.items())
        if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
    }
    device_ops = {k: v for k, v in device_ops.items() if v}
    out = {"busy_s": 0.0, "scoped_s": 0.0, "stages": {}, "passes": {},
           "kernel_s": {}, "kernel_calls": {}, "outside": []}
    if not device_ops:
        return out
    window = trace_reduce.find_window(planes)
    if window is None:
        every = [iv for ops in device_ops.values() for iv in ops]
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = window
    n = len(device_ops)
    outside: dict = {}

    def seconds(intervals) -> float:
        return trace_reduce.total(
            trace_reduce.union(trace_reduce.clip(intervals, lo, hi))) / n

    for plane, ops in device_ops.items():
        names = op_names.get(plane, {})
        every, scoped, stages, passes, kernels = [], [], {}, {}, {}
        for name, s, e in ops:
            every.append((s, e))
            op_name = names.get(name, "")
            scope = parse_scope(op_name)
            if scope is None:
                cover = trace_reduce.overlap((s, e), (lo, hi))
                if cover > 0:
                    outside[name] = outside.get(name, 0.0) + cover / n
                continue
            top_scope, stage = scope
            scoped.append((s, e))
            if top_scope.startswith("pass"):
                passes.setdefault(top_scope, []).append((s, e))
                if stage in ("attention", "mlp", "layers"):
                    stages.setdefault("layers", []).append((s, e))
                if stage in STAGES:
                    stages.setdefault(stage, []).append((s, e))
            else:
                stages.setdefault(top_scope, []).append((s, e))
            kind = kernel_kind(op_name)
            if kind and trace_reduce.overlap((s, e), (lo, hi)) > 0:
                kernels.setdefault(kind, []).append((s, e))
        out["busy_s"] += seconds(every)
        out["scoped_s"] += seconds(scoped)
        for table, found in ((out["stages"], stages), (out["passes"], passes),
                             (out["kernel_s"], kernels)):
            for key, intervals in found.items():
                table[key] = table.get(key, 0.0) + seconds(intervals)
        for kind, intervals in kernels.items():
            out["kernel_calls"][kind] = out["kernel_calls"].get(kind, 0) + len(intervals) / n
    out["outside"] = sorted(([k, v] for k, v in outside.items()),
                            key=lambda row: -row[1])[:top]
    return out


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float) -> dict:
    return reduce_scopes(trace_reduce.read_planes(path), read_op_names(path))


def of_run(run) -> dict | None:
    """The reduction of this run's trace (the newest under ``benchmarks/.out``,
    as ``scopes.of_run`` finds it); None for an untraced run and for a program
    whose trace names no ``seq.`` scope."""
    if not run.get("trace") or not run.get("steps"):
        return None
    path = scopes.newest_xplane()
    if path is None:
        return None
    found = _reduced(path, os.path.getmtime(path))
    return found if found["scoped_s"] else None


def per_step_ms(run, stage: str) -> float | None:
    found = of_run(run)
    if found is None or stage not in found["stages"]:
        return None
    return 1000.0 * found["stages"][stage] / run["steps"]


if __name__ == "__main__":
    xplane = sys.argv[1] if len(sys.argv) > 1 else scopes.newest_xplane()
    print(json.dumps(
        reduce_scopes(trace_reduce.read_planes(xplane), read_op_names(xplane)),
        indent=1))
