"""From a profiler trace to device time by the sparse backbone's scopes.

``predictionio_tpu/models/sequence/sparse_moe.py`` names, inside a layer's
``attention`` scope, ``index`` (the indexer's projections and scores),
``select`` (the k-th largest) and ``kernel`` (attention over the selection),
and beside it ``moe/route`` and ``moe/experts``; the experts' grouped matmuls
are XLA's own ragged dot on a TPU, whose calls carry their own name and no
scope, and are taken by that name. The same names come wrapped
in the recomputed and the backward pass, so a name is taken apart by search,
as ``scopes_seq.py`` does; its reader of the trace's ``op_name``s is used as
it is. A program that names no such scope gives nothing.

    python benchmarks/scopes_sparse.py [trace.xplane.pb]
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

#: stage -> the scope names that have to be among an ``op_name``'s parts
STAGES = {"index": ("attention", "index"), "select": ("attention", "select"),
          "kernel": ("attention", "kernel"), "route": ("moe", "route"),
          "experts": ("moe", "experts")}


def parse_stage(op_name: str) -> str | None:
    if scopes_seq.TOP.search(op_name) is None:
        return None
    parts = set(re.split(r"[/():]", op_name))
    return next((stage for stage, need in STAGES.items() if parts.issuperset(need)), None)


#: XLA's own grouped matmul (what ``jax.lax.ragged_dot`` becomes on a TPU): the
#: rewrite names the call and drops the scope it came from. Nothing but the
#: held experts makes one
GROUPED_MATMUL = "ragged-dot"


def stage_of(instruction: str, op_name: str) -> str | None:
    """The stage of a device operation, from its scope or, for the grouped
    matmuls, from the instruction's own name."""
    return parse_stage(op_name) or (
        "experts" if instruction.lstrip("%").startswith(GROUPED_MATMUL) else None)


def kernel_kind(op_name: str) -> str | None:
    """``forward`` or ``backward`` for a call of an attention program, else
    None; a forward call recomputed inside the backward pass is a forward call."""
    if parse_stage(op_name) != "kernel" or scopes_seq.KERNEL not in op_name:
        return None
    backward = "transpose(" in op_name and "rematted_computation" not in op_name
    return "backward" if backward else "forward"


def reduce_stages(planes: dict, op_names: dict) -> dict:
    """Device seconds in the window by stage (unions of intervals clipped to
    the window, the mean over the device planes), and the attention programs'
    seconds and calls by kind."""
    device_ops = {name: lines.get(trace_reduce.OP_LINE, [])
                  for name, lines in sorted(planes.items())
                  if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)}
    device_ops = {k: v for k, v in device_ops.items() if v}
    out = {"stages": {}, "kernel_s": {}, "kernel_calls": {}}
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
        stages, kernels = {}, {}
        for name, s, e in ops:
            op_name = names.get(name, "")
            stage = stage_of(name, op_name)
            if stage is None:
                continue
            stages.setdefault(stage, []).append((s, e))
            kind = kernel_kind(op_name)
            if kind and trace_reduce.overlap((s, e), (lo, hi)) > 0:
                kernels.setdefault(kind, []).append((s, e))
        for table, found in ((out["stages"], stages), (out["kernel_s"], kernels)):
            for key, intervals in found.items():
                table[key] = table.get(key, 0.0) + seconds(intervals)
        for kind, intervals in kernels.items():
            out["kernel_calls"][kind] = out["kernel_calls"].get(kind, 0) + len(intervals) / n
    return out


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float) -> dict:
    return reduce_stages(trace_reduce.read_planes(path), scopes_seq.read_op_names(path))


def of_run(run) -> dict | None:
    """The reduction of this run's trace; None for an untraced run and for a
    program whose trace names none of these scopes."""
    if not run.get("trace") or not run.get("steps"):
        return None
    path = scopes.newest_xplane()
    if path is None:
        return None
    found = _reduced(path, os.path.getmtime(path))
    return found if found["stages"] else None


def per_step_ms(run, stage: str) -> float | None:
    found = of_run(run)
    if found is None or stage not in found["stages"]:
        return None
    return 1000.0 * found["stages"][stage] / run["steps"]


def kernel_calls_need(run, per_call_of) -> tuple[float, float] | None:
    """``(amount, seconds)``: what the attention programs' calls in the traced
    window need by ``per_call_of(selected pairs a layer)`` (a table by kind of
    call), and their device time. A backward pass runs ``dq`` and ``dkv``, one
    call each: half its calls are either."""
    found, counts = of_run(run), run.get("step_counts")
    if found is None or not counts or not found["kernel_s"]:
        return None
    per_call = per_call_of(counts["selected_pairs"] / run["dims"]["num_hidden_layers"])
    calls = found["kernel_calls"]
    amount = (calls.get("forward", 0) * per_call["forward"]
              + calls.get("backward", 0) * per_call["backward"] / 2)
    seconds = sum(found["kernel_s"].values())
    return (amount, seconds) if amount and seconds else None


if __name__ == "__main__":
    xplane = sys.argv[1] if len(sys.argv) > 1 else scopes.newest_xplane()
    print(json.dumps(reduce_stages(trace_reduce.read_planes(xplane),
                                   scopes_seq.read_op_names(xplane)), indent=1))
