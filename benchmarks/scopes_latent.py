"""From a profiler trace to device time by the latent backbone's scopes.

``predictionio_tpu/models/sequence/latent_moe.py`` names, inside a layer's
``attention/qkv``, the two latent paths ``q_latent`` (``W_qa``, its norm,
``W_qb``) and ``kv_latent`` (``W_kva``, its norm, ``W_kvb``), and puts the
prediction module under ``seq.pass1/mtp`` with ``merge``, the layer's own
``layers/...`` and ``exit`` below it (so the accepted stage and leaf readers
count the module's attention, experts and head where they count the stack's).
Same ``.xplane.pb``, same ``XLA Ops`` line, same ``bench.window`` clip and
union of intervals as the accepted readers, whose pieces are used as they are.
An operation is read by its own ``op_name``: XLA's ragged dots, which carry
none, are in no place here (``moe_grouped_ms`` has them). A program that names
no such scope gives nothing.

    python benchmarks/scopes_latent.py [trace.xplane.pb]
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

LATENTS = ("q_latent", "kv_latent")
MODULE = "mtp"


def places_of(op_name: str) -> tuple[str, ...]:
    """The places an ``op_name`` lies in: one of ``LATENTS`` (the last among
    the name's components), ``MODULE``, both, or none. The last component is
    the primitive's own name and is no scope."""
    scoped, _, _ = op_name.rstrip(":").rpartition("/")
    found = scopes_seq.TOP.search(scoped)
    if found is None:
        return ()
    parts = re.split(r"[/():]", scoped[found.end():])
    latent = next((p for p in reversed(parts) if p in LATENTS), None)
    return tuple(p for p in (latent, MODULE if MODULE in parts else None) if p)


def reduce_places(planes: dict, op_names: dict) -> dict:
    """Device seconds in the window (unions of intervals clipped to it, the
    mean over the device planes) under ``q_latent``, ``kv_latent`` and
    ``mtp``."""
    device_ops = {name: lines.get(trace_reduce.OP_LINE, [])
                  for name, lines in sorted(planes.items())
                  if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)}
    device_ops = {k: v for k, v in device_ops.items() if v}
    out = dict.fromkeys(LATENTS + (MODULE,), 0.0)
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
            if name.split(".")[0].lstrip("%") in ("while", "conditional", "call"):
                continue   # control flow holds its body's operations: those are added
            for place in places_of(names.get(name, "")):
                found.setdefault(place, []).append((s, e))
        for place, intervals in found.items():
            out[place] += trace_reduce.total(trace_reduce.union(
                trace_reduce.clip(intervals, lo, hi))) / len(device_ops)
    return out


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float) -> dict:
    return reduce_places(trace_reduce.read_planes(path), scopes_seq.read_op_names(path))


def per_step_ms(run, *places: str) -> float | None:
    """Device milliseconds a step under the named places together (they do not
    overlap: a latent is one of the two); None for an untraced run and for a
    program whose trace names none of them."""
    if not run.get("trace") or not run.get("steps"):
        return None
    path = scopes.newest_xplane()
    if path is None:
        return None
    found = _reduced(path, os.path.getmtime(path))
    seconds = sum(found[place] for place in places)
    return 1000.0 * seconds / run["steps"] if seconds else None


def attention_programs(run) -> tuple[dict, dict, float] | None:
    """``(step_counts, dims, seconds)``: what the two shares of the latent
    attention programs are taken from, the seconds a step of device time of
    the programs under ``attention``; None for a run of another backbone, an
    untraced run and a trace without the programs."""
    step, dims = run.get("step_counts"), run.get("dims") or {}
    if not step or "mtp_causal_pairs" not in step or "qk_head_dim" not in dims:
        return None
    ms = scopes_leaf.per_unit_ms(run, lambda p: p.stage == "attention" and p.program)
    return (step, dims, ms / 1000.0) if ms else None


if __name__ == "__main__":
    xplane = sys.argv[1] if len(sys.argv) > 1 else scopes.newest_xplane()
    print(json.dumps(reduce_places(trace_reduce.read_planes(xplane),
                                   scopes_seq.read_op_names(xplane)), indent=1))
