"""From a profiler trace to device time by class of operation and by phase.

One level below the stage readers (``scopes.py``, ``scopes_seq.py``,
``scopes_sparse.py``, which this file imports and leaves as they are). The
program names, under the stages that hold most of a step, a **leaf** scope for
each class of operation (``jax.named_scope``; the strings are constants beside
the stage names at the top of ``models/sequence/looped.py``, ``sparse_moe.py``
and ``parallel/als.py``):

- under a layer's ``attention``: ``norm``, ``qkv``, ``rope``, ``kernel`` (the
  attention programs and the transposes and casts around them), ``out``; the
  sparse backbone's ``index`` and ``select`` count as leaves here too; ``norm``
  also under ``mlp`` and ``moe``;
- under ``moe/experts``: ``sort``, ``take``, ``grouped``, ``give``, and ``sum``
  nested in ``give`` forward and in ``take`` backward;
- under an ALS bucket's ``gram``: ``gather``, ``products``, and the
  ``exchange`` that was there.

Same ``.xplane.pb``, same ``XLA Ops`` line, same ``bench.window`` clip and the
same union of intervals as the accepted readers. Every device operation in the
window gets a ``Place``:

- **stage**: ``attention``, ``mlp``, ``exit`` as ``scopes_seq`` finds them;
  ``route``, ``experts`` as ``scopes_sparse`` does; ``moe`` and ``layers`` for
  what lies under those and no narrower stage; ``gram``, ``solve``,
  ``assemble`` as ``scopes`` does;
- **leaf**: the last scope component that is one of the family's leaf names.
  An ``op_name`` ends in the primitive (``.../give/sum/gather``), which is
  never read as a scope. An operation under a stage and no leaf is the stage's
  **self** time;
- **phase**, for the sequence step: ``recomputed`` if a component is
  ``rematted_computation`` or ``again`` (``sparse_moe._passes_bwd`` says so
  around the forward half of its ``jax.vjp``: a ``custom_vjp`` rule's
  recomputation carries no ``rematted_computation``), else ``backward`` if the
  name holds ``transpose(`` (``transpose(again)/jvp(give)/...`` is the
  pullback: backward), else ``forward``;
- **program**: a Pallas or other ``tpu_custom_call`` against the compiler's
  own operation.

**Operations without a scope** (XLA's ragged dot, whose rewrite keeps only its
own name; the compiler's copies and casts). ISSUE 35 asked for the innermost
*named* operation whose interval holds them. In a v5e trace of jax 0.9.0 the
events that hold others, ``while`` and ``conditional``, carry no ``tf_op`` at
all, so the place they would have had is taken from what they hold: the
components that the ``op_name``s of every scoped operation inside a control
flow event share (a ``conditional`` of the experts' backward pass holds
``transpose(jvp(seq.pass1))/layers/.../moe/experts/while/body/closed_call/
checkpoint/cond/branch_1_fun/...`` and nothing else). An operation without a
scope takes the place of the innermost control flow event around it that has
one. So a ragged dot under the backward ``conditional`` is counted
``backward``, those of the forward half run again among them (three of its nine
calls): ``jax.lax.ragged_dot`` was not made to keep its metadata.
A ragged dot that nothing places is still ``experts`` / ``grouped`` by its
instruction's name (``scopes_sparse.GROUPED_MATMUL``), with no phase. Control
flow events themselves are never added: their bodies are.

A program that names no leaf gives nothing (``of_run`` is None), so every
metric that reads this file is silent on a program from before the leaves.

    python benchmarks/scopes_leaf.py [trace.xplane.pb]

prints seconds in the window by stage, leaf and phase.
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys
from typing import NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import scopes, scopes_seq, scopes_sparse, trace_reduce  # noqa: E402

#: leaf scopes of the sequence step, and the sparse backbone's stages under
#: ``attention`` that are one class of operation already
SEQ_LEAVES = ("norm", "qkv", "rope", "kernel", "out", "index", "select",
              "sort", "take", "grouped", "give", "sum")
ALS_LEAVES = ("gather", "products", "exchange")
#: a leaf that every program with leaves names: a layer's, a bucket's
MARKERS = ("norm", "gather")
MOE = "moe"
#: what marks forward work run again in the backward pass
RECOMPUTED = ("rematted_computation", "again")
#: events of the ``XLA Ops`` line that hold their bodies
CONTROL_FLOW = ("while", "conditional", "call")
PROGRAM_TARGET = "tpu_custom_call"
#: stages that are one class of operation without a leaf below them; ``layers``
#: with no narrower stage is the scan's own slicing of the stacked parameters
#: and stacking of their gradients, which no scope of the program can reach
WHOLE_STAGES = ("mlp", "exit", "route", "experts", "layers")


class Place(NamedTuple):
    family: str          # "seq" | "als"
    top: str             # pass<t> | embed | optimizer; als.<side>_half_step
    stage: str | None
    leaf: str | None
    phase: str | None    # forward | recomputed | backward (sequence passes)
    program: bool = False
    placed: bool = False  # the place is the surrounding control flow's


def _last(parts, names) -> str | None:
    return next((p for p in reversed(parts) if p in names), None)


def phase_of(op_name: str) -> str:
    if any(c in RECOMPUTED for c in op_name.rstrip(":").split("/")):
        return "recomputed"
    return "backward" if "transpose(" in op_name else "forward"


@functools.lru_cache(maxsize=1 << 16)        # a trace repeats a few thousand names
def place_of(op_name: str) -> Place | None:
    """Where an ``op_name`` lies; None outside ``seq.`` and ``als.``. The last
    component is the primitive's own name and is no scope."""
    scoped, _, _ = op_name.rstrip(":").rpartition("/")
    found = scopes_seq.TOP.search(scoped)
    if found is not None:
        top = found.group(1)
        parts = re.split(r"[/():]", scoped[found.end():])
        stage = (scopes_seq.parse_scope(scoped)[1] if top.startswith("pass") else None)
        if stage in (None, "layers"):
            sparse = scopes_sparse.parse_stage(scoped)
            if sparse in ("route", "experts"):
                stage = sparse
            elif MOE in parts:
                stage = MOE
        leaf = _last(parts, SEQ_LEAVES) if stage not in (None, "layers") else None
        phase = phase_of(scoped) if top.startswith("pass") else None
        return Place("seq", top, stage, leaf, phase)
    als = scopes.parse_scope(scoped)
    if als is not None:
        side, stage = als
        parts = scoped.split("/")
        after = parts[parts.index(stage) + 1:] if stage in parts else []
        return Place("als", side, stage, _last(after, ALS_LEAVES), None)
    return None


def opcode_of(hlo: str) -> str:
    """The opcode of an instruction's HLO text (``%n = shape opcode(...``)."""
    _, sep, rest = hlo.partition(" = ")
    found = re.search(r"(?:^|[ )}\]])([a-z][\w\-]*)\(", rest) if sep else None
    return found.group(1) if found else ""


# ---- the file's bytes ----------------------------------------------------

def read_instructions(path: str) -> dict[str, dict[str, tuple[str, str]]]:
    """``{device plane: {instruction (short_name): (op_name, opcode)}}``, every
    instruction of the plane's ``event_metadata`` (``scopes.read_op_names``'s
    walk; that one keeps ``als.`` names alone and no opcode). Two programs that
    give one name to instructions in different places leave it unplaced."""
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
            hlo, op_name = "", ""
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
                        op_name = stat_names.get(stat[7], "")
            key = trace_reduce.short_name(hlo)
            mine = (op_name, opcode_of(hlo))
            theirs = by_name.setdefault(key, mine)
            if theirs != mine and place_of(theirs[0]) != place_of(op_name):
                by_name[key] = ("", theirs[1])
    return out


# ---- the reduction -------------------------------------------------------

def _shared(a: list | None, b: list) -> list:
    if a is None:
        return b
    n = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), min(len(a), len(b)))
    return a[:n]


def place_events(ops, instructions: dict) -> list[tuple[Place, float, float]]:
    """``(place, start, end)`` for the operations of one device's ``XLA Ops``
    line that have a place; control flow events are left out."""
    ops = sorted(ops, key=lambda op: (op[1], -op[2]))
    known = [instructions.get(name, ("", "")) for name, _, _ in ops]
    own = [place_of(op_name) for op_name, _ in known]
    parent, shared, open_ = [-1] * len(ops), {}, []
    for i, (name, s, e) in enumerate(ops):
        while open_ and ops[open_[-1]][2] <= s:
            open_.pop()
        parent[i] = next((j for j in reversed(open_) if ops[j][2] >= e), -1)
        if own[i] is not None:
            parts, j = known[i][0].rstrip(":").split("/"), parent[i]
            while j >= 0:
                shared[j] = _shared(shared.get(j), parts)
                j = parent[j]
        if known[i][1] in CONTROL_FLOW:
            open_.append(i)
    around: dict = {}

    def place_around(j: int) -> Place | None:
        """The place of control flow event ``j`` or of the nearest around it."""
        if j < 0:
            return None
        if j not in around:
            found = own[j] or place_of("/".join(shared.get(j, [])) + "/")
            around[j] = found if found is not None else place_around(parent[j])
        return around[j]

    out = []
    for i, (name, s, e) in enumerate(ops):
        op_name, opcode = known[i]
        if opcode in CONTROL_FLOW:
            continue
        program = name.endswith(PROGRAM_TARGET) or scopes_seq.KERNEL in op_name
        grouped = name.startswith(scopes_sparse.GROUPED_MATMUL)
        place = own[i]
        if place is None:
            place = place_around(parent[i])
            if place is not None:
                place = place._replace(placed=True)
            elif grouped:
                place = Place("seq", "", "experts", "grouped", None, placed=True)
        if place is None:
            continue
        if grouped and place.stage == "experts" and place.leaf is None:
            place = place._replace(leaf="grouped")
        out.append((place._replace(program=program), s, e))
    return out


def reduce_leaves(planes: dict, instructions: dict) -> dict:
    """``{"busy_s", "planes": [{Place: [clipped intervals]}, ...]}``: the
    window's device-busy seconds (mean over the device planes) and, plane by
    plane, the intervals of every place, clipped to the window. ``seconds``
    adds up what a metric asks for."""
    device_ops = {name: lines.get(trace_reduce.OP_LINE, [])
                  for name, lines in sorted(planes.items())
                  if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)}
    device_ops = {k: v for k, v in device_ops.items() if v}
    out = {"busy_s": 0.0, "planes": []}
    if not device_ops:
        return out
    window = trace_reduce.find_window(planes)
    if window is None:
        every = [iv for ops in device_ops.values() for iv in ops]
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = window
    for plane, ops in device_ops.items():
        out["busy_s"] += trace_reduce.total(trace_reduce.union(
            trace_reduce.clip(((s, e) for _, s, e in ops), lo, hi))) / len(device_ops)
        by_place: dict = {}
        for place, s, e in place_events(ops, instructions.get(plane, {})):
            by_place.setdefault(place, []).extend(trace_reduce.clip([(s, e)], lo, hi))
        out["planes"].append({k: v for k, v in by_place.items() if v})
    return out


def seconds(found: dict, want) -> float:
    """Device seconds (the union of the intervals, the mean over the device
    planes) of the places ``want(place)`` accepts."""
    if not found["planes"]:
        return 0.0
    return sum(
        trace_reduce.total(trace_reduce.union(
            iv for place, ivs in by_place.items() if want(place) for iv in ivs))
        for by_place in found["planes"]) / len(found["planes"])


def has_leaves(found: dict) -> bool:
    return any(place.leaf in MARKERS and not place.placed
               for by_place in found["planes"] for place in by_place)


def named(place: Place) -> bool:
    """Whether ``seq_leaf_coverage`` counts the place as named to a class of
    operation: under a leaf, in a stage that is one class already, or in
    ``seq.embed`` / ``seq.optimizer``. What it leaves out under the scopes is
    the self time of ``attention`` and of ``moe``, which the leaves split."""
    return (place.leaf is not None or place.stage in WHOLE_STAGES
            or place.top in ("embed", "optimizer"))


# ---- this run's trace, for the readers -----------------------------------

@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float) -> dict:
    return reduce_leaves(trace_reduce.read_planes(path), read_instructions(path))


def of_run(run) -> dict | None:
    """The reduction of this run's trace (the newest under ``benchmarks/.out``,
    as ``scopes.of_run`` finds it); None for an untraced run and for a program
    that names no leaf."""
    if not run.get("trace") or not (run.get("steps") or run.get("iterations")):
        return None
    path = scopes.newest_xplane()
    if path is None:
        return None
    found = _reduced(path, os.path.getmtime(path))
    return found if has_leaves(found) else None


def per_unit_ms(run, want, family: str = "seq") -> float | None:
    """Device milliseconds a step (sequence cells) or an iteration (ALS cells)
    of the places of ``family`` that ``want`` accepts; None where none is."""
    found = of_run(run)
    if found is None:
        return None
    total = seconds(found, lambda place: place.family == family and want(place))
    return 1000.0 * total / (run.get("steps") or run["iterations"]) if total else None


def table(found: dict) -> list:
    """``[family, top kind, stage, leaf, phase, program, placed, seconds]`` rows,
    the passes taken together, largest first."""
    rows: dict = {}
    for by_place in found["planes"]:
        for place, ivs in by_place.items():
            top = "pass" if place.top.startswith("pass") else place.top
            key = (place.family, top) + tuple(place[2:])
            rows[key] = rows.get(key, 0.0) + trace_reduce.total(
                trace_reduce.union(ivs)) / len(found["planes"])
    return sorted(([*k, v] for k, v in rows.items()), key=lambda row: -row[-1])


if __name__ == "__main__":
    xplane = sys.argv[1] if len(sys.argv) > 1 else scopes.newest_xplane()
    reduced = reduce_leaves(trace_reduce.read_planes(xplane), read_instructions(xplane))
    print(json.dumps({"busy_s": reduced["busy_s"], "has_leaves": has_leaves(reduced),
                      "named_s": seconds(reduced, named),
                      "rows": table(reduced)}, indent=1))
