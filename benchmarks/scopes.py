"""From a profiler trace to device time by the program's own scopes.

The program names the work inside an ALS iteration with ``jax.named_scope``
(``als.<side>_half_step/bucket<i>/<stage>``, and ``als.<side>_half_step/
assemble`` outside the buckets; ``predictionio_tpu/parallel/als.py`` holds
the strings). This file adds up, over the same ``XLA Ops`` line and the same
``bench.window`` clip as ``trace_reduce.reduce_planes``, the device seconds
that fall under each side, each stage, and any ``als.`` scope at all.

What it relies on, as seen in a v5e trace of jax 0.9.0 (PERF.md section 6,
PR 24; ``tests/train_v5e_scoped.xplane.pb`` is a cut of that trace):

- every event of a device plane points at an entry of the plane's
  ``event_metadata``; the entry's ``name`` is the instruction's HLO text (what
  ``jax.profiler.ProfileData`` gives as the event's name) and its ``stats``
  hold ``tf_op``: the instruction's ``op_name``, scopes and all, with a colon
  at the end (``jit(iteration)/als.user_half_step/bucket0/gram/als_gram_rhs/
  pallas_call:``). ``ProfileData`` shows an event's own stats only, so the
  metadata is read from the file's bytes: a walk over protobuf's wire format
  with the field numbers of ``xplane.proto`` (``XSpace.planes`` 1;
  ``XPlane.name`` 2, ``.event_metadata`` 4, ``.stat_metadata`` 5;
  ``XEventMetadata.name`` 2, ``.stats`` 5; ``XStat.metadata_id`` 1,
  ``.str_value`` 5, ``.ref_value`` 7; ``XStatMetadata.name`` 2);
- the compiler's own operations (copies of a block into a kernel's layout,
  slices it splits off) carry no ``tf_op`` or one without a scope: they are
  the part of busy time that ``coverage`` leaves out.

Events are joined to metadata on ``trace_reduce.short_name``. Where two
programs of a trace give one name to instructions under different scopes
(the iteration and the sync's one-element slice both have a ``copy.1``), the
name is left unscoped rather than guessed.

A reader has no path in ``run``: this run's trace is the newest
``.xplane.pb`` under ``benchmarks/.out/*/trace`` (the window deletes and
rewrites its cell's before every traced run).

    python benchmarks/scopes.py [trace.xplane.pb]

prints the reduction of one trace, with every operation outside the scopes
by name.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import trace_reduce  # noqa: E402

SCOPE_PREFIX = "als."
STAGES = ("gram", "solve", "assemble")
SCOPE_STAT = "tf_op"


def parse_scope(op_name: str) -> tuple[str, str | None] | None:
    """``(side scope, stage)`` of an ``op_name``: the first component that
    starts with ``als.`` and the first stage name after it; None outside."""
    parts = op_name.rstrip(":").split("/")
    for i, part in enumerate(parts):
        if part.startswith(SCOPE_PREFIX):
            stage = next((p for p in parts[i + 1:] if p in STAGES), None)
            return part, stage
    return None


# ---- the file's bytes ----------------------------------------------------

def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = int.from_bytes(buf[at:at + size], "little"), at + size
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield number, value


def _map_value(entry):
    """The value of one ``map<int64, Message>`` entry (key 1, value 2)."""
    return next((v for n, v in _fields(entry) if n == 2), memoryview(b""))


def read_op_names(path: str) -> dict[str, dict[str, str]]:
    """``{device plane: {instruction (short_name): op_name}}``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, v in _fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 4:
                events.append(_map_value(v))
            elif n == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        by_name: dict = out.setdefault(name, {})
        for event in events:
            hlo, op_name = "", None
            for n, v in _fields(event):
                if n == 2:
                    hlo = bytes(v).decode()
                elif n == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
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


# ---- the reduction -------------------------------------------------------

def reduce_scopes(planes: dict, op_names: dict, top: int = 20) -> dict:
    """Device seconds in the window under the program's scopes.

    ``planes`` as ``trace_reduce.read_planes`` gives them, ``op_names`` as
    ``read_op_names``. Seconds are the union of the events' intervals clipped
    to the window, averaged over the device planes, like ``busy_s``:
    ``scoped_s`` under any ``als.`` scope, ``sides`` by half-step, ``stages``
    by stage, and ``outside`` the operations under none, by name."""
    device_ops = {
        name: lines.get(trace_reduce.OP_LINE, [])
        for name, lines in sorted(planes.items())
        if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
    }
    device_ops = {k: v for k, v in device_ops.items() if v}
    out = {"busy_s": 0.0, "scoped_s": 0.0, "sides": {}, "stages": {},
           "outside": []}
    if not device_ops:
        return out
    window = trace_reduce.find_window(planes)
    if window is None:
        every = [iv for ops in device_ops.values() for iv in ops]
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = window
    n = len(device_ops)
    outside: dict = {}
    for plane, ops in device_ops.items():
        names = op_names.get(plane, {})
        every, scoped, sides, stages = [], [], {}, {}
        for name, s, e in ops:
            every.append((s, e))
            scope = parse_scope(names.get(name, ""))
            if scope is None:
                cover = trace_reduce.overlap((s, e), (lo, hi))
                if cover > 0:
                    outside[name] = outside.get(name, 0.0) + cover / n
                continue
            side, stage = scope
            scoped.append((s, e))
            sides.setdefault(side, []).append((s, e))
            if stage is not None:
                stages.setdefault(stage, []).append((s, e))

        def seconds(intervals) -> float:
            return trace_reduce.total(
                trace_reduce.union(trace_reduce.clip(intervals, lo, hi))) / n

        out["busy_s"] += seconds(every)
        out["scoped_s"] += seconds(scoped)
        for table, found in ((out["sides"], sides), (out["stages"], stages)):
            for key, intervals in found.items():
                table[key] = table.get(key, 0.0) + seconds(intervals)
    out["outside"] = sorted(([k, v] for k, v in outside.items()),
                            key=lambda row: -row[1])[:top]
    return out


# ---- this run's trace, for the readers -----------------------------------

def newest_xplane() -> str | None:
    paths = []
    for trace_dir in glob.glob(os.path.join(HERE, ".out", "*", "trace")):
        try:
            paths.append(trace_reduce.find_xplane(trace_dir))
        except FileNotFoundError:
            pass
    return max(paths, key=os.path.getmtime, default=None)


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime: float) -> dict:
    return reduce_scopes(trace_reduce.read_planes(path), read_op_names(path))


def of_run(run) -> dict | None:
    """The reduction of this run's trace; None for an untraced run and for a
    program whose trace names no ``als.`` scope."""
    if not run.get("trace") or not run.get("iterations"):
        return None
    path = newest_xplane()
    if path is None:
        return None
    found = _reduced(path, os.path.getmtime(path))
    return found if found["scoped_s"] else None


def per_iteration_ms(run, table: str, key: str) -> float | None:
    found = of_run(run)
    if found is None or key not in found[table]:
        return None
    return 1000.0 * found[table][key] / run["iterations"]


def coverage_pct(run) -> float | None:
    found = of_run(run)
    if found is None or not found["busy_s"]:
        return None
    return 100.0 * found["scoped_s"] / found["busy_s"]


if __name__ == "__main__":
    xplane = sys.argv[1] if len(sys.argv) > 1 else newest_xplane()
    print(json.dumps(
        reduce_scopes(trace_reduce.read_planes(xplane), read_op_names(xplane)),
        indent=1))
