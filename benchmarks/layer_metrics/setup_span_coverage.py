"""The share of ``setup_s`` that the program's own timeline explains: the
union of every span and every table row that ended before the window
(``backend.init``, ``als.pack``, ``seq.pack``, ``jit.*``) over the set-up. The
rest is the benchmark's seeded data and parameters, imports, transfers and the
warm steps' device time."""

from benchmarks.layer_metrics._setup import before, union_s


def read(run, **made_up):
    found = before(run, **made_up)
    if found is None:
        return None
    intervals = [(row["start_s"], row["end_s"]) for row in found.rows]
    intervals += [(start, end) for _, start, end in found.spans]
    return 100.0 * union_s(intervals, found.start, found.opened) / (found.opened - found.start)
