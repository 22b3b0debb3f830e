"""Programs compiled and written to the persistent cache before the window
(rows with ``cache`` ``miss``): 0 on a warm machine, unless a program's key
moves from run to run."""

from benchmarks.layer_metrics._setup import before


def read(run, **made_up):
    found = before(run, **made_up)
    return found and sum(row["cache"] == "miss" for row in found.rows)
