"""Seconds spent loading programs from the persistent cache before the window
(``compile_s`` of the rows whose ``cache`` is ``hit``)."""

from benchmarks.layer_metrics._setup import before


def read(run, **made_up):
    found = before(run, **made_up)
    return found and sum(row["compile_s"] for row in found.rows if row["cache"] == "hit")
