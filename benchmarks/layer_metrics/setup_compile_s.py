"""Seconds the compiler took before the window: ``compile_s`` of the rows the
persistent cache did not have (``cache`` ``miss``: compiled and written;
``none``: compiled and, under the threshold or with no cache, not written)."""

from benchmarks.layer_metrics._setup import before


def read(run, **made_up):
    found = before(run, **made_up)
    return found and sum(row["compile_s"] for row in found.rows
                         if row["cache"] in ("miss", "none"))
