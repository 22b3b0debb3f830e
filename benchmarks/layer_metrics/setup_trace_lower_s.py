"""Seconds the programs made before the window took to trace and to lower
(``compile_report()``'s ``trace_s`` + ``lower_s``; a helper traced inside its
caller is in the caller's seconds, once)."""

from benchmarks.layer_metrics._setup import before


def read(run, **made_up):
    found = before(run, **made_up)
    return found and sum(row["trace_s"] + row["lower_s"] for row in found.rows)
