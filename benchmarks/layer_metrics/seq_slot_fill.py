"""Filled slots over all slots of the packed histories, from the counts the
span ``seq.pack`` carries: the share of a step's positions that hold an
event."""

from benchmarks.layer_metrics._program import span


def read(run):
    attrs = (span("seq.pack") or {}).get("attrs", {})
    if not attrs.get("slots"):
        return None
    return 100.0 * attrs["filled_slots"] / attrs["slots"]
