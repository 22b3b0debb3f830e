"""Retained edges over padded slots, both sides, from the counts the span
``als.pack`` carries: the share of gather slots that hold a rating."""

from benchmarks.layer_metrics._program import span


def read(run):
    attrs = (span("als.pack") or {}).get("attrs", {})
    sides = [attrs[side] for side in ("by_row", "by_col") if side in attrs]
    slots = sum(side["padded_slots"] for side in sides)
    if len(sides) < 2 or not slots:
        return None
    return 100.0 * sum(side["retained_edges"] for side in sides) / slots
