"""Device milliseconds per step of the sums by position (the leaf ``sum``: a
token's rows of a pass gathered and added, under ``give`` forward and under
``take`` backward)."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.stage == "experts" and p.leaf == "sum")
