"""Device milliseconds per step of the rest of ``moe/experts``: ``sort``,
``take`` and ``give`` outside their ``sum``, and what lies under no leaf (the
casts of the weights and rows, the chunk loop's slices)."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.stage == "experts" and p.leaf not in ("grouped", "sum"))
