"""Device milliseconds per step of the passes' backward work: under
``transpose(`` and not run-again forward work."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.phase == "backward")
