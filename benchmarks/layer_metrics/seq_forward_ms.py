"""Device milliseconds per step of the passes' forward work (``seq.pass<t>``
outside ``transpose(``, ``rematted_computation`` and ``again``)."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.phase == "forward")
