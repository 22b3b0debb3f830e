"""Device milliseconds per step of the rotary positions of ``q`` and ``k``
(the leaf ``rope``: float32 elementwise, three phases). Listed where the
compiler keeps them apart from the layout around the attention programs."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.stage == "attention" and p.leaf == "rope")
