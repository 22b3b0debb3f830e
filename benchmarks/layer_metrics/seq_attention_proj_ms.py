"""Device milliseconds per step of a layer's attention projections: the leaves
``qkv`` (the three projections, the cast of the normed input before them, into
which the compiler fuses the norm's scaling, and the reshape to heads) and
``out`` (the ``wo`` projection and the residual add), forward, recomputed and
backward."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.stage == "attention" and p.leaf in ("qkv", "out"))
