"""Device milliseconds per step of the held experts' grouped matmuls: the leaf
``grouped`` (the gated product between them, their casts) and XLA's ragged dots,
which carry no scope and are placed by the control flow that holds them."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.stage == "experts" and p.leaf == "grouped")
