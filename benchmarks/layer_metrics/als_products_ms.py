"""Device milliseconds per ALS iteration under ``gram/products``: the Gram and
right-hand-side einsums over the gathered rows or, in a dual block, the
whitening matmul, ``T`` and ``S``."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(
        run, lambda p: p.stage == "gram" and p.leaf == "products", family="als")
