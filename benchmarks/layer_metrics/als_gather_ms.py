"""Device milliseconds per ALS iteration under ``gram/gather``: the gather of
the opposite side's rows from the table, in the model layout the local hits and
their mask (the exchange that completes them is ``als_exchange_ms``'s)."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(
        run, lambda p: p.stage == "gram" and p.leaf == "gather", family="als")
