"""Device milliseconds per ALS iteration under the ``exchange`` scopes: what
crosses the chips in the model layout (completing the gathered rows over
``model``, handing the solved rows back over ``data``), mean over the chips."""

from benchmarks import scopes_sharded


def read(run):
    return scopes_sharded.exchange_ms(run)
