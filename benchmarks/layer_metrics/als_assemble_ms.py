"""Device milliseconds per ALS iteration under the ``assemble`` scopes: the
zero row, the replicated constraint, YtY, putting the buckets together."""

from benchmarks import scopes


def read(run):
    return scopes.per_iteration_ms(run, "stages", "assemble")
