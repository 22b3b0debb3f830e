"""Device milliseconds per ALS iteration under the ``gram`` scopes: the fused
gather->Gram kernel with its table copy, or the gather and the two einsums."""

from benchmarks import scopes


def read(run):
    return scopes.per_iteration_ms(run, "stages", "gram")
