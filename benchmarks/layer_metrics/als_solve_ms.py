"""Device milliseconds per ALS iteration under the ``solve`` scopes: ridge,
the batched K x K solve, the cast back to the factor dtype."""

from benchmarks import scopes


def read(run):
    return scopes.per_iteration_ms(run, "stages", "solve")
