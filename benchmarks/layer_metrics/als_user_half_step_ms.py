"""Device milliseconds per ALS iteration under ``als.user_half_step``."""

from benchmarks import scopes


def read(run):
    return scopes.per_iteration_ms(run, "sides", "als.user_half_step")
