"""Device milliseconds per step under ``seq.optimizer``: Adam's update of
every parameter."""

from benchmarks import scopes_seq


def read(run):
    return scopes_seq.per_step_ms(run, "optimizer")
