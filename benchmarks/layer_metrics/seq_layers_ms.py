"""Device milliseconds per step under ``seq.pass<t>/layers``: every layer
application of every pass, forward, recomputed and backward."""

from benchmarks import scopes_seq


def read(run):
    return scopes_seq.per_step_ms(run, "layers")
