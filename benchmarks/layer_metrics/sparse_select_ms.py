"""Device milliseconds per step under ``seq.pass1/layers/attention/select``: the k-th largest of every query's index scores and the selection's mask, forward and recomputed."""

from benchmarks import scopes_sparse


def read(run):
    return scopes_sparse.per_step_ms(run, "select")
