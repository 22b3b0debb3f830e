"""Device milliseconds per step under ``seq.pass1/layers/attention/index``: the indexer's projections, its norm and the index scores of every causal pair, forward and recomputed."""

from benchmarks import scopes_sparse


def read(run):
    return scopes_sparse.per_step_ms(run, "index")
