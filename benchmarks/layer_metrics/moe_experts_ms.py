"""Device milliseconds per step under ``seq.pass1/layers/moe/experts``: the sort by expert, the permutations in and out and the grouped matmuls of the held experts, forward, recomputed and backward."""

from benchmarks import scopes_sparse


def read(run):
    return scopes_sparse.per_step_ms(run, "experts")
