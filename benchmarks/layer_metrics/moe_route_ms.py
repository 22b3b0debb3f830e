"""Device milliseconds per step under ``seq.pass1/layers/moe/route``: the router's matmul, softmax, top-k, gates and the load counts, forward, recomputed and backward."""

from benchmarks import scopes_sparse


def read(run):
    return scopes_sparse.per_step_ms(run, "route")
