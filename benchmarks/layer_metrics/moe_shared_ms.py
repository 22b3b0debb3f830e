"""Device milliseconds per step under ``seq.pass1/layers/moe/shared``: the shared expert's three matmuls and its sigmoid gate on every token, forward, recomputed and backward."""

from benchmarks import scopes_hybrid


def read(run):
    return scopes_hybrid.per_step_ms(run, "shared")
