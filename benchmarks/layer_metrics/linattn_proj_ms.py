"""Device milliseconds per step under ``linear_attention``'s ``qkv`` (the two input projections) and ``out`` (the output projection and the residual add)."""

from benchmarks import scopes_hybrid


def read(run):
    return scopes_hybrid.per_step_ms(run, "qkv", "out")
