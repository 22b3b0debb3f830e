"""Device milliseconds per step under ``seq.pass1/layers/linear_attention``: the linear layers' mixers whole (norm, projections, conv, gates, the delta rule, gated norm, output projection), forward, recomputed and backward."""

from benchmarks import scopes_hybrid


def read(run):
    return scopes_hybrid.per_step_ms(run)
