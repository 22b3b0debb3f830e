"""Device milliseconds per step under ``attention/mix``: the stage between the projections and the attention's operands (the value's shift, the mean of queries and keys, both causal convolutions, the l2 norms and the temperature), forward, recomputed and backward."""

from benchmarks import scopes_cca


def read(run):
    return scopes_cca.per_step_ms(run, scopes_cca.MIX)
