"""Device milliseconds per step under ``linear_attention/delta``: the chunked gated delta rule (the chunks' triangular systems and products, the state pass's two Pallas programs, the layout around them)."""

from benchmarks import scopes_hybrid


def read(run):
    return scopes_hybrid.per_step_ms(run, "delta")
