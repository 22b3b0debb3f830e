"""Device milliseconds per step under ``linear_attention``'s ``conv`` (the depthwise convolution and its silu), ``gates`` (beta, g, the l2 norms of q and k) and ``gated_norm`` (the output's norm and gate): the elementwise work around the rule."""

from benchmarks import scopes_hybrid


def read(run):
    return scopes_hybrid.per_step_ms(run, "conv", "gates", "gated_norm")
