"""Device milliseconds per step under the layers' ``norm`` leaves, whatever the
stage: the RMSNorms' reductions and what the compiler roots at a norm. The
scaling by the norm's weight is fused into the cast before the projection that
reads it and is counted there."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.leaf == "norm")
