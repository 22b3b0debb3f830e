"""Device milliseconds per step under the layers' ``attention`` scopes: the
norms around it, the projections, rotary positions and the flash kernels."""

from benchmarks import scopes_seq


def read(run):
    return scopes_seq.per_step_ms(run, "attention")
