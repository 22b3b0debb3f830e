"""Device milliseconds per step of the device programs under ``attention``:
the flash kernels, or the sparse backbone's index, select and attention
programs, forward, recomputed and backward."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.stage == "attention" and p.program)
