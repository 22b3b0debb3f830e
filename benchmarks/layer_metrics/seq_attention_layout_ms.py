"""Device milliseconds per step under ``attention/kernel`` that no device
program takes: the ``[B, H, T, D]`` transposes, casts and mask reductions
around the attention programs. Listed where the compiler keeps them apart from
the rotary positions."""

from benchmarks import scopes_leaf


def read(run):
    return scopes_leaf.per_unit_ms(run, lambda p: p.stage == "attention" and p.leaf == "kernel" and not p.program)
