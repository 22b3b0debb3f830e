"""Device milliseconds per step of the device programs under ``window_attention``: the banded attention programs, forward, recomputed and backward."""

from benchmarks import scopes_window


def read(run):
    return scopes_window.per_step_ms(run, scopes_window.PROGRAMS)
