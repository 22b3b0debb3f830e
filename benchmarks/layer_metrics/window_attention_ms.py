"""Device milliseconds per step under ``window_attention``: the window layers' norms, projections and gate, rotary positions, the banded programs with the layout around them and the output projection, forward, recomputed and backward."""

from benchmarks import scopes_window


def read(run):
    return scopes_window.per_step_ms(run)
