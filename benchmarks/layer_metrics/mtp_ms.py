"""Device milliseconds per step under ``seq.pass1/mtp``: the prediction module's merge, its whole expert layer and its head and loss, forward, recomputed and backward (less XLA's ragged dots, which carry no scope)."""

from benchmarks import scopes_latent


def read(run):
    return scopes_latent.per_step_ms(run, scopes_latent.MODULE)
