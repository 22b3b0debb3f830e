"""Device milliseconds per step under ``attention/qkv/q_latent`` and ``attention/qkv/kv_latent``: the down-projections to the two latents, their norms and the up-projections to the heads' queries, keys and values, in every layer and in the prediction module, forward, recomputed and backward."""

from benchmarks import scopes_latent


def read(run):
    return scopes_latent.per_step_ms(run, *scopes_latent.LATENTS)
