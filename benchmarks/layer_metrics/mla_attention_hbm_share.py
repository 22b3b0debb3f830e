"""The latent attention programs' share of the HBM roofline: the least bytes
the attention moves, forward and backward
(``counts_joyai.latent_attention_bytes``: q, k_nope, v, the output and their
cotangents once a position a head, the shared rotary key once a position, in
bfloat16), at 819 GB/s, over the device time of the attention programs."""

from benchmarks import counts, counts_joyai, scopes_latent


def read(run):
    found = scopes_latent.attention_programs(run)
    if found is None:
        return None
    step, dims, seconds = found
    moved = counts_joyai.latent_attention_bytes(step, dims)
    return counts.hbm_share_pct(moved, seconds, run["device_kind"])
