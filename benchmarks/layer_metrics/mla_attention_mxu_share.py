"""The latent attention programs' share of the MXU's peak: the flops the
attention itself needs, forward and backward
(``counts_joyai.latent_attention_flops``: a causal pair of real positions is a
score over 192 and a weighted sum over 128 a head, whatever blocks or operands
work it; recomputation not counted), at the chip's bfloat16 peak, over the
device time of the attention programs (forward, recomputed, ``dq``, ``dkv``)."""

from benchmarks import counts_joyai, counts_seq, scopes_latent


def read(run):
    found = scopes_latent.attention_programs(run)
    if found is None:
        return None
    step, dims, seconds = found
    flops = counts_joyai.latent_attention_flops(step, dims)
    return counts_seq.mxu_share_pct(flops, seconds, run["device_kind"])
