"""The banded attention programs' share of the MXU's peak: the flops the
window layers' attention itself needs, forward and backward
(``counts_laguna.window_attention_flops``: a pair of the band between real
positions is a score over 128 and a weighted sum over 128 a head, whatever
tiles work it; recomputation not counted), at the chip's bfloat16 peak, over
the device time of the programs under ``window_attention`` (forward,
recomputed and the one backward program). A program that walks the triangle
reads about an eighth of one that walks the band."""

from benchmarks import counts_laguna, counts_seq, scopes_window


def read(run):
    found = scopes_window.programs_of(run)
    if found is None:
        return None
    step, dims, seconds = found
    flops = counts_laguna.window_attention_flops(step, dims)
    return counts_seq.mxu_share_pct(flops, seconds, run["device_kind"])
