"""The attention programs' share of the MXU's peak: the flops their calls in
the traced window need for the pairs the indexer selected
(``counts_keye.attention_call_flops``, a call of each kind counted from the
trace, recomputed forward calls among them) at the chip's bfloat16 peak, over
their device time. A masked dense program reads low."""

from benchmarks import counts_keye, counts_seq, scopes_sparse


def read(run):
    got = scopes_sparse.kernel_calls_need(
        run, lambda pairs: counts_keye.attention_call_flops(pairs, run["dims"]))
    if got is None:
        return None
    return counts_seq.mxu_share_pct(*got, run["device_kind"])
