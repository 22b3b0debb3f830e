"""The attention programs' share of the MXU's peak: the flops the causal pairs
need (``counts_zaya.attention_flops``: seven dots over 128 a pair and query
head, two forward and five backward; the forward pass worked again is not
counted), at the chip's bfloat16 peak, over the device time of the programs
under the ``attention/kernel`` leaf alone (forward, recomputed and the one
backward program; the programs that write their operands lie under ``rope``
and are not in it)."""

from benchmarks import counts_seq, counts_zaya, scopes_cca


def read(run):
    found, ms = scopes_cca.counted(run), scopes_cca.per_step_ms(run, scopes_cca.PROGRAMS)
    if found is None or not ms:
        return None
    return counts_seq.mxu_share_pct(counts_zaya.attention_flops(*found), ms / 1000.0,
                                    run["device_kind"])
