"""The mixing stage's share of the HBM roofline: the least bytes it moves
(``counts_zaya.mix_bytes``: its inputs read once and its outputs written once
a phase, float32, the backward phase with the cotangents), at the chip's HBM
peak, over the device time under ``attention/mix``."""

from benchmarks import counts, counts_zaya, scopes_cca


def read(run):
    found, ms = scopes_cca.counted(run), scopes_cca.per_step_ms(run, scopes_cca.MIX)
    if found is None or not ms:
        return None
    return counts.hbm_share_pct(counts_zaya.mix_bytes(*found), ms / 1000.0, run["device_kind"])
