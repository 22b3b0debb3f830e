"""The banded attention programs' share of the HBM roofline: the least bytes
the window layers' attention moves, forward and backward
(``counts_laguna.window_attention_bytes``: q, k, v, the output and their
cotangents once a position a head, in bfloat16), at 819 GB/s, over the device
time of the programs under ``window_attention``."""

from benchmarks import counts, counts_laguna, scopes_window


def read(run):
    found = scopes_window.programs_of(run)
    if found is None:
        return None
    step, dims, seconds = found
    moved = counts_laguna.window_attention_bytes(step, dims)
    return counts.hbm_share_pct(moved, seconds, run["device_kind"])
