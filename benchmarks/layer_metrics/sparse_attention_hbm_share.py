"""The attention programs' share of the HBM roofline: the least bytes their
calls in the traced window move for the pairs selected
(``counts_keye.attention_call_bytes``) at 819 GB/s, over their device time."""

from benchmarks import counts, counts_keye, scopes_sparse


def read(run):
    got = scopes_sparse.kernel_calls_need(
        run, lambda pairs: counts_keye.attention_call_bytes(
            run["step_counts"]["tokens"], pairs, run["dims"]))
    if got is None:
        return None
    return counts.hbm_share_pct(*got, run["device_kind"])
