"""The held experts' share of the MXU's peak: forward and backward flops of a
step's assignments to held experts (``counts_keye.experts_flops``;
recomputation not counted) at the chip's bfloat16 peak, over the device time
under ``moe/experts``, which holds the sort and the permutations too."""

from benchmarks import counts_keye, counts_seq, scopes_sparse


def read(run):
    counts, ms = run.get("step_counts"), scopes_sparse.per_step_ms(run, "experts")
    if not counts or not ms:
        return None
    flops = counts_keye.experts_flops(counts["moe_held_assignments"], run["dims"])
    return counts_seq.mxu_share_pct(flops, ms / 1000.0, run["device_kind"])
