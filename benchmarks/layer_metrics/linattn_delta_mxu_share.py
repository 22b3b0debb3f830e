"""The delta rule's share of the MXU's peak: the flops the recurrence itself
needs, forward and backward (``counts_qwen3next.delta_rule_flops``: three
``dk x dv`` products a token a value head, whatever chunk size or kernel works
them; recomputation not counted), at the chip's bfloat16 peak, over the device
time under ``linear_attention/delta``."""

from benchmarks import counts_qwen3next, counts_seq, scopes_hybrid


def read(run):
    counts, ms = run.get("step_counts"), scopes_hybrid.per_step_ms(run, "delta")
    if not counts or not ms or "linear_layers" not in counts:
        return None
    flops = counts_qwen3next.delta_rule_flops(
        counts["tokens"], counts["linear_layers"], run["dims"])
    return counts_seq.mxu_share_pct(flops, ms / 1000.0, run["device_kind"])
