"""The delta rule's share of the HBM roofline: the least bytes the recurrence
moves, forward and backward (``counts_qwen3next.delta_rule_bytes``: q, k, v,
g, beta, the output and their cotangents once a token; the state stays on the
chip), at 819 GB/s, over the device time under ``linear_attention/delta``."""

from benchmarks import counts, counts_qwen3next, scopes_hybrid


def read(run):
    step, ms = run.get("step_counts"), scopes_hybrid.per_step_ms(run, "delta")
    if not step or not ms or "linear_layers" not in step:
        return None
    moved = counts_qwen3next.delta_rule_bytes(step["tokens"], step["linear_layers"], run["dims"])
    return counts.hbm_share_pct(moved, ms / 1000.0, run["device_kind"])
