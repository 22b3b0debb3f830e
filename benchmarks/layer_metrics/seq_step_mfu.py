"""Model flops utilisation of a step: the forward-and-backward flops the
window's batches need (``counts_seq.step_model_flops``: real tokens and
targets only, recomputation not counted) at the chip's bfloat16 peak, over
the device time a step took in the traced window."""

from benchmarks import counts_seq


def read(run):
    trace, n, flops = run.get("trace"), run.get("steps"), run.get("model_flops_per_step")
    if not trace or not n or not flops or not trace["busy_s"]:
        return None
    return counts_seq.mxu_share_pct(flops, trace["busy_s"] / n, run["device_kind"])
