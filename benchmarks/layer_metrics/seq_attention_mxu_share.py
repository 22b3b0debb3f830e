"""The flash kernels' share of the MXU's peak: the flops their calls in the
traced window computed (``counts_seq.flash_call_flops``, a call of each kind
counted from the trace) at the chip's bfloat16 peak, over their device time."""

from benchmarks import counts_seq, scopes_seq


def read(run):
    found, call = scopes_seq.of_run(run), run.get("flash_call")
    if found is None or not call or not found["kernel_s"]:
        return None
    per_call = counts_seq.flash_call_flops(**call)
    calls, seconds = found["kernel_calls"], sum(found["kernel_s"].values())
    # a backward pass runs dq and dkv, one call each: half its calls are either
    flops = (calls.get("forward", 0) * per_call["forward"]
             + calls.get("backward", 0) * per_call["backward"] / 2)
    if not seconds or not flops:
        return None
    return counts_seq.mxu_share_pct(flops, seconds, run["device_kind"])
