"""The iteration's share of the HBM roofline: the least bytes an iteration
needs (``counts.als_iteration_bytes``) at the chip's peak, over the device
time an iteration took in the traced window."""

from benchmarks import counts


def read(run):
    trace, n = run.get("trace"), run.get("iterations")
    if not trace or not n or not trace["busy_s"]:
        return None
    return counts.hbm_share_pct(run["least_bytes_per_iteration"],
                                trace["busy_s"] / n, run["device_kind"])
