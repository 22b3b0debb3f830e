"""Shared by the ``device_idle_share.*`` readers: 100 x (1 - busy / window)."""


def idle_share_pct(run):
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
