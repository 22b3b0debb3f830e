"""Device-busy milliseconds per optimizer step in the traced window."""


def read(run):
    trace, n = run.get("trace"), run.get("steps")
    if not trace or not n or not trace["busy_s"]:
        return None
    return 1000.0 * trace["busy_s"] / n
