"""Device-busy milliseconds per ALS iteration in the traced window."""


def read(run):
    trace, n = run.get("trace"), run.get("iterations")
    if not trace or not n or not trace["busy_s"]:
        return None
    return 1000.0 * trace["busy_s"] / n
