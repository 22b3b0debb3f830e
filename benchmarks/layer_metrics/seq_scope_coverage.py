"""Share of the window's device-busy time that falls under any ``seq.``
scope: the instrument's own health."""

from benchmarks import scopes_seq


def read(run):
    found = scopes_seq.of_run(run)
    if found is None or not found["busy_s"]:
        return None
    return 100.0 * found["scoped_s"] / found["busy_s"]
