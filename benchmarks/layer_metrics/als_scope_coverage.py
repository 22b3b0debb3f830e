"""Share of the window's device-busy time that falls under any ``als.``
scope: the instrument's own health. A refactor that drops the names reads
lower here, and the stage metrics with it."""

from benchmarks import scopes


def read(run):
    return scopes.coverage_pct(run)
