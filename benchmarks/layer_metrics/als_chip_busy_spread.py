"""Largest minus smallest device-busy time of the chips in the traced window,
over their mean: heavy-tailed rows under a split over ``data`` make
stragglers, and the others wait for them at the next exchange."""

from benchmarks import scopes_sharded


def read(run):
    found = scopes_sharded.of_run(run)
    if found is None or len(found["busy_s"]) < 2 or not sum(found["busy_s"]):
        return None
    busy = found["busy_s"]
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
