"""Shared by the readers of ``/metrics`` histograms: the mean of what was
observed between the scrape at the window's start and the one at its end."""


def delta(run, name: str):
    counters = run.get("counters")
    if not counters:
        return None
    return counters["after"].get(name, 0.0) - counters["before"].get(name, 0.0)


def histogram_mean(run, name: str):
    count = delta(run, name + "_count")
    if not count:
        return None
    return delta(run, name + "_sum") / count
