"""Seconds of the program's own span ``als.pack`` (``prepare_als_data``)."""

from benchmarks.layer_metrics._program import span


def read(run):
    found = span("als.pack")
    return None if found is None else found["durationMs"] / 1000.0
