"""Mean milliseconds a query waited in the micro-batcher's queue."""

from benchmarks.layer_metrics._counters import histogram_mean


def read(run):
    mean = histogram_mean(run, "pio_serving_batch_queue_wait_seconds")
    return None if mean is None else 1000.0 * mean
