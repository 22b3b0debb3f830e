"""Mean real (unpadded) size of the micro-batches flushed in the window."""

from benchmarks.layer_metrics._counters import histogram_mean


def read(run):
    return histogram_mean(run, "pio_serving_batch_size")
