"""Device-busy milliseconds per micro-batch flushed in the traced window."""

from benchmarks.layer_metrics._counters import delta


def read(run):
    trace, batches = run.get("trace"), delta(run, "pio_serving_batch_size_count")
    if not trace or not batches or not trace["busy_s"]:
        return None
    return 1000.0 * trace["busy_s"] / batches
