"""Seconds this process has spent tracing functions to jaxprs and lowering
them to MLIR, up to the reader's call (``pio_jit_trace_seconds_total`` +
``pio_jit_lower_seconds_total``)."""

from benchmarks.layer_metrics._program import counter


def read(run):
    parts = [counter("pio_jit_trace_seconds_total"), counter("pio_jit_lower_seconds_total")]
    return None if None in parts else sum(parts)
