"""Seconds this process has spent compiling programs or loading them from
the persistent cache, up to the reader's call
(``pio_jit_compile_seconds_total``)."""

from benchmarks.layer_metrics._program import counter


def read(run):
    return counter("pio_jit_compile_seconds_total")
