"""Programs this process compiled and wrote to the persistent cache, up to
the reader's call (``pio_jit_cache_misses_total``): 0 on a warm machine."""

from benchmarks.layer_metrics._program import counter


def read(run):
    return counter("pio_jit_cache_misses_total")
