"""Attention tiles the flash kernels work over all tiles of the packed
histories' rows, from the counts the span ``seq.pack`` carries
(``ops/flash_attention.tiles_worked``: all users, ``maxLen``, causal): the
share of the score square that can hold a (query, key) pair that counts."""

from benchmarks.layer_metrics._program import span


def read(run):
    attrs = (span("seq.pack") or {}).get("attrs", {})
    if not attrs.get("attention_tiles"):
        return None
    return 100.0 * attrs["attention_tiles_worked"] / attrs["attention_tiles"]
