"""The band's pairs over the pairs of the tiles the banded programs walk, from
the counts the fit's span carries (``seq.fit``: ``window_tiles_needed`` over
``window_tiles_walked``, the forward and the backward program together, both in
tiles of the forward program's size): 100% for tiles that hold nothing but the
band, 12% for programs that walk the causal triangle."""

from benchmarks.layer_metrics._program import span


def read(run):
    attrs = (span("seq.fit") or {}).get("attrs", {})
    if not attrs.get("window_tiles_walked"):
        return None
    return 100.0 * attrs["window_tiles_needed"] / attrs["window_tiles_walked"]
