"""The least a chip must move in one implicit ALS iteration with the factor
tables sharded over ``model`` and the rows over ``data``, and the peak it is
held against.

Like ``counts.py``: counted from retained edges and real rows, never from
padded slots or from what the compiler made of the exchange, so a layout that
sends less cannot read over 100% and one that sends more is not flattered.
"""

from __future__ import annotations

#: published chip-to-chip bandwidth of one chip, all its links together, keyed
#: by ``jax.devices()[0].device_kind`` (Google Cloud documentation, "TPU v5e":
#: 1,600 Gbit/s of inter-chip interconnect a chip = 200e9 bytes/s). On a 2x2
#: host a chip has two neighbours, so an exchange between the two chips of one
#: mesh axis can use only part of it: the share reads low, never high.
ICI_PEAKS = {
    "TPU v5 lite": {"ici_bytes_per_s": 200e9},
}


def ici_peak(device_kind: str) -> float:
    try:
        return ICI_PEAKS[device_kind]["ici_bytes_per_s"]
    except KeyError:
        raise KeyError(
            f"no published inter-chip bandwidth for device kind {device_kind!r}:"
            " add it to benchmarks/counts_sharded.py with its source, do not"
            " default it"
        ) from None


def exchange_bytes_per_chip(
    retained_edges_by_row: int,
    retained_edges_by_col: int,
    user_rows: int,
    item_rows: int,
    rank: int,
    factor_itemsize: int,
    data_shards: int,
    model_shards: int,
) -> float:
    """Least bytes one chip sends to other chips in one iteration, both
    half-steps, edges and rows spread evenly over the ``d x m`` chips.

    - Completing the gathered rows: a chip holds ``1/m`` of the other side's
      table and ``1/d`` of the rows, so it gathers ``edges / (d * m)`` factor
      rows; the chip that solves a row is one of the ``m`` of its pair, so
      ``(m - 1) / m`` of what a chip gathered is for another chip.
    - Handing the solved rows back: a chip solves ``rows / (d * m)`` rows;
      the next half-step gathers from a table sharded over ``model`` alone,
      so each solved row has to reach the ``d`` chips that hold its shard,
      one of which may be the chip itself: ``d - 1`` copies sent.
    - ``Y'Y``: one ``rank x rank`` float32 partial sum a side, sent once.
    """
    chips = data_shards * model_shards
    row_bytes = rank * factor_itemsize
    edges = retained_edges_by_row + retained_edges_by_col
    rows = user_rows + item_rows
    gathered = edges / chips * (model_shards - 1) / model_shards * row_bytes
    solved = rows / chips * (data_shards - 1) * row_bytes
    yty = 2 * rank * rank * 4 if chips > 1 else 0
    return gathered + solved + yty


def ici_share_pct(bytes_sent: float, seconds: float, device_kind: str) -> float:
    """Share of the inter-chip peak: least time at the peak over time taken."""
    return 100.0 * (bytes_sent / ici_peak(device_kind)) / seconds


def als_implicit_iteration_bytes_per_chip(
    retained_edges_by_row: int,
    retained_edges_by_col: int,
    user_rows: int,
    item_rows: int,
    rank: int,
    factor_itemsize: int,
    chips: int,
) -> float:
    """Least HBM bytes one chip moves in one implicit iteration, the work
    spread evenly over ``chips``: each retained edge read once in each
    orientation (a 4-byte index, a 4-byte play count, the other side's factor
    row); each real row's ``rank x rank`` Gram and ``rank`` right-hand side in
    float32, counted once; and each table read once more for ``Y'Y``."""
    per_edge = 4 + 4 + rank * factor_itemsize
    edges = retained_edges_by_row + retained_edges_by_col
    rows = user_rows + item_rows
    total = (edges * per_edge + rows * (rank * rank + rank) * 4
             + rows * rank * factor_itemsize)
    return total / chips
