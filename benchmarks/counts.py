"""Peaks of the chip and the least work an ALS iteration needs.

The yardstick's arithmetic: kept here so that no PR that claims a gain can
change what a share is a share of. Counts come from retained edges and real
rows, never from padded slots, so a kernel that pads less cannot read over
100% and one that pads more is not flattered.
"""

from __future__ import annotations

#: published peaks of one chip, keyed by ``jax.devices()[0].device_kind``
#: (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 819 GB/s HBM, 16 GB). A device that is not in the table is an error.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it to"
            " benchmarks/counts.py with its source, do not default it"
        ) from None


def als_iteration_bytes(
    retained_edges_by_row: int,
    retained_edges_by_col: int,
    user_rows: int,
    item_rows: int,
    rank: int,
    factor_itemsize: int,
) -> int:
    """Least HBM bytes of one ALS iteration (user and item half-step).

    Each retained edge is read once in each orientation: a 4-byte index, a
    4-byte value and the opposite side's factor row (``rank`` x itemsize).
    Each real row's normal equations are formed and solved once: a
    ``rank x rank`` Gram and a ``rank`` right-hand side in float32, written
    and read back as little as once, counted once.
    """
    per_edge = 4 + 4 + rank * factor_itemsize
    edges = retained_edges_by_row + retained_edges_by_col
    rows = user_rows + item_rows
    return edges * per_edge + rows * (rank * rank + rank) * 4


def hbm_share_pct(bytes_moved: float, busy_s: float, device_kind: str) -> float:
    """Share of the HBM roofline: least time at the peak over time taken."""
    peak = device_peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * (bytes_moved / peak) / busy_s
