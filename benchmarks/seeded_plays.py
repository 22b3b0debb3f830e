"""Implicit-feedback interactions drawn from ``--seed``: who played what comes
from ``seeded.make_ratings`` (the configuration's degrees on both sides, the
structure fixed by ``structure_seed``, the seed relabelling users and items),
and each (user, song) pair's play count from the distribution the
configuration gives under ``data["plays"]``.

Pure NumPy, imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

from benchmarks import seeded

#: the stream of a seed the play counts are drawn from (0 to 4 are taken:
#: ratings, the two factor tables, the two row samples)
PLAY_STREAM = 5


def play_count_cdf(shape: dict) -> np.ndarray:
    """Cumulative distribution of a play count over ``1 .. shape["max"]``:
    ``P(k)`` proportional to ``k ** -shape["exponent"]``, a whole number with
    most of the mass at 1 and a heavy tail."""
    k = np.arange(1, shape["max"] + 1, dtype=np.float64)
    weights = k ** -shape["exponent"]
    return np.cumsum(weights / weights.sum())


def make_plays(data: dict, n_edges: int, n_users: int, n_items: int, seed: int):
    """``(users, items, plays)``: the edges of ``seeded.make_ratings`` (so
    every seed packs to the same shapes) with float32 play counts >= 1 in
    place of the stars."""
    users, items, _ = seeded.make_ratings(data, n_edges, n_users, n_items, seed)
    cdf = play_count_cdf(data["plays"])
    draws = seeded.rng_for(seed, PLAY_STREAM).random(n_edges)
    plays = np.minimum(np.searchsorted(cdf, draws), cdf.size - 1) + 1
    return users, items, plays.astype(np.float32)
