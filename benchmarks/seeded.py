"""Everything a run draws from ``--seed``: ratings, factor tables, samples.

Pure NumPy, imports nothing of the program, so the plain reference
(``reference.py``) and the drivers read the same data. ``make_ratings`` takes
``bench.py:make_dataset``'s place; that one draws users uniformly and gives
every item more ratings than the cap, which no public data set bears out
(PERF.md, Findings).
"""

from __future__ import annotations

import numpy as np

#: which stream of a seed a factor table is drawn from
USER_STREAM, ITEM_STREAM = 1, 2


def user_id(row: int) -> str:
    """The id a row is known by to the program and in a query."""
    return f"u{row}"


def item_id(row: int) -> str:
    return f"i{row}"


def item_row(item: str) -> int:
    return int(item[1:])


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One generator per (seed, purpose). ``--seed`` may exceed 2**31."""
    return np.random.default_rng([int(seed), int(stream)])


def degree_sequence(n: int, total: int, shape: dict) -> np.ndarray:
    """``n`` whole degrees that sum to ``total``, sorted ascending, along the
    quantile curve a configuration gives for one side of its ratings.

    ``shape["quantiles"]`` are the published minimum, quartiles and maximum.
    Between the minimum and the third quartile the curve is linear in the
    logarithm. The top quarter, which holds most of the ratings, runs from the
    third quartile to the maximum as ``w*t + (1-w)*t**p`` of the logarithm's
    span: ``tail_linear`` (w) and ``tail_power`` (p) were solved once so that
    the mean and the standard deviation come out as published too. The curve
    is then scaled to the total (by a factor within 1e-5 of 1 at the
    configuration's own size; a rehearsal or a test at another size keeps the
    shape and scales the degrees) and rounded, the remainder going to the
    rows with the largest rounding error.
    """
    q = np.arange(n) / max(n - 1, 1)
    logs = np.log(np.asarray(shape["quantiles"], dtype=np.float64))
    w, p = shape["tail_linear"], shape["tail_power"]
    low = q <= 0.75
    t = (q[~low] - 0.75) / 0.25
    curve = np.empty(n)
    curve[low] = np.interp(q[low], (0.0, 0.25, 0.5, 0.75), logs[:4])
    curve[~low] = logs[3] + (logs[4] - logs[3]) * (w * t + (1 - w) * t ** p)
    curve = np.exp(curve)
    curve *= total / curve.sum()
    degrees = np.maximum(np.rint(curve), 1).astype(np.int64)
    rest = int(total - degrees.sum())
    sign = 1 if rest > 0 else -1
    # the rows rounded farthest the other way first; ties keep their order
    order = np.argsort((degrees - curve) * sign, kind="stable")
    while rest:
        able = order[degrees[order] + sign >= 1][: abs(rest)]
        degrees[able] += sign
        rest -= sign * able.size
    return degrees


def make_ratings(data: dict, n_edges: int, n_users: int, n_items: int, seed: int):
    """Ratings with the configuration's counts and its degrees on both sides.

    ``data`` is the configuration's ``data`` block. Each side's degrees follow
    ``degree_sequence``; the two sides' ends are matched at random (so a user
    and an item can meet more than once: the packer and the kernels take every
    rating as an edge, and the work is that of the degrees), and the ratings
    come in random order, which is the order the cap keeps the last of.

    Who rated what is drawn from ``data["structure_seed"]``, which the
    configuration fixes; ``seed`` relabels the users and the items and draws
    the stars. So every seed has the same multiset of ratings per user and per
    item under other names: the packed shapes, and with them the compiled
    program and the work of an iteration, do not change with the seed.
    """
    structure = rng_for(data["structure_seed"], 0)
    ends = []
    for n, shape in ((n_users, data["user_degrees"]), (n_items, data["item_degrees"])):
        degrees = degree_sequence(n, n_edges, shape)
        side = np.repeat(np.arange(n, dtype=np.int32), degrees)
        structure.shuffle(side)
        ends.append(side)
    rng = rng_for(seed, 0)
    users = rng.permutation(n_users).astype(np.int32)[ends[0]]
    items = rng.permutation(n_items).astype(np.int32)[ends[1]]
    ratings = rng.integers(1, 6, size=n_edges, dtype=np.int8).astype(np.float32)
    return users, items, ratings


def make_factors(rows: int, rank: int, seed: int, stream: int) -> np.ndarray:
    """A float32 factor table drawn N(0, 1/sqrt(rank))."""
    rng = rng_for(seed, stream)
    return (rng.standard_normal((rows, rank), dtype=np.float32)
            / np.float32(np.sqrt(rank)))


def sample_rows(n: int, size: int, seed: int, stream: int) -> np.ndarray:
    rng = rng_for(seed, stream)
    return np.sort(rng.choice(n, size=min(size, n), replace=False))
