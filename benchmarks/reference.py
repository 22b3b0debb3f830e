"""The plain references that decide ``correct``. NumPy only.

Imports nothing of the program and takes nothing the program made except the
state it is asked to judge (the factors a half-step started from and the
factors it produced).
"""

from __future__ import annotations

import numpy as np


def _store(a: np.ndarray, precision: str) -> np.ndarray:
    """Round ``a`` to the storage type ``precision`` and widen it again."""
    if precision == "float64":
        return np.asarray(a, dtype=np.float64)
    import ml_dtypes

    return np.asarray(a, dtype=np.float32).astype(
        getattr(ml_dtypes, precision)
    ).astype(np.float64)


def kept_edges(own, rows, cap: int | None):
    """For each of ``rows``, the ratings its normal equations are formed from,
    as indices into the rating list: all of the row's ratings, or where it has
    more than ``cap`` the last ``cap`` in the order they were handed over
    (``maxEventsPerUser``: the most recent are kept, and the cap holds for
    either side). Returns the indices grouped by row and each row's bounds."""
    rows = np.asarray(rows)
    sel = np.nonzero(np.isin(own, rows))[0]
    order = sel[np.argsort(own[sel], kind="stable")]  # stable: keeps the order within a row
    starts = np.searchsorted(own[order], rows, side="left")
    ends = np.searchsorted(own[order], rows, side="right")
    if cap:
        starts = np.maximum(starts, ends - cap)
    return order, starts, ends


def half_step(own, other, ratings, other_factors, rows, reg, cap: int | None = None,
              precision: str = "float64") -> np.ndarray:
    """One ALS-WR half-step for ``rows`` of one side: per row the normal
    equations ``(Y'Y + reg * n * I) x = Y'r`` over the ratings that row keeps
    (``kept_edges``; ``n`` counts those), ``Y`` the other side's factors,
    solved with ``np.linalg.solve``. ``own`` and ``other`` are the two id
    columns of the rating list: users and items for the user half-step, items
    and users for the item half-step.

    ``precision="float64"`` is the reference. Any other value names a storage
    type of ``ml_dtypes`` and gives the control: the factors gathered and the
    factors written are rounded to that type and the arithmetic between is
    float32, which is the program's recipe one storage precision lower.
    """
    order, starts, ends = kept_edges(own, rows, cap)
    work = np.float64 if precision == "float64" else np.float32
    table = _store(other_factors, precision).astype(work)
    k = table.shape[1]
    out = np.zeros((len(starts), k), dtype=work)
    eye = np.eye(k, dtype=work)
    for n, (lo, hi) in enumerate(zip(starts, ends)):
        edge = order[lo:hi]
        y = table[other[edge]]
        gram = y.T @ y + work(reg * max(hi - lo, 1)) * eye
        out[n] = np.linalg.solve(gram, y.T @ ratings[edge].astype(work))
    return _store(out, precision)


def relative_error(got: np.ndarray, want: np.ndarray) -> float:
    """Norm of the difference over the norm of the reference."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def rmse(user_factors, item_factors, users, items, ratings) -> float:
    pred = np.einsum(
        "nk,nk->n",
        np.asarray(user_factors, dtype=np.float64)[users],
        np.asarray(item_factors, dtype=np.float64)[items],
    )
    return float(np.sqrt(np.mean((pred - ratings) ** 2)))


def global_mean_rmse(ratings) -> float:
    ratings = np.asarray(ratings, dtype=np.float64)
    return float(np.sqrt(np.mean((ratings - ratings.mean()) ** 2)))


def exact_scores(user_factors, item_factors, user: int, item_rows) -> np.ndarray:
    """Float32 dot products of one user with the named items."""
    u = np.asarray(user_factors[user], dtype=np.float32)
    return np.asarray(item_factors, dtype=np.float32)[np.asarray(item_rows)] @ u


def exact_top(user_factors, item_factors, user: int, num: int) -> np.ndarray:
    """Rows of the ``num`` best items for ``user`` by exact float32 score."""
    scores = np.asarray(item_factors, dtype=np.float32) @ np.asarray(
        user_factors[user], dtype=np.float32
    )
    top = np.argpartition(-scores, num - 1)[:num]
    return top[np.argsort(-scores[top], kind="stable")]
