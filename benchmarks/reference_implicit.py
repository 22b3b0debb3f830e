"""The plain reference of the implicit-feedback half-step (Hu, Koren,
Volinsky, ICDM 2008). NumPy only.

Imports nothing of the program and takes nothing the program made except the
state it is asked to judge; the cap's rule is ``reference.kept_edges``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference import _store, kept_edges


def half_step(own, other, plays, other_factors, rows, reg, alpha,
              cap: int | None = None, precision: str = "float64") -> np.ndarray:
    """One implicit half-step for ``rows`` of one side. With ``Y`` the other
    side's whole factor table and, per row, the edges it keeps
    (``kept_edges``), each with confidence ``1 + alpha * r`` and preference 1:

        (Y'Y + sum_obs alpha * r * y y' + reg * I) x = sum_obs (1 + alpha * r) * y

    ``Y'Y`` runs over every row of the table (an unobserved pair has
    confidence 1 and preference 0), ``reg`` is constant, and the system is
    solved with ``np.linalg.solve``. ``own`` and ``other`` are the two id
    columns of the edge list: users and songs for the user half-step, songs
    and users for the song half-step. An edge that repeats a pair adds its
    confidence, as the program's sum over edges does.

    ``precision`` as in ``reference.half_step``: "float64" is the reference;
    any other value names a storage type of ``ml_dtypes`` and gives the
    control, the factors gathered and written rounded to that type and the
    arithmetic between in float32.
    """
    order, starts, ends = kept_edges(own, rows, cap)
    work = np.float64 if precision == "float64" else np.float32
    table = _store(other_factors, precision).astype(work)
    k = table.shape[1]
    base = table.T @ table + work(reg) * np.eye(k, dtype=work)
    out = np.zeros((len(starts), k), dtype=work)
    for n, (lo, hi) in enumerate(zip(starts, ends)):
        edge = order[lo:hi]
        y = table[other[edge]]
        weight = work(alpha) * plays[edge].astype(work)
        gram = base + (y * weight[:, None]).T @ y
        out[n] = np.linalg.solve(gram, y.T @ (1 + weight))
    return _store(out, precision)
