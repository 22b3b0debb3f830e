"""Batched linear algebra for the MXU/VPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax.linalg import cholesky
from jax.scipy.linalg import cho_solve

from predictionio_tpu.ops.ragged import round_up
from predictionio_tpu.utils.platform import note_blocked_solve

#: ranks above this leave the unrolled solve -- its graph grows O(K^2) in
#: traced ops -- for the blocked one, whose diagonal blocks are this wide
_UNROLL_MAX_K = 32

#: lanes of a TPU vector register row: the minor dimension of an array in HBM
#: is padded to a multiple of it
LANES = 128

#: most rows a caller should hand the blocked solve at a time
#: (``parallel.als.block_plan`` cuts its row chunks to it). One v5e chip, rank
#: 128, us a row by rows a call (PERF.md section 6, PR 27): 4,096 1.37, 8,192
#: 1.46, 16,384 1.67, 32,768 3.18: past a few thousand rows the steps'
#: temporaries leave the chip's fast memory. Not cut here, in a loop of its
#: own: compiled into the ALS iteration such a loop took the gather's table
#: out of that memory, and the gather went from 613 to 904 ms an iteration.
BLOCKED_SOLVE_ROWS = 4096


def solve_path(k: int, unroll: bool) -> str:
    """Which of ``batched_spd_solve``'s three paths K x K systems take
    (``unroll`` as resolved for the target platform: true on a TPU):
    "unrolled" up to ``_UNROLL_MAX_K``, "blocked" above it, and off the TPU
    LAPACK's "cholesky" for every K."""
    if not unroll:
        return "cholesky"
    return "unrolled" if k <= _UNROLL_MAX_K else "blocked"


def solve_gram_arrays(k: int, unroll: bool) -> float:
    """How many ``[rows, K, K]`` float32 arrays ``batched_spd_solve`` holds at
    its peak on the path it takes, the Gram it was handed included: what a
    caller that sizes its batches (``parallel.als.block_plan``) counts.

    Read from ``memory_analysis`` of the ALS tail (Gram einsum, ridge, solve,
    cast) compiled for a described v5e, PR 27; ``tests/test_tpu_compile.py``
    holds the blocked count to it. A TPU stores a ``[K, K]`` matrix in whole
    rows of 128 lanes, so the counts are of padded arrays.

    - "blocked": the Gram, worked where it lies, the trailing matrix and a
      step's panel and product: 2.22 at rank 128 and 4,096 rows. A rank that
      is not whole blocks adds its padded copy (6.25 unpadded Grams at rank
      48).
    - "unrolled": the Gram and its copy with the rows on the lanes, 8.05
      unpadded Grams at rank 16 and 5.33 at 32.
    - "cholesky": the Gram and the factor ``lax.linalg.cholesky`` writes
      beside it, 2.0 (the triangular solves reuse the Gram's place)."""
    path = solve_path(k, unroll)
    if path == "cholesky":
        return 2.0
    if path == "unrolled":
        return 1.35 * LANES / k

    def lane_padded(width: int) -> float:
        return width * round_up(width, LANES) / (k * k)

    kp = round_up(k, _UNROLL_MAX_K)
    return 2.25 * lane_padded(kp) + (lane_padded(k) if kp != k else 0.0)


def batched_spd_solve(
    gram: jnp.ndarray,
    rhs: jnp.ndarray,
    jitter: float = 1e-6,
    unroll: bool | None = None,
):
    """Solve ``gram[b] @ x[b] = rhs[b]`` for a batch of SPD systems: the
    K x K normal equations of ALS (K = rank), float32, by Cholesky.

    Three paths, chosen from the platform and K (``solve_path``). On a TPU,
    up to K = 32: the factorisation and both substitutions unrolled over K,
    every step an elementwise op across the batch, so the batch rides the
    VPU lanes (``als-ml20m-r16.train-steady``, one v5e: 5.15 ms for 165,272
    rank-16 rows an iteration where the Gram of a Pallas custom call, rank on
    the lanes, had cost 243; PERF.md section 6, PR 25). On a TPU above 32:
    the same factorisation blocked (``_blocked_chol_solve``), its work
    ``highest``-precision matmuls: 1.4 to 1.7 us a rank-128 row where
    ``lax.linalg.cholesky`` + ``cho_solve`` took 25.6 (one v5e, PR 27).
    Anywhere else LAPACK's batched Cholesky, which on a CPU is ~8x FASTER
    than the unrolled graph (round-2 driver evidence: 0.42 -> 0.05 it/s at
    ML-20M scale). ``unroll=None`` decides from ``jax.default_backend()``;
    callers that compile for an explicit mesh (e.g. ``parallel.als``) pass
    the mesh platform instead, since the default backend need not match the
    target devices.

    A small jitter guards rows whose Gram is singular (entities with no
    interactions); their solution is ~0 because their rhs is 0.
    """
    k = gram.shape[-1]
    eye = jnp.eye(k, dtype=gram.dtype)
    gram = gram + jitter * eye
    if unroll is None:
        unroll = jax.default_backend() == "tpu"
    path = solve_path(k, unroll) if gram.ndim == 3 else "cholesky"
    if path == "unrolled":
        return _unrolled_chol_solve(gram, rhs)
    if path == "blocked":
        note_blocked_solve()
        return _blocked_chol_solve(gram, rhs)
    chol = cholesky(gram)
    return cho_solve((chol, True), rhs[..., None])[..., 0]


def _unrolled_chol_solve(gram: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """Batch-major Cholesky + triangular solves, fully unrolled over K.

    Layout rationale: a [R, K, K] batch with tiny K is lane-hostile on TPU
    (K pads to 128); every operation here is instead a [R]- or [R, K]-wide
    elementwise op, so the batch dim R rides the vector lanes.
    """
    k = gram.shape[-1]
    arange = jnp.arange(k)

    # Cholesky, left-looking by column: cols[j] is L[:, :, j] as [R, K]
    cols: list[jnp.ndarray] = []
    for j in range(k):
        s = gram[:, :, j]
        for p in range(j):
            s = s - cols[p] * cols[p][:, j : j + 1]
        d = jnp.sqrt(jnp.maximum(s[:, j], 1e-12))
        cols.append((s / d[:, None]) * (arange >= j)[None, :])
    diag = [cols[i][:, i] for i in range(k)]

    # forward solve L y = b
    ys: list[jnp.ndarray] = []
    for i in range(k):
        s = rhs[:, i]
        for p in range(i):
            s = s - cols[p][:, i] * ys[p]
        ys.append(s / diag[i])

    # back solve L^T x = y  (L^T[i, p] = L[p, i] = cols[i][:, p])
    xs: list[jnp.ndarray | None] = [None] * k
    for i in reversed(range(k)):
        s = ys[i]
        for p in range(i + 1, k):
            s = s - cols[i][:, p] * xs[p]
        xs[i] = s / diag[i]
    return jnp.stack(xs, axis=1)


@jax.jit
def _factor_and_invert(block_t: jnp.ndarray) -> jnp.ndarray:
    """``inv(L)`` of a batch of b x b SPD blocks ``D = L L'``, the rows of
    the batch on the last axis: ``block_t[i, j, r] = D[r, i, j]`` in, and
    ``out[i, c, r] = inv(L[r])[i, c]`` out.

    ``_unrolled_chol_solve``'s recurrence and guard, a column a step of a
    ``fori_loop`` (a b-wide body traced b times costs a program seconds of
    tracing and a minute of compiling): column ``j`` of L is ``D[:, j]`` less
    the earlier columns times their ``j``-th entries, over its square-rooted
    pivot; then row ``i`` of ``inv(L)`` by forward substitution into the
    identity. Every step is elementwise across the batch, and the loops'
    state stays in the chip's fast memory. Jitted so that a program lowers
    it once for the steps of a solve and not once a step.
    """
    b = block_t.shape[0]
    lane = jnp.arange(b)
    at = lambda x, i, axis: lax.dynamic_index_in_dim(x, i, axis, keepdims=False)  # noqa: E731
    put = lax.dynamic_update_index_in_dim

    def factor_column(j, carry):
        cols, pivots = carry  # cols[p, i] = L[i, p]; columns from j on still zero
        s = at(block_t, j, 1) - (cols * at(cols, j, 1)[:, None]).sum(0)
        pivot = jnp.sqrt(jnp.maximum(at(s, j, 0), 1e-12))
        column = jnp.where((lane >= j)[:, None], s / pivot[None], 0.0)
        return put(cols, column, j, 0), put(pivots, pivot, j, 0)

    cols, pivots = lax.fori_loop(
        0, b, factor_column, (jnp.zeros_like(block_t), jnp.zeros_like(block_t[0]))
    )

    def invert_row(i, inv):  # inv[p, c] = inv(L)[p, c]; rows from i on still zero
        unit = (lane == i).astype(block_t.dtype)[:, None]
        s = unit - (at(cols, i, 1)[:, None] * inv).sum(0)
        return put(inv, s / at(pivots, i, 0)[None], i, 0)

    return lax.fori_loop(0, b, invert_row, jnp.zeros_like(block_t))


def _matmul(spec: str, *operands: jnp.ndarray) -> jnp.ndarray:
    """The blocked solve's einsum: float32 in and out, six bf16 passes on the
    MXU. At its default precision (one pass) a half-step is 5e-3 off (PERF.md
    section 6), which is the limit of the benchmark's ``correct``."""
    return jnp.einsum(spec, *operands, precision="highest")


def _blocked_chol_solve(gram: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """Right-looking blocked Cholesky solve of ``[R, K, K]`` systems with one
    right-hand side each.

    K is padded to whole ``_UNROLL_MAX_K``-wide blocks with the identity (and
    zeros in ``rhs``): the padding factors to itself and solves to zero.

    Upper form, ``A = U' U`` with ``U = L'``, so that a step's panel is b
    whole rows of the matrix: K stays on the lanes. Step ``j`` factors the
    b x b diagonal block of the trailing matrix and inverts the factor
    (``_factor_and_invert``, rows on the lanes), forms the row panel
    ``U[j, j+1:] = inv(L_jj) A[j, j+1:]`` and takes ``panel' panel`` off the
    trailing matrix: batched matmuls at ``highest`` precision (``_matmul``),
    over 85% of a system's flops. The right-hand side goes forward with the
    steps (``y_j = inv(L_jj) b_j``, ``b[j+1:] -= panel' y_j``) and comes back
    through the stored panels and inverses: ``x_j = inv(L_jj)' (y_j -
    panel x[j+1:])``.
    """
    k = rhs.shape[-1]
    b = _UNROLL_MAX_K
    pad = -k % b
    if pad:
        gram = jnp.pad(gram, ((0, 0), (0, pad), (0, pad)))
        gram = gram + jnp.diag((jnp.arange(k + pad) >= k).astype(gram.dtype))
        rhs = jnp.pad(rhs, ((0, 0), (0, pad)))
    steps = (k + pad) // b
    a, y = gram, rhs
    inverses, panels, ys = [], [], []
    for j in range(steps):
        inv_t = _factor_and_invert(jnp.transpose(a[:, :b, :b], (1, 2, 0)))
        inverse = jnp.transpose(inv_t, (2, 0, 1))             # [R, b, b], lower
        inverses.append(inverse)
        ys.append(_matmul("rck,rk->rc", inverse, y[:, :b]))
        if j == steps - 1:
            break
        panel = _matmul("rck,rki->rci", inverse, a[:, :b, b:])  # [R, b, K - (j+1) b]
        panels.append(panel)
        a = a[:, b:, b:] - _matmul("rci,rcj->rij", panel, panel)
        y = y[:, b:] - _matmul("rci,rc->ri", panel, ys[j])
    x = _matmul("rkc,rk->rc", inverses[-1], ys[-1])
    for j in reversed(range(steps - 1)):
        s = ys[j] - _matmul("rci,ri->rc", panels[j], x)
        x = jnp.concatenate([_matmul("rkc,rk->rc", inverses[j], s), x], axis=1)
    return x[:, :k]
