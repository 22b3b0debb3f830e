"""Alternating Least Squares on the device mesh.

The TPU-native replacement for MLlib ALS (reference call site: the
recommendation template's ``ALSAlgorithm.train`` -> ``org.apache.spark.mllib
.recommendation.ALS``, SURVEY.md section 2.6/3.1 -- Spark dep, not repo
code). Design anchor: ALX (arxiv 2112.02194, PAPERS.md), "ALS on TPUs":

- interactions live as padded CSR blocks (``ops.ragged``): static shapes,
  gathers instead of ragged loops;
- rows are LENGTH-BUCKETED: each side's entities are relabeled into
  length-sorted slots and split into a few buckets, each bucket its own
  padded block with its own (much tighter) padded length. At ML-20M's
  history distribution one global pad length wastes ~25-35% of gather
  slots on padding; bucketing recovers most of that as iteration time.
  The opposite side's column ids are slot-mapped at pack time, so the
  device math never sees the permutation -- ``slot_of`` maps factors
  back to original entity order at the host boundary only;
- each half-step solves all rows' K x K normal equations as one batched
  Cholesky per bucket on the MXU: Gram via ``einsum`` over the padded
  gather, masked;
- sharding: every bucket's rows shard over the ``data`` mesh axis; the
  opposite-side factor matrix is replicated (XLA all-gathers it once per
  half-step -- the collective that replaces MLlib's factor-block shuffle);
- implicit-feedback mode (MLlib ``trainImplicit`` parity) uses the YtY trick:
  the global Gram is one replicated K x K matmul + per-row corrections over
  observed entries only.

Explicit objective:  sum_obs (r - u.v)^2 + lam * (|U|^2 + |V|^2)
Implicit objective (Hu-Koren-Volinsky): confidence c = 1 + alpha*r on
observed pairs, preference p = 1; unobserved pairs have c = 1, p = 0.
"""

from __future__ import annotations

import functools
import logging
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import solve_triangular
from jax.sharding import NamedSharding, PartitionSpec

from predictionio_tpu.ops.linalg import (
    BLOCKED_SOLVE_ROWS, LANES, batched_spd_solve, solve_gram_arrays, solve_path)
from predictionio_tpu.ops.ragged import PaddedCSR, pack_padded_csr, round_up
from predictionio_tpu.parallel.mesh import cached_by_mesh, one_step_in_flight
from predictionio_tpu.utils.jax_compat import axis_size, shard_map


logger = logging.getLogger("pio.als")

#: Device scopes of one iteration (``jax.named_scope``; metadata only, no
#: operation). Every device operation of ``_build_iteration``'s program falls
#: under ``als.<side>_half_step/bucket<i>/<stage>`` or, outside the buckets,
#: ``als.<side>_half_step/assemble``. A profiler trace or the compiled text
#: carries them as each instruction's ``op_name``; other components (``jit(
#: iteration)``, ``shard_map``) may sit between these.
SCOPE_HALF_STEP = {"user": "als.user_half_step", "item": "als.item_half_step"}
SCOPE_BUCKET = "bucket{}"
#: forming a row's system: the gather (with its model-axis exchange) and the
#: two einsums or, in the dual form, the whitening and the ``[L, L]`` system
SCOPE_GRAM = "gram"
#: leaves under ``gram``, by class of operation: the gather of the opposite
#: side's rows from the table (in the model layout the local hits and their
#: mask; the ``exchange`` that completes them is ``gram``'s own child, beside
#: this), and the matmuls that make a row's system from the gathered rows (the
#: Gram and right-hand-side einsums; in a dual block the whitening, ``T``, ``S``)
SCOPE_GATHER = "gather"
SCOPE_PRODUCTS = "products"
#: ridge, ``ops.linalg.batched_spd_solve``, the cast back to the factor dtype
#: (and the dual form's projection back to ``[K]``)
SCOPE_SOLVE = "solve"
#: the zero row, the replicated constraint, YtY, and putting the buckets'
#: rows together
SCOPE_ASSEMBLE = "assemble"
#: nested where the work is, under ``gram`` or ``assemble``: what crosses the
#: chips in the model layout -- the exchange over ``model`` that completes a
#: bucket's gathered rows, and the re-layout of the solved rows from
#: ``P(("data", "model"))`` back to ``P("model")``
SCOPE_EXCHANGE = "exchange"
#: nested under ``assemble``: the side's global K x K Gram and the whitening
#: matrix made from it (implicit only; ``_shared_gram``)
SCOPE_YTY = "yty"


@dataclass
class ALSConfig:
    rank: int = 16
    iterations: int = 10
    reg: float = 0.1           # lambda (MLlib: lambda_)
    alpha: float = 40.0        # implicit confidence scale
    implicit: bool = False
    seed: int = 0
    max_len: int | None = None  # per-row history cap (SURVEY 5.7)
    dtype: str = "float32"     # factor dtype; Grams always accumulate f32
    buckets: int = 1           # length buckets per side (1 = single block)
    #: "replicated": the opposite-side factor matrix is all-gathered whole
    #: per half-step (fine while a catalog fits one device's HBM).
    #: "model": ALX block model-parallelism -- factors shard over the
    #: ``model`` mesh axis, each device gathers only its local hits, and a
    #: psum_scatter over ``model`` completes the sum; per-device factor
    #: memory drops to total_slots/model_axis rows (see docs/parallelism.md
    #: for the max-catalog math). Requires build_als_data(model_shards=m).
    factor_sharding: str = "replicated"
    #: a vestige: nothing in the package branches on it. There is one
    #: half-step, the einsum tail (``resolve_solver`` refuses any other name).
    #: Kept because benchmarks/drivers/als_train.py:92 calls
    #: ``resolve_solver(config.solver, platform)`` and als_train_sharded.py:125
    #: prints ``config.solver``; goes with those two reads (ROADMAP.md).
    solver: str = "auto"


@dataclass
class BucketedCSR:
    """One side's interactions as length-bucketed padded CSR blocks.

    Block ``b`` covers factor-matrix slots ``[offset_b, offset_b +
    padded_rows_b)``; real rows are deterministically SCATTERED across the
    block's padded range (multi-host load balance -- see _plan_buckets),
    padding rows carry zero mask wherever they fall. ``slot_of[original_
    id]`` is the factor row the entity occupies; built with ``buckets=1``
    the slot map is the identity and the single block equals the
    pre-bucketing layout.
    ``indices`` entries are the OPPOSITE side's slots; padding slots carry
    the sentinel ``opposite.total_slots`` (callers append one zero row to
    the gathered factor matrix so padding gathers stay in-bounds).
    """

    blocks: tuple[PaddedCSR, ...]
    slot_of: np.ndarray  # int64 [num_rows]: original row id -> factor slot
    num_rows: int        # real (original) row count
    total_slots: int     # sum of the blocks' padded row counts
    #: set by the SHARDED reader (parallel.reader): blocks then hold only
    #: this process's data-axis rows and these are the GLOBAL per-bucket
    #: padded row counts used to assemble the device arrays via
    #: make_array_from_process_local_data. None = blocks are global.
    global_rows: tuple[int, ...] | None = None
    #: edges this process retained after the partitioned scan (the
    #: memory-scaling evidence the sharded-reader tests assert on)
    retained_edges: int = 0

    @property
    def truncated(self) -> int:
        return sum(b.truncated for b in self.blocks)

    @property
    def padded_slots(self) -> int:
        """Total gather slots (the quantity bucketing minimizes)."""
        return sum(int(np.prod(b.indices.shape)) for b in self.blocks)

    def _single(self) -> PaddedCSR:
        if len(self.blocks) != 1:
            raise ValueError(
                "flat accessors are only defined for single-bucket data; "
                f"this side has {len(self.blocks)} buckets"
            )
        return self.blocks[0]

    # single-bucket compatibility accessors (tests / direct kernel drivers)
    @property
    def indices(self) -> np.ndarray:
        return self._single().indices

    @property
    def values(self) -> np.ndarray:
        return self._single().values

    @property
    def mask(self) -> np.ndarray:
        return self._single().mask


@dataclass
class ALSData:
    """Both orientations of the interaction matrix, padded for the mesh."""

    by_row: BucketedCSR  # users x items
    by_col: BucketedCSR  # items x users


@dataclass
class _BucketPlan:
    order: np.ndarray      # original ids in slot order (real rows only)
    sizes: list[int]       # real rows per bucket
    offsets: list[int]     # first slot of each bucket
    slot_of: np.ndarray    # [num_rows]
    total_slots: int
    lengths: list[int]     # padded L per bucket (every process must agree)

    @property
    def padded_rows(self) -> list[int]:
        ends = self.offsets[1:] + [self.total_slots]
        return [e - o for o, e in zip(self.offsets, ends)]


def _plan_buckets(
    counts: np.ndarray,
    cap: int | None,
    n_buckets: int,
    row_multiple: int,
    len_multiple: int = 8,
) -> _BucketPlan:
    """Partition rows into <=``n_buckets`` length buckets minimizing the
    total padded slot count sum_b padded_rows_b * padded_len_b.

    Rows are sorted by (capped) length descending; candidate cut points
    are the positions where the 8-rounded length drops (<= cap/8 + 1 of
    them, so the exact DP over candidates is tiny). Using FEWER buckets
    than allowed is considered too: each bucket pays a row-roundup tax.
    """
    n = counts.size

    def padded_len(raw: int) -> int:
        capped_max = min(raw, cap) if cap else raw
        return max(round_up(capped_max, len_multiple), len_multiple)

    if n_buckets <= 1 or n <= 1:
        total = max(round_up(max(n, 1), row_multiple), row_multiple)
        return _BucketPlan(
            order=np.arange(n, dtype=np.int64),
            sizes=[n],
            offsets=[0],
            slot_of=np.arange(n, dtype=np.int64),
            total_slots=total,
            lengths=[padded_len(int(counts.max()) if n else 0)],
        )

    capped = np.minimum(counts, cap) if cap else counts
    order = np.argsort(-capped, kind="stable").astype(np.int64)
    rounded = np.maximum(
        ((capped[order] + len_multiple - 1) // len_multiple) * len_multiple,
        len_multiple,
    )
    cuts = list(np.nonzero(np.diff(rounded) != 0)[0] + 1)
    cand = [0] + cuts + [n]
    if len(cand) > 66:  # cap DP size for absurd max_len; keep ends exact
        step = (len(cand) - 2) // 64 + 1
        cand = [0] + cand[1:-1][::step] + [n]

    def seg_cost(i: int, j: int) -> int:
        rows = cand[j] - cand[i]
        return round_up(rows, row_multiple) * int(rounded[cand[i]])

    m = len(cand) - 1
    inf = float("inf")
    dp = [[inf] * (m + 1) for _ in range(n_buckets + 1)]
    back: list[list[int]] = [[0] * (m + 1) for _ in range(n_buckets + 1)]
    dp[0][0] = 0.0
    for b in range(1, n_buckets + 1):
        for j in range(1, m + 1):
            for i in range(j):
                if dp[b - 1][i] == inf:
                    continue
                cost = dp[b - 1][i] + seg_cost(i, j)
                if cost < dp[b][j]:
                    dp[b][j] = cost
                    back[b][j] = i
    b_best = min(range(1, n_buckets + 1), key=lambda b: dp[b][m])
    bounds = [m]
    b, j = b_best, m
    while b > 0:
        j = back[b][j]
        bounds.append(j)
        b -= 1
    bounds.reverse()  # candidate indices 0 = start .. m = end

    sizes, offsets, lengths = [], [], []
    slot_of = np.empty(n, dtype=np.int64)
    off = 0
    for b, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
        size = cand[hi] - cand[lo]
        sizes.append(size)
        offsets.append(off)
        lengths.append(int(rounded[cand[lo]]))
        # deterministic scatter over the bucket's WHOLE padded range: in
        # length-sorted front-packed order, every bucket's heaviest rows
        # (and all its real rows, when padding is substantial) would land
        # in the FIRST contiguous data shards -- process 0 of a multi-host
        # mesh would retain most of the edge set. Scattering costs nothing
        # (the padded length is the bucket's, order-independent), keeps
        # the slot map a plan-level fact every process derives identically
        # from the same counts, and balances both edge retention and
        # per-shard solve work.
        padded_b = max(round_up(size, row_multiple), row_multiple)
        perm = np.random.default_rng(0x5EED + b).permutation(padded_b)[:size]
        slot_of[order[cand[lo] : cand[hi]]] = off + perm
        off += padded_b
    return _BucketPlan(
        order=order, sizes=sizes, offsets=offsets, slot_of=slot_of,
        total_slots=off, lengths=lengths,
    )


def _pack_side(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    times: np.ndarray | None,
    plan: _BucketPlan,
    opp_total_slots: int,
    opp_slot_of: np.ndarray,
    cap: int | None,
    row_multiple: int,
) -> BucketedCSR:
    """Pack one orientation into its bucket blocks (slot-mapped columns)."""
    row_slots = plan.slot_of[rows]
    cols_slotted = opp_slot_of[cols]
    blocks = []
    for off, padded, length in zip(
        plan.offsets, plan.padded_rows, plan.lengths
    ):
        sel = (row_slots >= off) & (row_slots < off + padded)
        blocks.append(
            pack_padded_csr(
                row_slots[sel] - off,
                cols_slotted[sel],
                vals[sel],
                num_rows=padded,
                num_cols=opp_total_slots,
                max_len=cap,
                times=None if times is None else times[sel],
                row_multiple=row_multiple,
                pad_len=length,
            )
        )
    return BucketedCSR(
        blocks=tuple(blocks),
        slot_of=plan.slot_of,
        num_rows=int(plan.slot_of.shape[0]),
        total_slots=plan.total_slots,
    )


def build_als_data(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    num_users: int,
    num_items: int,
    config: ALSConfig,
    times: np.ndarray | None = None,
    num_shards: int = 1,
    model_shards: int = 1,
) -> ALSData:
    """Pack COO interactions into both (bucketed) CSR orientations.

    Every bucket's row count is padded to a multiple of
    8 * num_shards * model_shards so each data shard is equal AND
    lane-aligned, and (``factor_sharding="model"``) each data shard splits
    evenly again over the model axis; with ``config.buckets == 1`` and the
    default shard counts the layout (and therefore the math and the
    seed-for-seed results) is exactly the historical single-block one.
    """
    users = np.asarray(users, dtype=np.int64)
    items = np.asarray(items, dtype=np.int64)
    ratings = np.asarray(ratings, dtype=np.float32)
    # ids beyond the declared catalog are an encoder/count mismatch; fail
    # HERE (np.bincount would silently grow the entity universe and hand
    # back a wrong-shaped factor model far from the cause)
    for ids, declared, what in ((users, num_users, "user"),
                                (items, num_items, "item")):
        if ids.size and int(ids.max()) >= declared:
            raise ValueError(
                f"{what} id {int(ids.max())} out of range for "
                f"num_{what}s={declared}"
            )
    rm = 8 * max(num_shards, 1) * max(model_shards, 1)
    nb = max(int(config.buckets), 1)
    plan_u = _plan_buckets(
        np.bincount(users, minlength=num_users), config.max_len, nb, rm
    )
    plan_i = _plan_buckets(
        np.bincount(items, minlength=num_items), config.max_len, nb, rm
    )
    by_row = _pack_side(
        users, items, ratings, times, plan_u,
        plan_i.total_slots, plan_i.slot_of, config.max_len, rm,
    )
    by_col = _pack_side(
        items, users, ratings, times, plan_i,
        plan_u.total_slots, plan_u.slot_of, config.max_len, rm,
    )
    return ALSData(by_row=by_row, by_col=by_col)


def _factor_precision(dtype):
    """Matmul precision for einsums whose operands are both factor-typed.

    f32 operands need "highest" (stops XLA lowering them to bf16 passes on
    TPU); bf16 operands are already exact in a single MXU pass with f32
    accumulation, and "highest" would force 3-pass emulation for nothing.
    """
    return "highest" if dtype == jnp.float32 else None


def _finish_explicit(gram, rhs, n_obs, reg, rank, unroll, out_dtype):
    """ALS-WR ridge + batched solve over precomputed Gram/rhs."""
    # MLlib-style weighted regularization: lambda * n_obs (ALS-WR); constant
    # lambda would also be defensible -- n_obs matches the reference template
    with jax.named_scope(SCOPE_SOLVE):
        ridge = reg * jnp.maximum(n_obs, 1.0)
        gram = gram + ridge[:, None, None] * jnp.eye(rank, dtype=gram.dtype)
        return batched_spd_solve(gram, rhs, unroll=unroll).astype(out_dtype)


def _finish_implicit(gram_fix, rhs, yty, reg, rank, unroll, out_dtype):
    """YtY + correction + constant ridge + solve.

    ``gram_fix`` holds only the per-row observed-entry corrections
    sum_obs (c-1) y y^T; the replicated global Gram lands here."""
    with jax.named_scope(SCOPE_SOLVE):
        gram = yty[None] + gram_fix + reg * jnp.eye(rank, dtype=yty.dtype)
        return batched_spd_solve(gram, rhs, unroll=unroll).astype(out_dtype)


def _gram_solve_explicit(gathered, values, n_obs, reg, rank, unroll, out_dtype):
    """Gram + ALS-WR ridge + rhs + batched solve over pre-gathered factors.

    PADDING INVARIANT (what lets the mask array stay on the host): padding
    slots' ``gathered`` rows are zero (their ``indices`` point at a zero
    factor row -- the appended trailing row in replicated mode, any
    out-of-shard index in model-sharded mode) and pack_padded_csr writes
    zero ``values`` into padding slots. Every padding contribution to the
    Gram and rhs therefore dies through the gathered zeros -- no ``[R, L]``
    mask stream over HBM, no ``[R, L, K]`` mask multiply over the largest
    intermediate. Only the per-row observation count ``n_obs`` (for ALS-WR
    regularization) survives to the device, as an ``[R]`` vector.

    Mixed precision, ALX-style: ``gathered`` may be bf16 (half the HBM
    traffic for the gather and half the ICI traffic for the collective;
    bf16 inputs are the MXU's native mode), while the Gram/rhs accumulate
    in f32 and the normal-equation solve runs in f32; the solution is cast
    back to ``out_dtype`` on return. ``reg`` may be a traced scalar (the
    iteration program is shared across regularization values).
    """
    with jax.named_scope(SCOPE_GRAM), jax.named_scope(SCOPE_PRODUCTS):
        gram = jnp.einsum(
            "rlk,rlj->rkj", gathered, gathered,
            precision=_factor_precision(gathered.dtype),
            preferred_element_type=jnp.float32,
        )
        rhs = jnp.einsum(
            "rlk,rl->rk", gathered, values,
            precision="highest", preferred_element_type=jnp.float32,
        )
    return _finish_explicit(gram, rhs, n_obs, reg, rank, unroll, out_dtype)


#: An implicit block whose padded length L is at most the rank over this takes
#: the dual form of its rows' systems (``_dual_solve_implicit``): ``2 * L <=
#: K``. One v5e, rank 128, bf16 table, one block through the half-step in
#: chunks of 4,096 rows, ms a call primal against dual (PERF.md section 6, PR
#: 29): 16 slots x 52,320 rows 76.7 / 5.6; 24 x 115,584 169.4 / 23.1; 48 x
#: 19,248 27.1 / 12.4; 56 x 76,848 114.0 / 56.8; 64 x 16,384 33.3 / 23.3; 96 x
#: 16,384 42.1 / 37.5; 128 x 8,192 25.0 / 25.9 to 29.0. At the line the dual
#: takes 70% of the primal's time; past it the margin thins (89% at three
#: quarters of the rank) and at L = K it is gone, and no listed cell has a
#: block between 64 and 128 slots at rank 128 to judge a later line by.
DUAL_RANK_OVER_LEN = 2


def takes_dual(implicit: bool, pad_len: int, rank: int) -> bool:
    """Whether a block of ``pad_len`` slots a row is solved in the dual form:
    a static test on the block's shape, made as the program is traced, by the
    tail, by ``block_plan`` and by ``block_paths`` alike. Explicit blocks never
    are (ALS-WR's ridge differs by row, so nothing is shared to whiten with).
    """
    return implicit and DUAL_RANK_OVER_LEN * pad_len <= rank


def _gram_solve_implicit(gathered, values, shared, reg, alpha, rank, unroll, out_dtype):
    """Hu-Koren-Volinsky implicit tail with the YtY trick.

    G = YtY + sum_obs (c-1) y y^T + lam*I ; rhs = sum_obs c * y
    Same mixed-precision contract and padding invariant as the explicit
    tail: padding slots carry zero gathered rows and zero values, so every
    padding term dies without a mask (``(1 + c-1) * y`` at a padding slot
    multiplies the gathered zero row). Implicit mode uses constant lambda
    (MLlib trainImplicit parity), so no n_obs.

    ``shared`` is ``_shared_gram``'s pair, made once a half-step: ``yty`` for
    this, the primal form, and ``whiten`` for the dual form, which a block
    short against the rank takes (``takes_dual``): the same equation by a
    matrix identity, an ``[L, L]`` system a row in place of a ``[K, K]`` one.
    """
    yty, whiten = shared
    if takes_dual(True, gathered.shape[1], rank):
        return _dual_solve_implicit(gathered, values, whiten, alpha, unroll, out_dtype)
    with jax.named_scope(SCOPE_GRAM), jax.named_scope(SCOPE_PRODUCTS):
        conf_minus_1 = alpha * values
        gram_fix = jnp.einsum(
            "rlk,rl,rlj->rkj", gathered, conf_minus_1, gathered,
            precision="highest", preferred_element_type=jnp.float32,
        )
        rhs = jnp.einsum(
            "rlk,rl->rk", gathered, (1.0 + conf_minus_1),
            precision="highest", preferred_element_type=jnp.float32,
        )
    return _finish_implicit(gram_fix, rhs, yty, reg, rank, unroll, out_dtype)


def _dual_solve_implicit(gathered, values, whiten, alpha, unroll, out_dtype):
    """The implicit tail in its dual (Woodbury) form, for rows of few slots.

    A row's system is ``(A + U C U') x = U w``: ``A = YtY + lam I``, shared by
    every row of the half-step; ``U`` the row's ``L`` gathered factors as
    columns; ``C = diag(alpha r)``; ``w = 1 + alpha r``. By the push-through
    identity ``(A + U C U')^-1 U = A^-1 U (I + C T)^-1`` with ``T = U' A^-1
    U``, so ``x = A^-1 U t`` with ``t = (I + C T)^-1 w``: one ``[L, L]`` system
    a row, no ``[K, K]`` Gram. Nothing is approximated.

    - ``whiten`` is ``inv(chol(A))`` (``_shared_gram``), so ``A^-1 = whiten'
      whiten``: the gathered rows are whitened by one large matmul, ``Ut = G
      whiten'``, and ``T = Ut Ut'`` is symmetric positive semi-definite as
      computed.
    - ``I + C T`` is made symmetric by ``D = sqrt(C)``: ``S = I + D T D``, SPD
      with every eigenvalue >= 1, solved by ``batched_spd_solve`` with no
      jitter (up to 32 slots unrolled with the rows on the lanes, above it
      blocked, off the TPU by LAPACK).
    - ``t = 1 + D S^-1 D (1 - T 1)``: multiply out ``(I + C T) t`` with ``D S
      D = C + C T C`` to get ``w``. No division by ``D``, so a slot of value
      zero (``t = 1`` there, as the primal form has it) and a padding slot
      (zero row and column of ``T``, ``S`` the identity there: the padding
      invariant, no mask) need nothing of their own. Values are confidences
      and not negative.
    - ``x = whiten' (Ut' t)``.

    Of three ways to take ``t`` this one was kept. Float32 against NumPy
    float64 at rank 128, 16 to 56 slots, play counts up to 9,667 at alpha 40
    (``alpha r`` 3.9e5), relative error of the solved rows (CPU, PR 29): this
    form 2.7e-7 to 3.2e-7; ``t = D S^-1 D^-1 w`` with ``D`` set to 1 where
    ``C`` is 0 the same, but a slot of value zero that is not padding then
    solves as if its confidence were 2; the subtractive ``t = w - D S^-1 D T
    w`` 7e-5 to 2.6e-4 (``w`` reaches 3.9e5 where ``t`` stays near ``T^-1
    1``, so it cancels). The primal form reads 1e-4 to 3e-4 on the same
    rows: its ``[K, K]`` system carries the condition number that ``S`` sheds.
    """
    pad_len = gathered.shape[1]
    with jax.named_scope(SCOPE_GRAM), jax.named_scope(SCOPE_PRODUCTS):
        white = jnp.einsum(
            "rlk,jk->rlj", gathered, whiten,
            precision="highest", preferred_element_type=jnp.float32,
        )
        cross = jnp.einsum("rlk,rmk->rlm", white, white, precision="highest")
        root = jnp.sqrt(alpha * values)
        system = (root[:, :, None] * cross * root[:, None, :]
                  + jnp.eye(pad_len, dtype=cross.dtype))
        rhs = root * (1.0 - cross.sum(axis=2))
    with jax.named_scope(SCOPE_SOLVE):
        weights = 1.0 + root * batched_spd_solve(system, rhs, jitter=0.0, unroll=unroll)
        back = jnp.einsum("rl,rlk->rk", weights, white, precision="highest")
        return jnp.einsum(
            "rk,kj->rj", back, whiten, precision="highest"
        ).astype(out_dtype)


def _shared_gram(factors, reg, implicit: bool = True):
    """What every row of one implicit half-step shares, computed ONCE per
    half-step by the caller (bucket-invariant: not once a bucket, not once a
    chunk) and handed to every block's tail: ``(yty, whiten)``.

    ``yty`` is the side's global factor Gram, the primal form's term.
    ``whiten`` is ``inv(L)`` for ``A = yty + lam I = L L'`` (float32 Cholesky
    of one K x K matrix), the dual form's (``_dual_solve_implicit``); ``A``
    carries ``batched_spd_solve``'s jitter, so that both forms solve the same
    system to the letter. A program none of whose blocks is dual drops it
    as dead code. Explicit mode feeds a dummy the steps drop.
    """
    k = factors.shape[1]
    if not implicit:
        return jnp.zeros((k, k), jnp.float32)
    with jax.named_scope(SCOPE_YTY):
        yty = jnp.einsum(
            "nk,nj->kj", factors, factors,
            precision=_factor_precision(factors.dtype),
            preferred_element_type=jnp.float32,
        )
        eye = jnp.eye(k, dtype=yty.dtype)
        chol = jnp.linalg.cholesky(yty + (reg + 1e-6) * eye)
        return yty, solve_triangular(chol, eye, lower=True)


def _half_step_explicit(indices, values, n_obs, factors, reg, rank, unroll):
    """Replicated-factor explicit half-step (gather + shared tail)."""
    with jax.named_scope(SCOPE_GRAM), jax.named_scope(SCOPE_GATHER):
        gathered = factors[indices]                   # [R, L, K]
    return _gram_solve_explicit(
        gathered, values, n_obs, reg, rank, unroll, factors.dtype
    )


def _half_step_implicit(indices, values, n_obs, factors, shared, reg, alpha,
                        rank, unroll):
    """Replicated-factor implicit half-step.

    ``n_obs`` is unused (constant lambda) but kept so both modes share one
    block layout. ``shared`` is ``_shared_gram``'s pair for the side,
    computed ONCE per half-step by the caller (it is bucket-invariant;
    computing it here would redo the [S, K] reduction for every bucket).
    """
    del n_obs
    with jax.named_scope(SCOPE_GRAM), jax.named_scope(SCOPE_GATHER):
        gathered = factors[indices]
    return _gram_solve_implicit(
        gathered, values, shared, reg, alpha, rank, unroll, factors.dtype
    )


def _sharded_block_body(idx, values, n_obs, opp_local, shared, reg, alpha,
                        implicit, rank, unroll):
    """Per-device half-step for one bucket with MODEL-SHARDED factors.

    Runs inside shard_map over the full ("data", "model") mesh. Each
    device holds opp_local = its model-axis shard of the opposite factor
    matrix ([S/m, K], replicated across the data axis) and the full local
    data-shard of the bucket's CSR rows. ``shared`` (implicit mode:
    ``_shared_gram``'s pair) arrives replicated from the caller -- it is
    bucket-invariant and was formerly re-psum'd here per bucket. The ALX
    block exchange:

    1. gather local hits only (out-of-shard indices -- including the
       padding sentinel, which is out of EVERY shard -- contribute zeros);
    2. an all_to_all over "model" hands each device the m partial copies
       of its 1/m slice of the rows, and their sum completes it (a
       reduce-scatter by hand: half the traffic of a psum, and the
       [rows, L, K] gathered intermediate shrinks by m. The TPU compiler
       turns ``psum_scatter`` of this array into pad + all-reduce + slice,
       twice the bytes and no ``op_name`` left for a trace: seen compiling
       the MSD rank-128 blocks for a described v5e 2x2, PR 26);
    3. each device solves its rows' normal equations -- compute scales
       with the full d*m device count, not just d.

    Output rows per device: the model-axis slice of the local data shard,
    i.e. global layout P(("data", "model")).
    """
    with jax.named_scope(SCOPE_GRAM):
        m = axis_size("model")
        mi = jax.lax.axis_index("model")
        s_m = opp_local.shape[0]
        loc = idx - mi * s_m
        rows = idx.shape[0] // m
        with jax.named_scope(SCOPE_GATHER):
            hit = (loc >= 0) & (loc < s_m)
            g = opp_local[jnp.clip(loc, 0, s_m - 1)]
            g = g * hit[..., None].astype(g.dtype)
        with jax.named_scope(SCOPE_EXCHANGE):
            # run j of the device's rows goes to device j of the model axis,
            # which adds up the m runs it receives (each slot hits one shard,
            # the others sent zeros: exact in any dtype)
            g = jax.lax.all_to_all(
                g.reshape((m, rows) + g.shape[1:]), "model", 0, 0
            ).sum(axis=0)
        val_s = jax.lax.dynamic_slice_in_dim(values, mi * rows, rows, 0)
    if implicit:
        return _gram_solve_implicit(
            g, val_s, shared, reg, alpha, rank, unroll, opp_local.dtype
        )
    with jax.named_scope(SCOPE_SOLVE):
        n_s = jax.lax.dynamic_slice_in_dim(n_obs, mi * rows, rows, 0)
    return _gram_solve_explicit(
        g, val_s, n_s, reg, rank, unroll, opp_local.dtype
    )


def _append_zero_row(factors: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate(
        [factors, jnp.zeros((1, factors.shape[1]), factors.dtype)], axis=0
    )


def resolve_solver(solver: str, platform: str) -> str:
    """Validate ``ALSConfig.solver``, the vestige of a selector: "auto" and
    "xla" are the einsum tail, the one half-step there is; any other name
    raises. Kept with the field for benchmarks/drivers/als_train.py:92, which
    calls it (ROADMAP.md)."""
    del platform
    if solver not in ("auto", "xla"):
        raise ValueError(
            f"ALSConfig.solver={solver!r}: the fused Gram kernel and the "
            "alsSolver selector were removed in PR 28; every block takes the "
            "einsum tail, in row chunks where it must (leave it at 'auto')"
        )
    return "xla"


#: Most bytes one row chunk of a block may allocate on one device
#: (``block_plan``): its lane-padded gathered rows, its float32 Grams and what
#: the solve holds beside them. A block over it is worked in equal row chunks,
#: each under it. A quarter of a v5e's 16 GiB, the smallest HBM this runs on.
#: Compiled for a described v5e (``memory_analysis``, PR 25): the einsum
#: program's temporaries are 1.07 to 1.27 times its largest block's gathered
#: rows (f32 explicit to bf16 implicit; 2.68 GB for the 2.31 GB of the ML-20M
#: cell's largest block, [35312, 256] bf16), its block streams another 1/32,
#: and the compiler refuses the program only past the whole chip (12 GiB of
#: gathered rows still compiles) -- so a quarter leaves the rest of the chip
#: to the state, to the other programs a process has loaded and to what the
#: compile of one program does not count. The recommendation template's
#: default packing (one bucket, no cap, f32) makes a [3712, 23832] item block
#: at MovieLens-1M: 45.3 GB of gathered rows, which the compiler refuses
#: whole (RESOURCE_EXHAUSTED) and the rule works in 11 chunks of 344 rows.
#: The floor of the rule: a chunk is at least 8 rows, so the budget holds for
#: rows of up to 1,048,576 slots at f32 and rank <= 128 (nine times MSD's
#: most-played song uncapped, 110,479); there is no code for more.
EINSUM_GATHER_BUDGET_BYTES = 4 << 30


def gathered_bytes(rows: int, pad_len: int, rank: int, itemsize: int) -> int:
    """HBM bytes of the einsum tail's ``[rows, pad_len, rank]`` gathered
    intermediate as a TPU holds it: the compiler lays the gathered factors
    out one row a lane row, whatever the rank (``bf16[R*L,16]{1,0:T(8,128)
    (2,1)}`` at rank 16), so a gather slot costs 128 lanes x itemsize, 8
    times the factors' own bytes at rank 16. ``rows`` are one device's."""
    return rows * pad_len * round_up(rank, LANES) * itemsize


def normal_equation_bytes(rows: int, rank: int, unroll: bool) -> int:
    """HBM bytes of ``rows`` rows' normal equations at their peak: the
    ``[rows, K, K]`` float32 Gram and what the solve holds beside it on the
    path it takes for (rank, ``unroll``: a TPU mesh), as
    ``ops.linalg.solve_gram_arrays`` counts it. 1 KiB a row and its copy,
    both lane-padded, at rank 16; 64 KiB a row and as much again and a
    quarter at rank 128."""
    return int(rows * rank * rank * 4 * solve_gram_arrays(rank, unroll))


def dual_block_bytes(rows: int, solved: int, pad_len: int, rank: int,
                     itemsize: int, unroll: bool) -> int:
    """HBM bytes of a block in the dual form (``_dual_solve_implicit``) at its
    peak, ``rows`` gathered and ``solved`` of them solved on one device: the
    float32 whitened rows, ``[solved, L, K]``, beside either the gathered
    rows they are made from or the ``[solved, L, L]`` systems made from them,
    whichever is more (the gathered rows are dead once whitened). A TPU pads
    ``L`` to whole rows of 128 lanes, so a system costs what a whitened row
    does; the unrolled solve works out of the one array, the blocked solve
    and LAPACK's hold a second. Compiled for a described v5e the temporaries
    are 0.92 to 1.12 times this (tests/test_tpu_compile.py, PR 29)."""
    whitened = gathered_bytes(solved, pad_len, rank, 4)
    held = 1 if solve_path(pad_len, unroll) == "unrolled" else 2
    systems = held * solved * pad_len * round_up(pad_len, LANES) * 4
    return whitened + max(gathered_bytes(rows, pad_len, rank, itemsize), systems)


#: Most rows of a dual block a TPU solves in one chunk: the blocked solve's
#: own 4,096, whichever solve the ``[L, L]`` systems take. One v5e, a dual
#: block of the MSD rank-128 cell alone (a device's solved rows of each), ms a
#: call by rows a chunk (PERF.md section 6, PR 29): 76,848 x 56 slots 1,011
#: 59.8, 2,022 54.7, 4,045 56.8, 7,685 77.3, 15,370 87.7; 115,584 x 24 1,022
#: 25.2, 2,027 20.4, 3,986 23.1, 7,705 20.7, 28,896 27.5, whole 37.8; 52,320 x
#: 16 2,012 5.1, 4,025 5.6, 7,474 7.4, 13,080 10.5, whole 7.3: a chunk's
#: whitened rows and systems stay in the chip's fast memory up to a few
#: thousand rows, and a block worked whole also compiles three times as long
#: (44.9 s against 16.7 at 24 slots).
DUAL_CHUNK_ROWS = BLOCKED_SOLVE_ROWS


def block_plan(platform: str, rows: int, pad_len: int, rank: int,
               itemsize: int, model_shards: int = 1, implicit: bool = False) -> int:
    """In how many equal row chunks ONE block is worked (1: whole) -- the one
    statement of the rule, asked at trace time by everything that has a
    block's static shape: ``rows`` on one device of the data axis,
    ``pad_len``, the factors' ``rank`` and ``itemsize``, ``model_shards``
    (the model layout solves ``rows / model_shards`` of them on each device; 1
    otherwise) and whether the half-step is ``implicit``.

    It counts what the block allocates -- the gathered rows
    (``gathered_bytes``), the float32 Grams and what the solve holds beside
    them (``normal_equation_bytes``) or, where the block ``takes_dual``, what
    that form holds (``dual_block_bytes``) -- and answers the number of chunks
    that brings one chunk's share under ``EINSUM_GATHER_BUDGET_BYTES``, and,
    on a TPU mesh, the rows a device solves in one chunk under
    ``ops.linalg.BLOCKED_SOLVE_ROWS`` where they take the blocked solve
    (above rank 32) and under ``DUAL_CHUNK_ROWS`` where the block is dual.
    Rows are independent, so a chunk's rows come out as they would from the
    whole block."""
    unroll = platform == "tpu"
    solved = rows // model_shards
    most_rows = None
    if takes_dual(implicit, pad_len, rank):
        allocated = dual_block_bytes(rows, solved, pad_len, rank, itemsize, unroll)
        if unroll:
            most_rows = DUAL_CHUNK_ROWS
    else:
        allocated = (gathered_bytes(rows, pad_len, rank, itemsize)
                     + normal_equation_bytes(solved, rank, unroll))
        if solve_path(rank, unroll) == "blocked":
            most_rows = BLOCKED_SOLVE_ROWS
    chunks = -(-allocated // EINSUM_GATHER_BUDGET_BYTES)
    if most_rows:
        chunks = max(chunks, -(-solved // most_rows))
    return max(1, chunks)


def _in_row_chunks(step, chunks: int, slices: int = 1, sharded: bool = False):
    """``step`` over ``chunks`` equal row chunks of one device's block, one
    after another (``lax.map``: one chunk's temporaries at a time), same
    signature and same rows out.

    The device's rows are ``slices`` contiguous runs (the model layout hands
    each device of the model axis one run of the solved rows; 1 otherwise):
    chunk ``c`` takes the ``c``-th piece of every run, so that what a device
    keeps over the chunks is its own run, in order. Runs are padded to a
    multiple of 8 * chunks rows with empty rows, dropped again on the way
    out. An empty row's slots all hold an index that gathers zeros (the
    padding invariant): the zero row the caller appended to a replicated
    table, or with ``sharded`` tables the first index past the last shard's
    rows, which is out of EVERY shard."""
    def chunked(idx, val, n_obs, table, shared, reg, alpha):
        run = idx.shape[0] // slices
        size = round_up(-(-run // chunks), 8)
        zero = slices * table.shape[0] if sharded else table.shape[0] - 1

        def split(x, fill):
            x = x.reshape((slices, run) + x.shape[1:])
            widths = [(0, 0), (0, chunks * size - run)] + [(0, 0)] * (x.ndim - 2)
            x = jnp.pad(x, widths, constant_values=fill)
            x = x.reshape((slices, chunks, size) + x.shape[2:])
            return jnp.moveaxis(x, 1, 0).reshape(
                (chunks, slices * size) + x.shape[3:]
            )

        out = jax.lax.map(
            lambda chunk: step(*chunk, table, shared, reg, alpha),
            (split(idx, zero), split(val, 0), split(n_obs, 0)),
        )
        return out.reshape((chunks * size, out.shape[-1]))[:run]

    return chunked


def block_paths(data, config: ALSConfig, mesh) -> dict[str, int]:
    """How ``data``'s blocks (both sides; resident or streamed) are worked in
    the program built for (mesh, config): ``"blocks"`` of them, of which
    ``"chunked"`` in row chunks, the most chunks of any under
    ``"max_chunks"`` (1: every block whole), ``"dual_solve"`` blocks whose
    rows are solved in the dual form (``takes_dual``: implicit blocks short
    against the rank) and ``"blocked_solve"`` blocks whose systems take the
    blocked Cholesky solve (``ops.linalg.solve_path``: on a TPU mesh, those
    wider than 32, be they ``[K, K]`` or a dual block's ``[L, L]``). The same
    rules the program asks at trace time, on the same shapes."""
    platform = mesh.devices.flat[0].platform
    d = mesh.shape["data"]
    m = mesh.shape.get("model", 1) if config.factor_sharding == "model" else 1
    itemsize = jnp.dtype(config.dtype).itemsize
    paths = {"blocks": 0, "chunked": 0, "max_chunks": 1, "blocked_solve": 0,
             "dual_solve": 0}
    for side in (data.by_row, data.by_col):
        specs = getattr(side, "specs", None)  # a streamed side's blocks
        if specs is not None:
            shapes = [(s.rows, s.pad_len) for s in specs]
        else:
            rows = side.global_rows or [b.indices.shape[0] for b in side.blocks]
            shapes = [(r, b.indices.shape[1]) for r, b in zip(rows, side.blocks)]
        for rows_b, pad_len in shapes:
            chunks = block_plan(platform, rows_b // d, pad_len, config.rank,
                                itemsize, m, config.implicit)
            dual = takes_dual(config.implicit, pad_len, config.rank)
            width = pad_len if dual else config.rank
            paths["blocks"] += 1
            paths["chunked"] += chunks > 1
            paths["max_chunks"] = max(paths["max_chunks"], chunks)
            paths["dual_solve"] += dual
            paths["blocked_solve"] += (
                solve_path(width, platform == "tpu") == "blocked")
    return paths


def _half_steps(mesh, implicit: bool, rank: int, factor_axis: str):
    """One bucket's half-step for (mesh, factor layout), chosen for each
    block as the program that holds it is traced.

    Returns ``pick(idx, factors) -> step``, with ``step(idx, values, n_obs,
    factors, shared, reg, alpha) -> rows`` (``shared``: ``_shared_gram``'s
    pair). ``block_plan`` decides the row chunks
    from the block's shape on one device (rows split over the data axis in
    both layouts). With replicated factors a block worked whole is left to
    GSPMD; the model-sharded body exchanges over ``model`` and a chunked block
    loops over its device's own rows, so those go through an explicit
    shard_map. The explicit tail drops ``shared`` and ``alpha`` (dummies and
    a scalar).
    """
    P = PartitionSpec
    platform = mesh.devices.flat[0].platform
    # solve-path choice is per TARGET platform, not default backend: the
    # benchmark compiles a CPU mesh while a TPU backend is live (and vice
    # versa), and the unrolled and blocked solves that win on a TPU lose to
    # LAPACK's batched Cholesky on a CPU (ops.linalg.batched_spd_solve).
    unroll = platform == "tpu"
    model = factor_axis == "model"
    slices = mesh.shape["model"] if model else 1

    def einsum_step(idx, val, n_obs, table, shared, reg, alpha):
        if implicit:
            return _half_step_implicit(
                idx, val, n_obs, table, shared, reg, alpha, rank, unroll
            )
        return _half_step_explicit(idx, val, n_obs, table, reg, rank, unroll)

    @functools.cache
    def build(chunks: int):
        if not model and chunks == 1:
            return einsum_step  # left to GSPMD
        body = einsum_step
        if model:
            body = functools.partial(
                _sharded_block_body, implicit=implicit, rank=rank, unroll=unroll
            )
        if chunks > 1:
            body = _in_row_chunks(body, chunks, slices, sharded=model)
        return shard_map(
            body,
            mesh=mesh,
            in_specs=(P("data", None), P("data", None), P("data"),
                      P("model", None) if model else P(), P(), P(), P()),
            out_specs=P(("data", "model") if model else "data", None),
            check_vma=model,
        )

    def pick(idx, factors):
        return build(block_plan(
            platform, idx.shape[0] // mesh.shape["data"], idx.shape[1], rank,
            factors.dtype.itemsize, slices, implicit,
        ))

    return pick


def make_iteration(mesh, config: ALSConfig):
    """The jitted full ALS iteration for (mesh, config) -- see _build_iteration.

    The returned callable takes the per-bucket CSR triples for both sides,
    the factor buffers, then the ``reg`` and ``alpha`` scalars (runtime
    values; the compiled program is shared across them). Bucket structure
    is part of jit's input signature, not the cache key: the same callable
    serves any bucket count (each distinct structure traces once).
    """
    if config.factor_sharding not in ("replicated", "model"):
        raise ValueError(
            "ALSConfig.factor_sharding must be 'replicated' or 'model', "
            f"got {config.factor_sharding!r}"
        )
    resolve_solver(config.solver, mesh.devices.flat[0].platform)
    return _build_iteration(
        mesh, config.rank, config.implicit, config.factor_sharding
    )


@cached_by_mesh(maxsize=32)
def _build_iteration(mesh, rank: int, implicit: bool,
                     factor_axis: str = "replicated"):
    """Build the jitted full ALS iteration (both half-steps fused).

    CSR rows (every bucket) shard over the 'data' mesh axis. Factor
    placement follows ``factor_axis``:

    - "replicated": factors live row-sharded over 'data' and are
      re-materialized replicated (+ zero pad row) INSIDE the jit, so the
      all-gather that replaces MLlib's factor-block shuffle is an
      on-device XLA collective, not a host round-trip.
    - "model": ALX block model-parallelism. Factors live row-sharded over
      the 'model' axis; each half-step runs as a shard_map over the full
      mesh doing local-hit gathers + a psum_scatter over 'model' (see
      _sharded_block_body). No device ever materializes a whole factor
      matrix: per-device factor memory is total_slots/m rows, which is
      what lifts the catalog-size ceiling from one device's HBM to the
      model axis's aggregate (docs/parallelism.md has the sizing math).

    Each bucket's half-step is the einsum tail, whole or in row chunks as
    ``block_plan`` says from its static shape as the program is traced
    (``_half_steps``). Implicit mode's ``yty`` and the dual form's whitening
    matrix (``_shared_gram``) are computed ONCE per half-step here
    (bucket-invariant) and fed to every bucket's solve.

    Factor buffers are donated: each iteration updates in place instead
    of reallocating.

    ``reg``/``alpha`` are RUNTIME scalars, not baked constants: a
    ``pio eval`` grid over lambda/alpha reuses one compiled program per
    (mesh, rank, mode) instead of paying a full XLA compile per candidate
    (about a minute each on a v5e). The remaining cache key
    covers repeated ``als_fit`` calls in one process (serving retrains,
    benchmarks).
    """
    P = PartitionSpec
    row = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    pick = _half_steps(mesh, implicit, rank, factor_axis)

    if factor_axis == "model":
        fsh = NamedSharding(mesh, P("model"))

        def iteration(u_blocks, i_blocks, users, items, reg, alpha):
            def solve_side(blocks, opp):
                # inter-bucket padding rows are zero and the sentinel is
                # out of every shard, so the full sharded [S, K] Gram is
                # the implicit global term (GSPMD psums it once per side)
                with jax.named_scope(SCOPE_ASSEMBLE):
                    shared = _shared_gram(opp, reg, implicit)
                outs = []
                for b, (idx, val, n_obs) in enumerate(blocks):
                    with jax.named_scope(SCOPE_BUCKET.format(b)):
                        step = pick(idx, opp)
                        outs.append(step(idx, val, n_obs, opp, shared, reg, alpha))
                with jax.named_scope(SCOPE_ASSEMBLE):
                    if len(outs) == 1:
                        # reshard P(("data","model")) -> P("model"): the
                        # all-gather over 'data' that readies this side
                        # for the next gather
                        with jax.named_scope(SCOPE_EXCHANGE):
                            return jax.lax.with_sharding_constraint(
                                outs[0], fsh
                            )
                    # multi-bucket assembly resharded PIECEWISE via
                    # dynamic_update_slice: jnp.concatenate of differently
                    # tuple-sharded bucket outputs followed by a reshard
                    # miscompiles under the legacy (0.4.x) GSPMD
                    # partitioner (values land in the wrong rows); updating
                    # each bucket's rows into a P("model") buffer keeps
                    # every reshard a single-array one, which partitions
                    # correctly on both APIs and lowers to the same
                    # all-gather traffic
                    total = sum(o.shape[0] for o in outs)
                    buf = jax.lax.with_sharding_constraint(
                        jnp.zeros((total, outs[0].shape[1]), outs[0].dtype),
                        fsh,
                    )
                    off = 0
                    for o in outs:
                        with jax.named_scope(SCOPE_EXCHANGE):
                            piece = jax.lax.with_sharding_constraint(o, fsh)
                        buf = jax.lax.dynamic_update_slice(
                            buf, piece, (off, 0)
                        )
                        off += o.shape[0]
                    return jax.lax.with_sharding_constraint(buf, fsh)

            with jax.named_scope(SCOPE_HALF_STEP["user"]):
                users = solve_side(u_blocks, items)
            with jax.named_scope(SCOPE_HALF_STEP["item"]):
                items = solve_side(i_blocks, users)
            return users, items

        return jax.jit(
            iteration,
            in_shardings=(row, row, fsh, fsh, rep, rep),
            out_shardings=(fsh, fsh),
            donate_argnums=(2, 3),
        )

    def iteration(u_blocks, i_blocks, users, items, reg, alpha):
        def solve_side(blocks, opp):
            with jax.named_scope(SCOPE_ASSEMBLE):
                opp_full = jax.lax.with_sharding_constraint(
                    _append_zero_row(opp), rep
                )
                shared = _shared_gram(opp_full[:-1], reg, implicit)
            outs = []
            for b, (idx, val, n_obs) in enumerate(blocks):
                with jax.named_scope(SCOPE_BUCKET.format(b)):
                    step = pick(idx, opp_full)
                    outs.append(
                        step(idx, val, n_obs, opp_full, shared, reg, alpha)
                    )
            with jax.named_scope(SCOPE_ASSEMBLE):
                out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
                return jax.lax.with_sharding_constraint(out, row)

        with jax.named_scope(SCOPE_HALF_STEP["user"]):
            users = solve_side(u_blocks, items)
        with jax.named_scope(SCOPE_HALF_STEP["item"]):
            items = solve_side(i_blocks, users)
        return users, items

    return jax.jit(
        iteration,
        in_shardings=(row, row, row, row, rep, rep),
        out_shardings=(row, row),
        donate_argnums=(2, 3),
    )


@dataclass
class ALSModel:
    user_factors: np.ndarray  # [num_users, K]
    item_factors: np.ndarray  # [num_items, K]
    #: lazily-built catalog norm cache -- similar_items is called once per
    #: anchor at serving time and must not rescan item_factors every call
    _item_norms: np.ndarray | None = field(
        default=None, init=False, repr=False, compare=False
    )
    #: lazily-built device retrieval indexes (``ops/mips.RetrievalIndex``),
    #: keyed by (kind, RetrievalConfig) -- see
    #: ``models/_als_common.retrieval_index``. Old pickled blobs predate
    #: this field; readers go through getattr with a default.
    _retrieval_cache: dict | None = field(
        default=None, init=False, repr=False, compare=False
    )

    def __getstate__(self):
        # device arrays + jitted programs must never enter a model blob:
        # the registry is the durability path, indexes rebuild at deploy
        state = self.__dict__.copy()
        state["_retrieval_cache"] = None
        return state

    def score_items_for_user(self, user_index: int) -> np.ndarray:
        # einsum, not @: BLAS sgemv picks its kernel by matrix height, so a
        # gathered-row product is a ULP off the full one -- einsum's per-row
        # reduction is height-independent, which lets the mips shortlist
        # re-rank (_als_common._host_rerank) reproduce these scores bitwise
        return np.einsum("ik,k->i", self.item_factors, self.user_factors[user_index])

    def score_users_for_item(self, item_index: int) -> np.ndarray:
        return self.user_factors @ self.item_factors[item_index]

    @property
    def item_norms(self) -> np.ndarray:
        if self._item_norms is None:
            self._item_norms = np.linalg.norm(self.item_factors, axis=1)
        return self._item_norms

    def similar_items(self, item_index: int) -> np.ndarray:
        """Cosine scores of all items against one (ALS-space similarity).

        einsum for the same reason as ``score_items_for_user``: the mips
        shortlist replays this row arithmetic and must land bitwise."""
        v = self.item_factors[item_index]
        norms = self.item_norms * (self.item_norms[item_index] + 1e-12)
        return np.einsum("ik,k->i", self.item_factors, v) / np.maximum(norms, 1e-12)


def device_put_blocks(side: BucketedCSR, put) -> tuple:
    """``put`` each bucket block as its device triple (indices, values,
    n_obs). The ``[R, L]`` mask never crosses the host link: the padding
    invariant (see _half_step_explicit) reduces it to the per-row
    observation count."""
    return tuple(
        (put(b.indices), put(b.values), put(b.mask.sum(axis=1)))
        for b in side.blocks
    )


def modeled_bytes_per_iteration(data, rank: int, itemsize: int) -> float:
    """HBM bytes one full ALS iteration moves through its half-step tails,
    summed over both sides' buckets (resident or streamed). The half-step is
    bandwidth-bound, so achieved GB/s against this model is the
    training-efficiency axis the ``--profile`` telemetry journal reports.

    A [rows, L] block: indices (i32) and values (f32) read once; Gram and rhs
    (f32) written once; the gather's random read of the factor table
    (rows*L*K*itemsize in expectation; the table's cold first touch is not
    modeled per block), the gathered [rows, L, K] intermediate written to HBM
    once and read back by the Gram and rhs einsums: 4 gather-sized passes."""
    total = 0.0
    for side in (data.by_row, data.by_col):
        specs = getattr(side, "specs", None)  # a streamed side's blocks
        if specs is not None:
            shapes = [(s.rows, s.pad_len) for s in specs]
        else:
            shapes = [b.indices.shape for b in side.blocks]
        for rows, pad_len in shapes:
            total += (
                rows * pad_len * (4 + 4)             # indices + values
                + rows * (rank * rank + rank) * 4    # gram + rhs, f32
                + 4 * rows * pad_len * rank * itemsize
            )
    return total


def real_edges(data: ALSData) -> int:
    """Real (unpadded) observations -- the edges/sec denominator. Sides
    built by the sharded reader hold only this process's rows; the count
    is then per-process, which is the per-chip rate ALX reports."""
    return int(sum(b.mask.sum() for b in data.by_row.blocks))


def _initial_side_factors(side, rank: int, seed: int) -> np.ndarray:
    """Seeded N(0, 1/sqrt(K)) init for one side, drawn in ORIGINAL entity
    order and scattered into factor slots: invariant to the bucket plan,
    to shard-count padding, AND to the resident-vs-streamed layout (both
    duck-type ``num_rows``/``total_slots``/``slot_of``); phantom rows stay
    zero (invisible to the implicit-mode global Gram)."""
    rng = np.random.default_rng(seed)
    scale = 1.0 / np.sqrt(rank)
    real = rng.normal(size=(side.num_rows, rank)) * scale
    out = np.zeros((side.total_slots, rank))
    out[side.slot_of] = real
    return out


def _scatter_side_init(side, host: np.ndarray) -> np.ndarray:
    """Checkpointed factors (original entity order) -> slot order."""
    out = np.zeros((side.total_slots, host.shape[1]), dtype=np.float64)
    out[side.slot_of] = np.asarray(host)[: side.num_rows]
    return out


def als_fit(
    data: ALSData,
    config: ALSConfig,
    mesh=None,
    callback=None,
    callback_interval: int = 1,
    init: tuple[np.ndarray, np.ndarray] | None = None,
    start_iteration: int = 0,
    telemetry=None,
) -> ALSModel:
    """Run ALS to convergence budget; returns host-side factor matrices.

    ``callback(iteration, user_factors, item_factors)`` runs every
    ``callback_interval`` iterations (skipping the final one, whose result
    als_fit returns anyway) with HOST numpy copies in ORIGINAL entity
    order (safe to retain -- the checkpointing hook; the on-device buffers
    are donated between iterations and must not escape). The interval
    lives HERE so non-callback iterations never pay the device sync + host
    copy that materializing the factors costs. ``init``/``start_iteration``
    resume from checkpointed factors (original order): the remaining
    iterations run, which is exact for ALS (each iteration depends only on
    the previous factors). ``mesh`` defaults to a 1-device local mesh.

    ``telemetry`` (``obs.telemetry.TrainTelemetry``) records one journal
    line per iteration: wall time, edges/sec, achieved GB/s vs the
    bytes-moved model, and the programs this process has compiled or loaded
    so far (steady after the first step). Per-step timing needs a device
    sync after EVERY iteration (a one-scalar fetch), which serializes the
    dispatch pipeline -- that cost is only paid when profiling is on;
    the un-profiled loop keeps its async chain.
    """
    from predictionio_tpu.obs.trace import global_tracer
    from predictionio_tpu.parallel.mesh import local_mesh

    tracer = global_tracer()

    mesh = mesh or local_mesh(1, 1)
    if config.dtype not in ("float32", "bfloat16"):
        # e.g. an integer dtype would truncate the N(0, 1/sqrt(K)) init to
        # all zeros -- a fixed point of the update -- and train a silently
        # degenerate model
        raise ValueError(
            f"ALSConfig.dtype must be 'float32' or 'bfloat16', got"
            f" {config.dtype!r}"
        )
    dtype = jnp.dtype(config.dtype)

    if init is not None:
        users0 = _scatter_side_init(data.by_row, init[0])
        items0 = _scatter_side_init(data.by_col, init[1])
    else:
        users0 = _initial_side_factors(data.by_row, config.rank, config.seed)
        items0 = _initial_side_factors(data.by_col, config.rank, config.seed + 1)

    from predictionio_tpu.parallel.mesh import fetch_global as fetch
    from predictionio_tpu.parallel.mesh import put_global

    row = NamedSharding(mesh, PartitionSpec("data"))
    # default path: every process loads the same event store; put_global
    # feeds each exactly its addressable row shards. Sides built by the
    # SHARDED reader (global_rows set) carry only this process's rows and
    # assemble via make_array_from_process_local_data -- no host ever held
    # the global edge set (SURVEY 2.6 DP row: host-side sharded reader).
    put_row = lambda a: put_global(a, row)

    def put_side(side: BucketedCSR):
        if side.global_rows is None:
            return device_put_blocks(side, put_row)
        return tuple(
            (
                jax.make_array_from_process_local_data(
                    row, b.indices, (rows, b.indices.shape[1])
                ),
                jax.make_array_from_process_local_data(
                    row, b.values, (rows, b.values.shape[1])
                ),
                jax.make_array_from_process_local_data(
                    row, b.mask.sum(axis=1), (rows,)
                ),
            )
            for b, rows in zip(side.blocks, side.global_rows)
        )

    with tracer.span(
        "als.transfer",
        attrs={"edges": data.by_row.retained_edges or None},
    ):
        # host->device CSR transfer: the step the device-resident-epochs
        # ROADMAP item wants to overlap; its span makes the cost visible
        u_blocks = put_side(data.by_row)
        i_blocks = put_side(data.by_col)

    if config.factor_sharding == "model":
        m = mesh.shape["model"]
        d = mesh.shape["data"]
        for side, name in ((data.by_row, "user"), (data.by_col, "item")):
            # sides built by the sharded reader hold only this process's
            # rows in their blocks; the divisibility guarantee (and the
            # device array shape) is on the GLOBAL per-bucket row counts
            rows_per_bucket = (
                side.global_rows
                if side.global_rows is not None
                else tuple(b.indices.shape[0] for b in side.blocks)
            )
            if side.total_slots % m or any(
                rows % (d * m) for rows in rows_per_bucket
            ):
                raise ValueError(
                    f"factor_sharding='model' needs every {name} bucket's "
                    f"padded rows divisible by data*model = {d}*{m}; build "
                    f"the data with build_als_data(..., num_shards={d}, "
                    f"model_shards={m})"
                )
        fsh = NamedSharding(mesh, PartitionSpec("model"))
    else:
        fsh = row
    user_factors = put_global(users0.astype(dtype), fsh)
    item_factors = put_global(items0.astype(dtype), fsh)

    iteration = make_iteration(mesh, config)
    # globally-replicated scalars: a process-local jnp scalar cannot feed a
    # jit whose sharding spans other processes' devices (multi-host train)
    from predictionio_tpu.parallel.mesh import replicated

    rep = replicated(mesh)
    reg = put_global(np.float32(config.reg), rep)
    alpha = put_global(np.float32(config.alpha), rep)

    def to_host(factors, side: BucketedCSR) -> np.ndarray:
        # f32 on the host regardless of the on-device factor dtype:
        # checkpoints and serving stay dtype-stable across bf16 runs
        return fetch(factors)[side.slot_of].astype(np.float32)

    if telemetry is not None:
        from predictionio_tpu.obs.telemetry import compiles_so_far

        def step_sync(x) -> None:
            # one-scalar fetch: a hard device sync; the donated-buffer
            # chain keeps it honest
            np.asarray(jax.device_get(x[:1, :1]))

    first_call_t0 = time.perf_counter()
    for it in range(start_iteration, config.iterations):
        if it == start_iteration + 1:
            # the first call returned once the program was traced and
            # compiled (or read from the persistent cache); running it is
            # asynchronous, so this is the compile share of the fit
            paths = block_paths(data, config, mesh)
            logger.info(
                "als_fit: platform=%s devices=%d mesh_data=%d mesh_model=%d"
                " factor_sharding=%s blocks=%d"
                " blocks_chunked=%d max_chunks=%d blocked_solve=%d"
                " dual_solve=%d"
                " first_call_s=%.2f (trace + compile, or cache load)",
                mesh.devices.flat[0].platform, mesh.devices.size,
                mesh.shape["data"], mesh.shape.get("model", 1),
                config.factor_sharding, paths["blocks"],
                paths["chunked"], paths["max_chunks"],
                paths["blocked_solve"], paths["dual_solve"],
                time.perf_counter() - first_call_t0,
            )
        if telemetry is not None:
            # per-half-step resolution lives inside one jitted program;
            # the per-iteration span + journal line (wall, edges/sec,
            # achieved GB/s) is the honest host-visible boundary
            with tracer.span("als.iteration", attrs={"step": it}):
                step_t0 = time.perf_counter()
                user_factors, item_factors = iteration(
                    u_blocks, i_blocks, user_factors, item_factors, reg, alpha
                )
                step_sync(user_factors)
                telemetry.record_step(
                    it,
                    time.perf_counter() - step_t0,
                    recompile_count=compiles_so_far(),
                )
        else:
            user_factors, item_factors = iteration(
                u_blocks, i_blocks, user_factors, item_factors, reg, alpha
            )
            one_step_in_flight(mesh, user_factors)
        if (
            callback is not None
            and (it + 1) % callback_interval == 0
            and it + 1 < config.iterations
        ):
            # host copies: the device buffers are donated into the next
            # iteration; handing them out would raise 'Array has been
            # deleted' one iteration later, far from the cause
            callback(
                it,
                to_host(user_factors, data.by_row),
                to_host(item_factors, data.by_col),
            )

    # serving model is always f32 host-side (numpy top-k math on bf16 via
    # ml_dtypes is slow and lossy; the dtype knob is a TRAINING layout)
    return ALSModel(
        user_factors=to_host(user_factors, data.by_row),
        item_factors=to_host(item_factors, data.by_col),
    )


# --------------------------------------------------------------------------
# device-resident epochs over streamed blocks (ALX, arxiv 2112.02194)
# --------------------------------------------------------------------------


class _StreamPrograms:
    """Jitted programs of one streamed-epoch configuration.

    ``prep`` runs ONCE per half-step (the loop-invariant hoist the J006
    lint encodes): it materializes the opposite side's replicated
    ``[S+1, K]`` gather table and implicit mode's ``_shared_gram``, so the
    per-block python loop re-ships NOTHING invariant -- each block step
    moves only that block's streams plus two 4-byte scalars (offset,
    uniform value). ``step(has_values)`` solves one block's rows and
    dynamic_update_slice's them into the DONATED side buffer: the factor
    table is updated in place and never leaves the device during the
    epoch. A half-step's solve never reads its own side, so in-place
    block updates are exact, not approximate.
    """

    def __init__(self, mesh, rank: int, implicit: bool, factor_axis: str):
        self.implicit = implicit
        self.factor_axis = factor_axis
        P = PartitionSpec
        row = NamedSharding(mesh, P("data"))
        rep = NamedSharding(mesh, P())
        pick = _half_steps(mesh, implicit, rank, factor_axis)

        if factor_axis == "model":
            fsh = NamedSharding(mesh, P("model"))
            self.prep = jax.jit(
                lambda opp, reg: (opp, _shared_gram(opp, reg, implicit)),
                in_shardings=(fsh, rep), out_shardings=(fsh, rep),
            )
            # single-array reshard P(("data","model")) -> P("model"): the
            # J005-safe assembly (no concat ever feeds a reshard)
            placed = lambda piece: jax.lax.with_sharding_constraint(piece, fsh)
            buf_sh = fsh
        else:
            fsh = row
            self.prep = jax.jit(
                lambda f, reg: (_append_zero_row(f), _shared_gram(f, reg, implicit)),
                in_shardings=(row, rep), out_shardings=(rep, rep),
            )
            placed = lambda piece: piece
            buf_sh = row

        self.factor_sharding = buf_sh

        def make_step(has_values: bool):
            def block_update(buf, idx, val_in, n_obs, opp, shared, reg, alpha, off):
                if has_values:
                    val = val_in
                else:
                    # uniform-value block: the value stream never crossed
                    # the host link. Exact, not lossy -- padding slots
                    # gather the appended zero factor row, so their value
                    # is don't-care (the module's padding invariant).
                    val = jnp.full(idx.shape, val_in, jnp.float32)
                if n_obs.ndim == 0:
                    # implicit mode never reads per-row counts (constant
                    # ridge): the driver ships a scalar placeholder and the
                    # [rows] vector materializes on device
                    n_obs = jnp.zeros((idx.shape[0],), jnp.float32)
                step = pick(idx, opp)
                rows = placed(step(idx, val, n_obs, opp, shared, reg, alpha))
                return jax.lax.dynamic_update_slice(buf, rows, (off, 0))

            val_sh = row if has_values else rep
            nob_sh = rep if implicit else row
            opp_sh = fsh if factor_axis == "model" else rep
            return jax.jit(
                block_update,
                in_shardings=(buf_sh, row, val_sh, nob_sh, opp_sh, rep,
                              rep, rep, rep),
                out_shardings=buf_sh,
                donate_argnums=(0,),
            )

        self._steps = {True: make_step(True), False: make_step(False)}

    def step(self, has_values: bool):
        return self._steps[has_values]


@cached_by_mesh(maxsize=32)
def _build_stream_programs(mesh, rank: int, implicit: bool,
                           factor_axis: str) -> _StreamPrograms:
    return _StreamPrograms(mesh, rank, implicit, factor_axis)


def als_fit_streamed(
    data,
    config: ALSConfig,
    mesh=None,
    callback=None,
    callback_interval: int = 1,
    init: tuple[np.ndarray, np.ndarray] | None = None,
    start_iteration: int = 0,
    telemetry=None,
    device_budget_bytes: int = 0,
    stats=None,
) -> ALSModel:
    """``als_fit`` restructured as ALX device-resident epochs.

    Both factor tables are placed on device ONCE (sharded per
    ``config.factor_sharding``) and stay resident across every half-step;
    the padded-CSR row blocks of ``data`` (a ``parallel.stream.
    StreamedALSData`` block store) stream host->device through a
    prefetch-1 feeder -- block N+1's ``device_put`` is in flight while the
    half-step kernel consumes block N -- and are dropped the moment their
    rows are solved. The ``[rows, L]`` host intermediate for a whole side
    never exists: peak host memory is O(block), which is what lifts the
    edge ceiling from "fits in RAM twice" to "fits on disk".

    Bit-identical to ``als_fit`` over ``build_als_data`` at equal shapes
    (same plans, same per-row packing, same half-step, same update order);
    the parity tests in ``tests/test_als_stream.py`` pin all whole/chunked x
    mode x dtype x sharding combinations.

    ``device_budget_bytes`` > 0 pins streamed blocks device-resident (in
    first-seen order) until the budget is exhausted: later iterations
    re-ship only the overflow. At ``0`` every iteration re-streams --
    predictable O(block) memory on hosts where "device" memory IS host
    RAM (the CPU box). ``stats`` (``parallel.stream.StreamStats``)
    receives the measured host->device traffic -- the evidence the bench's
    achieved-vs-modeled transfer metric reports.
    """
    import time as _time

    from predictionio_tpu.obs.trace import global_tracer
    from predictionio_tpu.parallel.mesh import (
        fetch_global,
        local_mesh,
        put_global,
        replicated,
    )
    from predictionio_tpu.parallel.stream import (
        FeedAccounting,
        StreamStats,
        prefetch_blocks,
    )

    tracer = global_tracer()
    mesh = mesh or local_mesh(1, 1)
    if config.dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"ALSConfig.dtype must be 'float32' or 'bfloat16', got"
            f" {config.dtype!r}"
        )
    if config.factor_sharding not in ("replicated", "model"):
        raise ValueError(
            "ALSConfig.factor_sharding must be 'replicated' or 'model', "
            f"got {config.factor_sharding!r}"
        )
    if jax.process_count() > 1:
        raise ValueError(
            "als_fit_streamed is single-process (the block store feeds "
            "local devices); multi-host training uses the sharded-reader "
            "resident path"
        )
    resolve_solver(config.solver, mesh.devices.flat[0].platform)
    dtype = jnp.dtype(config.dtype)
    implicit = bool(config.implicit)
    stats = stats if stats is not None else StreamStats()

    d = mesh.shape["data"]
    m = mesh.shape.get("model", 1)
    if config.factor_sharding == "model":
        for side, name in ((data.by_row, "user"), (data.by_col, "item")):
            if side.total_slots % m or any(
                s.rows % (d * m) for s in side.specs
            ):
                raise ValueError(
                    f"factor_sharding='model' needs every {name} block's "
                    f"rows divisible by data*model = {d}*{m}; build the "
                    f"block store with num_shards={d}, model_shards={m}"
                )
        fsh = NamedSharding(mesh, PartitionSpec("model"))
    else:
        if any(
            s.rows % d for side in (data.by_row, data.by_col)
            for s in side.specs
        ):
            raise ValueError(
                f"streamed blocks must shard evenly over the {d}-way data "
                f"axis; build the block store with num_shards={d}"
            )
        fsh = NamedSharding(mesh, PartitionSpec("data"))
    row = NamedSharding(mesh, PartitionSpec("data"))
    rep = replicated(mesh)

    if init is not None:
        users0 = _scatter_side_init(data.by_row, init[0])
        items0 = _scatter_side_init(data.by_col, init[1])
    else:
        users0 = _initial_side_factors(data.by_row, config.rank, config.seed)
        items0 = _initial_side_factors(
            data.by_col, config.rank, config.seed + 1
        )
    with tracer.span(
        "als.transfer", attrs={"edges": data.real_edges or None}
    ):
        # ONE factor placement per epoch sequence -- the device-resident
        # contract; everything else streams through the feeder below
        user_factors = put_global(users0.astype(dtype), fsh)
        item_factors = put_global(items0.astype(dtype), fsh)
    # loop-invariant scalars cross the host link exactly once per fit
    # (the hoisted shape the J006 lint pins)
    reg = put_global(np.float32(config.reg), rep)
    alpha = put_global(np.float32(config.alpha), rep)

    programs = _build_stream_programs(
        mesh, config.rank, implicit, config.factor_sharding
    )
    accounting = FeedAccounting()
    pinned: dict = {}
    budget_left = [int(device_budget_bytes)]

    def put_block(spec, host):
        idx, val, nobs = host
        idx_d = put_global(idx, row)
        moved = idx.nbytes
        if val is not None:
            val_d = put_global(val, row)
            moved += val.nbytes
        else:
            val_d = np.float32(spec.const)  # 4-byte scalar rides the call
            stats.h2d_scalar_bytes += 4
        if implicit:
            nobs_d = np.float32(0.0)  # scalar placeholder; see block_update
        else:
            nobs_d = put_global(nobs, row)
            moved += nobs.nbytes
        stats.h2d_block_bytes += moved
        return (idx_d, val_d, nobs_d), moved

    def feed(side, side_name):
        acquired: dict[int, bool] = {}

        def produce(spec):
            hit = pinned.get((side_name, spec.index))
            if hit is not None:
                stats.blocks_pinned += 1
                return hit
            accounting.acquire()
            acquired[spec.index] = True
            host = side.load_block(spec)
            dev, moved = put_block(spec, host)
            del host  # the feeder's two-block residency bound
            stats.blocks_streamed += 1
            if budget_left[0] >= moved:
                pinned[(side_name, spec.index)] = dev
                budget_left[0] -= moved
                stats.pinned_bytes += moved
            return dev

        def consumed(spec) -> None:
            if acquired.pop(spec.index, False):
                accounting.release()

        return prefetch_blocks(side.specs, produce, consumed)

    def solve_side(side, side_name, opp, buf):
        opp_arg, shared = programs.prep(opp, reg)
        for spec, (idx_d, val_d, nobs_d) in feed(side, side_name):
            step = programs.step(spec.const is None)
            buf = step(
                buf, idx_d, val_d, nobs_d, opp_arg, shared, reg, alpha,
                np.int32(spec.offset),
            )
            stats.h2d_scalar_bytes += 4  # the block offset scalar
            one_step_in_flight(mesh, buf)
        stats.half_steps += 1
        return buf

    def to_host(factors, side) -> np.ndarray:
        return fetch_global(factors)[side.slot_of].astype(np.float32)

    if telemetry is not None:
        from predictionio_tpu.obs.telemetry import compiles_so_far

        def step_sync(x) -> None:
            np.asarray(jax.device_get(x[:1, :1]))

    for it in range(start_iteration, config.iterations):
        if telemetry is not None:
            with tracer.span("als.iteration", attrs={"step": it}):
                step_t0 = _time.perf_counter()
                user_factors = solve_side(
                    data.by_row, "u", item_factors, user_factors
                )
                item_factors = solve_side(
                    data.by_col, "i", user_factors, item_factors
                )
                step_sync(user_factors)
                telemetry.record_step(
                    it,
                    _time.perf_counter() - step_t0,
                    recompile_count=compiles_so_far(),
                )
        else:
            user_factors = solve_side(
                data.by_row, "u", item_factors, user_factors
            )
            item_factors = solve_side(
                data.by_col, "i", user_factors, item_factors
            )
        if (
            callback is not None
            and (it + 1) % callback_interval == 0
            and it + 1 < config.iterations
        ):
            callback(
                it,
                to_host(user_factors, data.by_row),
                to_host(item_factors, data.by_col),
            )

    stats.max_inflight_blocks = accounting.max_live
    return ALSModel(
        user_factors=to_host(user_factors, data.by_row),
        item_factors=to_host(item_factors, data.by_col),
    )
