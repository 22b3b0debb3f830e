"""ALS fold-in: solve only new/touched user rows against frozen item factors.

ALX (arxiv 2112.02194) makes the point that the per-row ALS solve is
cheap: one K x K normal-equation system per row. Between full retrains,
that is exactly enough to keep a deployed factor model fresh -- a user who
just rated something gets their row re-solved against the CURRENT item
factors (one half-step over a delta CSR block, the tail ``parallel.als``
trains with), while every untouched row keeps its trained
factors bit-for-bit. New users append rows; new items append zero factors
(they score 0 until the next full retrain -- which the staleness budget
triggers once item-vocab growth makes zero rows matter).

Correctness contract (the parity test pins it): a folded user row equals
the exact ridge solution of that user's normal equations against the
frozen item factors -- which is precisely what a full retrain's final
user half-step computes, given the same item factors. Fold-in is therefore
idempotent over replayed windows (it re-solves from the user's FULL
history, not incrementally), which is what makes the loop's crash
recovery safe: re-running a window after a SIGKILL converges to the same
factors.
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass, field

import numpy as np

logger = logging.getLogger("pio.online.foldin")


class StalenessExceeded(Exception):
    """The delta outgrew the fold-in budget; escalate to a full retrain."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


@dataclass
class StalenessBudget:
    """When incremental fold-in stops being a good approximation.

    - ``max_touched_frac``: once this fraction of known users was touched
      since the last full retrain, the frozen item factors are stale for a
      large share of the matrix -- retrain instead of folding;
    - ``max_item_growth_frac``: new (zero-factor) items as a fraction of
      the known catalog; zero rows never get recommended, so growth here
      is silent quality loss;
    - ``max_user_growth_frac``: same for appended user rows (cheap but
      still an approximation against frozen items).
    """

    max_touched_frac: float = 0.2
    max_item_growth_frac: float = 0.05
    max_user_growth_frac: float = 0.5

    def check(
        self,
        touched_users: int,
        known_users: int,
        new_users: int,
        new_items: int,
        known_items: int,
    ) -> None:
        """Raise :class:`StalenessExceeded` when any threshold trips."""
        users = max(known_users, 1)
        items = max(known_items, 1)
        if touched_users / users > self.max_touched_frac:
            raise StalenessExceeded(
                f"touched-user fraction {touched_users}/{users} exceeds"
                f" {self.max_touched_frac}"
            )
        if new_items / items > self.max_item_growth_frac:
            raise StalenessExceeded(
                f"item-vocab growth {new_items}/{items} exceeds"
                f" {self.max_item_growth_frac}"
            )
        if new_users / users > self.max_user_growth_frac:
            raise StalenessExceeded(
                f"user-vocab growth {new_users}/{users} exceeds"
                f" {self.max_user_growth_frac}"
            )


@dataclass
class FoldinDelta:
    """What the retrain loop hands an algorithm's ``fold_in`` hook.

    ``snapshot`` is the refreshed columnar generation (``data/snapshot``);
    ``window_start_ms`` bounds the NEW rows (``event_time_ms >=``); the
    model must come to reflect everything in the window, and MAY re-reflect
    older rows (fold-in re-solves from full history, so overlap is free).
    ``touched_user_ids`` (entity-id strings, from the WAL tail) widens the
    touched set beyond the window when provided -- e.g. records whose
    client-supplied event time predates the window.
    """

    snapshot: object
    window_start_ms: int
    touched_user_ids: set | None = None
    budget: StalenessBudget = field(default_factory=StalenessBudget)
    #: datasource knobs riding the online handle (e.g. the e-commerce
    #: template's per-event confidence map) -- DASE keeps per-component
    #: params separate, so the loop forwards them here
    extras: dict = field(default_factory=dict)
    #: entity types that received ``$set``/``$unset``/``$delete`` records
    #: in this window (from the WAL tail): algorithms deriving state from
    #: a property aggregate (the e-commerce category index) rescan it
    #: instead of serving the stale index until a full retrain
    set_entity_types: set | None = None


def _pow2_ceil(n: int, floor: int = 8) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


#: id(host array) -> (weakref, device copy). Tiny by construction: the
#: retrain loop holds a handful of live factor tables at once.
_DEVICE_FACTOR_CACHE: dict = {}


def _device_factors(item_factors: np.ndarray):
    """Device copy of the frozen item factors, cached across fold-in
    cycles. Between full retrains the item table is REPLACED, never
    mutated (``fold_in_als_model`` vstacks a new array when items grow,
    else passes the same object through), so object identity is a sound
    cache key -- and without the cache every ``pio retrain --follow``
    cycle re-shipped the model's largest array to the device to solve a
    handful of touched rows (the J006 loop-invariant-transfer shape,
    hoisted here because the "loop" spans run_once calls rather than a
    lexical ``for``). The weakref guards id() reuse after GC."""
    import weakref

    import jax

    key = id(item_factors)
    hit = _DEVICE_FACTOR_CACHE.get(key)
    if hit is not None and hit[0]() is item_factors:
        return hit[1]
    # prune DEAD entries only (host array GC'd): a bulk clear at a count
    # threshold would pin up to N dead device tables until it fired AND
    # evict the live hot entry with them -- on an accelerator that is HBM
    # held by garbage plus a forced full re-ship next cycle
    for k in [k for k, (ref, _) in _DEVICE_FACTOR_CACHE.items() if ref() is None]:
        del _DEVICE_FACTOR_CACHE[k]
    dev = jax.device_put(np.asarray(item_factors, np.float32))
    _DEVICE_FACTOR_CACHE[key] = (weakref.ref(item_factors), dev)
    return dev


@functools.lru_cache(maxsize=16)
def _build_solver(implicit: bool, rank: int, platform: str, chunks: int = 1):
    """One jitted delta half-step per (mode, rank, platform, row chunks) --
    repeated fold-ins reuse the compiled program (shapes are padded to a
    pow2 ladder below for the same reason)."""
    import jax

    from predictionio_tpu.parallel.als import (
        _append_zero_row,
        _half_step_explicit,
        _half_step_implicit,
        _in_row_chunks,
        _shared_gram,
    )

    unroll = platform == "tpu"

    def block(indices, values, n_obs, full, shared, reg, alpha):
        if implicit:
            return _half_step_implicit(
                indices, values, n_obs, full, shared, reg, alpha, rank, unroll
            )
        return _half_step_explicit(indices, values, n_obs, full, reg, rank, unroll)

    if chunks > 1:  # parallel.als.block_plan: too many rows for one piece
        block = _in_row_chunks(block, chunks)

    def step(indices, values, n_obs, factors, reg, alpha):
        return block(indices, values, n_obs, _append_zero_row(factors),
                     _shared_gram(factors, reg, implicit), reg, alpha)

    return jax.jit(step)


def fold_in_users(
    item_factors: np.ndarray,
    rows: np.ndarray,
    cols: np.ndarray,
    values: np.ndarray,
    num_rows: int,
    config,
    times: np.ndarray | None = None,
) -> np.ndarray:
    """Solve ``num_rows`` user rows against frozen ``item_factors``.

    ``(rows, cols, values)`` is the touched users' FULL interaction COO in
    local row order (``rows`` in ``[0, num_rows)``) and MODEL item space
    (``cols`` indexing ``item_factors``). Returns ``[num_rows, K]`` f32 --
    the exact ridge/implicit solution per row, via the same half-step tail
    ``als_fit`` runs, in row chunks where the packed block is too large to
    work whole (``parallel.als.block_plan``, from its shape, like training).

    Shapes are padded to a pow2 ladder (rows AND history length) so a
    long-running loop compiles a handful of programs, not one per delta.
    """
    import jax

    from predictionio_tpu.ops.ragged import pack_padded_csr
    from predictionio_tpu.parallel.als import block_plan

    if num_rows == 0:
        return np.zeros((0, item_factors.shape[1]), np.float32)
    platform = jax.devices()[0].platform  # where the jitted step will run
    counts = np.bincount(np.asarray(rows, np.int64), minlength=num_rows)
    longest = int(counts.max()) if counts.size else 1
    if config.max_len:
        longest = min(longest, int(config.max_len))
    csr = pack_padded_csr(
        rows,
        cols,
        np.asarray(values, np.float32),
        num_rows=_pow2_ceil(num_rows),
        num_cols=item_factors.shape[0],
        max_len=config.max_len,
        times=times,
        pad_len=_pow2_ceil(max(longest, 1)),
    )
    rank = item_factors.shape[1]
    # the table ships as float32 (_device_factors)
    implicit = bool(config.implicit)
    chunks = block_plan(platform, *csr.indices.shape, rank, 4, implicit=implicit)
    step = _build_solver(implicit, rank, platform, chunks)
    out = step(
        csr.indices,
        csr.values,
        csr.mask.sum(axis=1).astype(np.float32),
        # hoisted: the frozen table ships once, not once per cycle
        _device_factors(item_factors),
        np.float32(config.reg),
        np.float32(config.alpha),
    )
    return np.asarray(out)[:num_rows].astype(np.float32)


@dataclass
class AlsFoldResult:
    """A folded ALS-family model core plus the vocab/bookkeeping both ALS
    templates share; template-specific carriers wrap this."""

    als: object                       # parallel.als.ALSModel
    user_index: dict
    item_ids: list
    item_index: dict
    touched_users: int
    new_users: int
    new_items: int
    #: (model user row, model item idx) pairs of the WINDOW rows only --
    #: what a trained-in seen map must absorb
    window_pairs: np.ndarray | None = None
    max_window_ms: int = 0


def fold_in_als_model(
    als,
    user_index: dict,
    item_ids: list,
    item_index: dict,
    delta: FoldinDelta,
    config,
    event_values: dict | None = None,
    rating_default: float = 1.0,
) -> AlsFoldResult | None:
    """The shared fold both ALS templates run over a refreshed snapshot.

    Reads the snapshot's columns, finds the users touched inside the
    delta window (unioned with ``delta.touched_user_ids``), maps entities
    by STRING id into the model's spaces (so snapshot rebuilds that
    renumber codes cannot misalign factors), extends vocabularies for new
    users/items, and re-solves the touched rows from their full history.
    Returns None when the window holds no usable interaction. Raises
    :class:`StalenessExceeded` per ``delta.budget`` BEFORE any solve.

    ``event_values`` (e-commerce streaming parity) scores each row by its
    event name; otherwise the rating column is used with NaN ->
    ``rating_default`` (the recommendation template's implicit-event
    convention).
    """
    snap = delta.snapshot
    users_c = np.asarray(snap.column("users"))
    items_c = np.asarray(snap.column("items"))
    names_c = np.asarray(snap.column("names"))
    times = np.asarray(snap.column("times"))
    ratings = np.asarray(snap.column("ratings"))
    uvocab = snap.vocab("users")
    ivocab = snap.vocab("items")
    nvocab = snap.vocab("names")

    valid = items_c >= 0
    times_ms = (times * 1000.0).astype(np.int64)
    window = valid & (times_ms >= delta.window_start_ms)
    touched_codes = np.unique(users_c[window])
    if delta.touched_user_ids:
        # WAL-reported users whose event times predate the window (client
        # timestamps): widen by string id. One C-speed dict build, not a
        # per-element python membership scan over the vocab.
        code_of = {uid: code for code, uid in enumerate(uvocab)}
        extra = {
            code_of[uid]
            for uid in delta.touched_user_ids
            if uid in code_of
        }
        extra -= set(touched_codes.tolist())
        if extra:
            touched_codes = np.sort(
                np.concatenate([touched_codes, np.fromiter(extra, np.int64)])
            )
    if touched_codes.size == 0:
        return None

    history = valid & np.isin(users_c, touched_codes)
    h_users = users_c[history]
    h_items = items_c[history]
    h_names = names_c[history]
    h_times = times[history]
    h_ratings = ratings[history]

    # -- map entities into MODEL space, extending for new ones -------------
    user_index = dict(user_index)
    item_index = dict(item_index)
    item_ids = list(item_ids)
    known_users = len(user_index)
    known_items = len(item_index)
    local_of_code: dict[int, int] = {}
    model_row_of_local: list[int] = []
    new_users = 0
    for code in touched_codes.tolist():
        uid = uvocab[code]
        row = user_index.get(uid)
        if row is None:
            row = len(user_index)
            user_index[uid] = row
            new_users += 1
        local_of_code[code] = len(model_row_of_local)
        model_row_of_local.append(row)
    item_model_of_code: dict[int, int] = {}
    new_items = 0
    for code in np.unique(h_items).tolist():
        iid = ivocab[code]
        idx = item_index.get(iid)
        if idx is None:
            idx = len(item_index)
            item_index[iid] = idx
            item_ids.append(iid)
            new_items += 1
        item_model_of_code[code] = idx

    delta.budget.check(
        touched_users=int(touched_codes.size),
        known_users=known_users,
        new_users=new_users,
        new_items=new_items,
        known_items=known_items,
    )

    rank = als.item_factors.shape[1]
    item_factors = als.item_factors
    if new_items:
        item_factors = np.vstack(
            [item_factors, np.zeros((new_items, rank), item_factors.dtype)]
        )

    rows_local = np.fromiter(
        (local_of_code[c] for c in h_users.tolist()), np.int64,
        count=h_users.size,
    )
    cols_model = np.fromiter(
        (item_model_of_code[c] for c in h_items.tolist()), np.int64,
        count=h_items.size,
    )
    if event_values is not None:
        by_code = np.asarray(
            [float(event_values.get(n, 1.0)) for n in nvocab], np.float32
        )
        vals = by_code[h_names]
    else:
        vals = np.where(
            np.isnan(h_ratings), rating_default, h_ratings
        ).astype(np.float32)

    solved = fold_in_users(
        item_factors, rows_local, cols_model, vals,
        num_rows=len(model_row_of_local), config=config, times=h_times,
    )
    user_factors = als.user_factors
    if new_users:
        user_factors = np.vstack(
            [user_factors, np.zeros((new_users, rank), user_factors.dtype)]
        )
    else:
        user_factors = user_factors.copy()
    user_factors[np.asarray(model_row_of_local, np.int64)] = solved

    from predictionio_tpu.parallel.als import ALSModel

    w_users = users_c[window]
    w_items = items_c[window]
    window_pairs = np.stack(
        [
            np.fromiter(
                (user_index[uvocab[c]] for c in w_users.tolist()), np.int64,
                count=w_users.size,
            ),
            np.fromiter(
                (item_index[ivocab[c]] for c in w_items.tolist()), np.int64,
                count=w_items.size,
            ),
        ],
        axis=1,
    ) if w_users.size else None
    return AlsFoldResult(
        als=ALSModel(user_factors=user_factors, item_factors=item_factors),
        user_index=user_index,
        item_ids=item_ids,
        item_index=item_index,
        touched_users=int(touched_codes.size),
        new_users=new_users,
        new_items=new_items,
        window_pairs=window_pairs,
        max_window_ms=int(times_ms[window].max()) if window.any() else 0,
    )
