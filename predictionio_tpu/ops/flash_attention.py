"""Pallas flash attention: tiled online-softmax attention for TPU.

``plain_attention`` (parallel/ring_attention.py) materializes the full
[B, H, T, T] score matrix -- fine for short histories, O(T^2) HBM for long
ones. This kernel computes the same attention with scores living only in
VMEM tiles, carrying the flash-attention running (max, sum, acc) statistics
across key blocks, plus the matching custom-VJP backward (recomputation
form: probabilities are rebuilt per tile from the saved logsumexp, never
stored).

Role in the framework: the intra-shard / single-device attention for the
sequence template (``models/sequence``). Across mesh shards the same online
softmax runs at the ring level (``parallel.ring_attention``); within a
shard, this kernel keeps the memory footprint O(T * D) so per-chip
sequences can grow until HBM, not VMEM-score-matrix, is the limit.

Shapes follow plain_attention: q, k, v [B, T, H, D]; optional ``mask``
[B, T]; causal masking over absolute positions; optional ``rope``, the
rotary table where the programs are to turn q and k themselves (its contract
is below). On CPU test backends the
kernels run in interpret mode (tests pin fwd+grad against plain_attention
at the valid positions).

The contract of ``mask``. It marks the positions of a self-attention row
that hold something. An invalid position is neither key nor query: a
(query, key) pair counts when the key is valid, the query is valid and,
under ``causal``, the query is at or after the key. The output row of an
invalid position is exactly 0 and no gradient passes through it, as query
or as key, wherever a block edge falls (the in-tile mask takes the query's
validity as well as the key's). ``plain_attention`` gives an invalid query
the average of the row's valid keys instead; nothing reads that: both
backbones pass ``pad_mask = seq > 0``, mask the loss at those positions
and mask them as keys of the next layer, and ``parallel/ulysses.py`` hands
the kernel whole rows with the same mask. ``mask=None`` is every position.

Only the tiles that can hold a counting pair are worked. From the mask (a
traced value: a new batch compiles nothing) plain XLA operations reduce,
per row, the first and the last BLOCK-wide block that holds a valid
position (:func:`_block_bounds`), and scalar prefetch hands both to the
three programs in SMEM, where they are loop bounds:

- forward and ``dq``, program (b, h, qi): key blocks ``first ..
  min(qi, last)`` under ``causal`` (``first .. last`` without), in place
  of every key block; a query block outside ``first .. last`` runs no loop
  and writes zeros (and the logsumexp a row with no key gets);
- ``dkv``, program (b, h, ki): query blocks ``max(ki, first) .. last``
  (``first .. last`` without ``causal``); a key block outside
  ``first .. last`` runs no loop and writes zeros.

A program works 4, 2 or 1 heads of its row (:func:`_heads_per_program`,
from the shape: what divides the heads and keeps the blocks within a VMEM
budget). The bounds are the row's, so its heads walk the same tiles, and a
loop body holds one tile of each head: where the skipping leaves a program
a single tile, the heads are the independent work the scheduler overlaps
(alone, one tile's chain of three matmuls waits on itself).

A tile outside those bounds holds no counting pair, so it adds exactly
nothing to the output at a valid position, to the logsumexp there or to
any gradient: leaving it out is the same result, not an approximation.
The bounds drop whole tiles only; the in-tile ``where(valid, ...)`` stays,
so a mask with holes is still right (a wholly invalid block between two
valid ones is walked and contributes zeros). :func:`tiles_worked` counts
the tiles by the same rule on the host.

Real-hardware layout constraints (learned the hard way -- interpret mode
checks none of this):

- Blocks must keep their last two dims (8, 128)-divisible or equal to the
  array dims. A four-dimensional block (1, BQ, 1, D) of the public
  [B, T, H, D] layout is refused: its second-minor 1 is neither. Read as
  [B, T, H x D] (a reshape, no copy) the same array blocks as
  (1, BQ, hb x D) at lane block ``g``, which is legal wherever
  ``D % 128 == 0``, and a head is then a static lane slice of the block.
  That is the shape rule (:func:`operands_in_place`), from the head's width
  alone: heads of whole lane tiles are read and written **in place**, as
  blocks of the arrays the projections wrote and the backward products read
  (q, k, v, ``do`` in; the output, ``dq``, ``dk``, ``dv`` out; XLA moves
  nothing); any other width (the ``sasrec`` backbone's heads of 16)
  transposes to [B, H, T, D] at the pallas boundary and blocks as
  (1, hb, BQ, D). The kernels' bodies are written once: :func:`_head` is
  where a head lies in either block.
- Row operands (mask, lse, delta) carry a singleton middle axis --
  [B, 1, T] / [B*H, 1, T] -- so their (1, T)-shaped blocks match the
  array's own last-two dims.
- Mosaic has no cheap turn of a lane vector into a column, so the query
  side gets its validity as an operand of its own in column form,
  [B, T, 1] blocked (1, BQ, 1), beside the key side's [B, 1, T]; both stay
  two-dimensional up to the tile's mask (a one-dimensional vector re-expanded
  a tile cost a fifth of the three programs' time on the chip).
- Mosaic cannot do dynamic SUBLANE (row) indexing inside a kernel
  ("dynamic load with unaligned indices"): all row selection lives in the
  BlockSpec index maps (per-program DMA), and in-kernel dynamic slices are
  lane/sublane slices at 128-multiple offsets only.

The contract of ``rope``. ``rope=(cos, sin)``, each [T, D] float32 in the
rotate-half convention over the whole head (``blocks.rope_tables``), says
that q and k come **unrotated** and the programs turn them, in VMEM, on the
blocks they hold anyway; only with the in-place blocks (``D % 128 == 0``;
anything else is refused, and the caller rotates first). The table is two
more operands, held whole ([T, D] padded with the rows; ``sin`` carries its
partner's sign, :func:`_signed_sin`), and the half-turn a lane roll by
``D / 2``. Forward and ``dq`` turn their query block once a program and a
key block as it is read; ``dkv`` its key block once and a query block as it
is read; ``dq`` and ``dk`` are turned back (the rotation's transpose) once,
before they are stored, so the gradients that leave are with respect to the
unrotated q and k, as ``rotate``'s own transpose gives. All of it float32:
the products and sums of ``rope_layout.rotate``. The table is a function of
the positions alone and gets no cotangent. ``rope=None``: nothing is turned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.utils.jax_compat import (
    pallas as pl, pallas_tpu as pltpu, shape_struct,
)

_NEG = -1e30  # matches plain_attention's finite masked-score constant

BLOCK_Q = 128
BLOCK_K = 128


def _block_bounds(maskp, block: int, xp=jnp):
    """Per row of ``maskp`` [B, T] (T a multiple of ``block``): the first and
    the last block that holds a valid position, [B] each; a row with none
    gets (T // block, -1), under which every loop below is empty. ``xp``:
    ``jnp`` for the programs' scalars, ``np`` for the host's count."""
    b, t = maskp.shape
    n = t // block
    held = maskp.reshape(b, n, block).any(axis=2)
    index = xp.arange(n, dtype=xp.int32)
    first = xp.where(held, index, n).min(axis=1)
    last = xp.where(held, index, -1).max(axis=1)
    return first, last


def tiles_worked(valid, causal: bool = True) -> tuple[int, int]:
    """``(worked, tiles)`` of the rows ``valid`` [N, T] (NumPy, on the host):
    the BLOCK x BLOCK tiles the three programs walk, by their own rule (a
    tile whose query block and key block both lie between the row's first
    and last block with a valid position and, under ``causal``, whose key
    block is not after its query block), and every tile of the rows."""
    valid = np.asarray(valid, bool)
    n = -(-valid.shape[1] // BLOCK_Q)
    padded = np.zeros((valid.shape[0], n * BLOCK_Q), bool)
    padded[:, : valid.shape[1]] = valid
    first, last = _block_bounds(padded, BLOCK_Q, np)
    width = np.maximum(last - first + 1, 0).astype(np.int64)      # 0: no event
    worked = width * (width + 1) // 2 if causal else width * width
    return int(worked.sum()), valid.shape[0] * n * n


def _valid(q_valid, q_off, k_valid, k_off, causal: bool):
    """The pairs of a tile that count, [BQ, BK], from the query side's
    validity as a column [BQ, 1], the key side's as a row [1, BK] and the
    two blocks' first positions. Columns and rows stay two-dimensional from
    the operand to here: a turn through a one-dimensional vector costs a
    relayout a tile (2D iota too: a 1D iota fails on TPU)."""
    valid = k_valid & q_valid
    if causal:
        bq, bk = q_valid.shape[0], k_valid.shape[1]
        q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = valid & (q_pos >= k_pos)
    return valid


def _dot(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _key_blocks(qi, first, last, causal: bool):
    """``[lo, hi)``: the key blocks query block ``qi`` walks; empty where the
    query block itself lies outside ``first .. last``."""
    hi = jnp.minimum(qi, last) if causal else last
    return first, jnp.where((qi >= first) & (qi <= last), hi + 1, first)


def operands_in_place(head_dim: int) -> bool:
    """The shape rule: heads that are whole lane tiles are read and written as
    blocks of the projections' own ``[B, T, H x D]`` arrays; any other width
    goes through ``[B, H, T, D]`` (the module docstring has why)."""
    return head_dim % 128 == 0


def _head(ref, hb: int, hh: int, rows=slice(None)):
    """Where head ``hh``'s ``rows`` lie in a block of ``hb`` heads, to load or
    to store ``[rows, D]``: a plane of the heads-first block ``[1, HB, T, D]``,
    a lane slice of the in-place block ``[1, T, HB x D]``."""
    if len(ref.shape) == 4:
        return (0, hh, rows, slice(None))
    d = ref.shape[2] // hb
    return (0, rows, slice(hh * d, (hh + 1) * d))


def _head_shape(ref, hb: int) -> tuple[int, int]:
    """``(rows, D)`` of one head of a block, in either layout."""
    if len(ref.shape) == 4:
        return ref.shape[2], ref.shape[3]
    return ref.shape[1], ref.shape[2] // hb


def _rope(table, rows):
    """``(turn, back)`` for ``[rows, D]`` float32 blocks at the positions
    ``rows``: the rotary positions and their transpose, from the table's two
    refs ``[T, D]`` (``cos``, and ``sin`` with its partner's sign:
    :func:`_signed_sin`). The half-turn is a lane roll by ``D / 2``, which
    for a whole head is the same lanes either way. No table: both are the
    identity."""
    if not table:
        return (lambda x: x), (lambda g: g)
    cos, sin = (ref[rows, :] for ref in table)
    half = cos.shape[1] // 2
    turn = lambda x: x * cos + pltpu.roll(x, half, 1) * sin      # noqa: E731
    back = lambda g: g * cos + pltpu.roll(g * sin, half, 1)      # noqa: E731
    return turn, back


def _fwd_kernel(
    first_ref,  # [B] SMEM: the row's first block with a valid position
    last_ref,   # [B] SMEM: its last
    q_ref,      # [1, HB, BQ, D], or in place [1, BQ, HB x D]
    k_ref,      # [1, HB, T, D], or in place [1, T, HB x D]
    v_ref,      # as k_ref
    mask_ref,   # [1, 1, T]
    qmask_ref,  # [1, BQ, 1]
    *rest,      # with ``rope``: cos_ref, sin_ref [T, D]; then the outputs:
                # out_ref as q_ref, lse_ref [HB, 1, BQ]
    causal: bool, sm_scale: float, block_k: int,
):
    *table, out_ref, lse_ref = rest
    b, qi = pl.program_id(0), pl.program_id(2)
    hb = lse_ref.shape[0]
    bq, d = _head_shape(q_ref, hb)
    q_valid = qmask_ref[0, :, :]
    turn_q, _ = _rope(table, pl.ds(qi * bq, bq))
    qs = [turn_q(q_ref[_head(q_ref, hb, hh)].astype(jnp.float32)) for hh in range(hb)]

    def body(kb, carry):
        keys = pl.ds(kb * block_k, block_k)
        valid = _valid(q_valid, qi * bq, mask_ref[0, :, keys], kb * block_k, causal)
        turn_k, _ = _rope(table, keys)
        out = []
        for hh, (acc, m, l) in enumerate(carry):
            k_blk = turn_k(k_ref[_head(k_ref, hb, hh, keys)].astype(jnp.float32))
            v_blk = v_ref[_head(v_ref, hb, hh, keys)].astype(jnp.float32)
            s = jnp.where(valid, _dot(qs[hh], k_blk, 1, 1) * sm_scale, _NEG)
            m_new = jnp.maximum(m, s.max(axis=1))
            p = jnp.exp(s - m_new[:, None]) * valid             # [BQ, BK]
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=1)
            acc = acc * corr[:, None] + _dot(p, v_blk, 1, 0)
            out.append((acc, m_new, l))
        return tuple(out)

    init = (jnp.zeros((bq, d), jnp.float32), jnp.full((bq,), _NEG, jnp.float32),
            jnp.zeros((bq,), jnp.float32))
    lo, hi = _key_blocks(qi, first_ref[b], last_ref[b], causal)
    for hh, (acc, m, l) in enumerate(
            jax.lax.fori_loop(lo, hi, body, (init,) * hb)):
        out_ref[_head(out_ref, hb, hh)] = (
            acc / jnp.maximum(l, 1e-20)[:, None]).astype(out_ref.dtype)
        lse_ref[hh, 0, :] = m + jnp.log(jnp.maximum(l, 1e-20))


def _dq_kernel(
    first_ref, last_ref,
    q_ref, k_ref, v_ref, mask_ref, qmask_ref, do_ref, lse_ref, delta_ref,
    *rest,      # with ``rope``: cos_ref, sin_ref; then dq_ref as q_ref
    causal: bool, sm_scale: float, block_k: int,
):
    """dQ for one query block: dq = sum_kb (P o (dP - delta)) K * scale, with
    ``rope`` against the rotated q and K and turned back before it is stored."""
    *table, dq_ref = rest
    b, qi = pl.program_id(0), pl.program_id(2)
    hb = lse_ref.shape[0]
    bq, d = _head_shape(q_ref, hb)
    q_valid = qmask_ref[0, :, :]
    turn_q, back_q = _rope(table, pl.ds(qi * bq, bq))
    heads = [
        (turn_q(q_ref[_head(q_ref, hb, hh)].astype(jnp.float32)),
         do_ref[_head(do_ref, hb, hh)].astype(jnp.float32),
         lse_ref[hh, 0, :][:, None], delta_ref[hh, 0, :][:, None])
        for hh in range(hb)
    ]

    def body(kb, dqs):
        keys = pl.ds(kb * block_k, block_k)
        valid = _valid(q_valid, qi * bq, mask_ref[0, :, keys], kb * block_k, causal)
        turn_k, _ = _rope(table, keys)
        out = []
        for hh, (q, do, lse, delta) in enumerate(heads):
            k_blk = turn_k(k_ref[_head(k_ref, hb, hh, keys)].astype(jnp.float32))
            v_blk = v_ref[_head(v_ref, hb, hh, keys)].astype(jnp.float32)
            s = _dot(q, k_blk, 1, 1) * sm_scale
            p = jnp.where(valid, jnp.exp(s - lse), 0.0)
            ds = p * (_dot(do, v_blk, 1, 1) - delta) * sm_scale
            out.append(dqs[hh] + _dot(ds, k_blk, 1, 0))
        return tuple(out)

    lo, hi = _key_blocks(qi, first_ref[b], last_ref[b], causal)
    dqs = jax.lax.fori_loop(
        lo, hi, body, (jnp.zeros((bq, d), jnp.float32),) * hb)
    for hh, dq in enumerate(dqs):
        dq_ref[_head(dq_ref, hb, hh)] = back_q(dq).astype(dq_ref.dtype)


def _dkv_kernel(
    first_ref, last_ref,
    q_ref, k_ref, v_ref, mask_ref, qmask_ref, do_ref, lse_ref, delta_ref,
    *rest,      # with ``rope``: cos_ref, sin_ref; then dk_ref, dv_ref as k_ref
    causal: bool, sm_scale: float, block_q: int,
):
    """dK/dV for one key block: loop over the query blocks that can hold a
    counting pair with it; with ``rope`` against the rotated K and queries,
    dK turned back before it is stored."""
    *table, dk_ref, dv_ref = rest
    b, ki = pl.program_id(0), pl.program_id(2)
    first, last = first_ref[b], last_ref[b]
    hb = lse_ref.shape[0]
    bk, d = _head_shape(k_ref, hb)
    k_valid = mask_ref[0, :, pl.ds(ki * bk, bk)]
    turn_k, back_k = _rope(table, pl.ds(ki * bk, bk))
    heads = [(turn_k(k_ref[_head(k_ref, hb, hh)].astype(jnp.float32)),
              v_ref[_head(v_ref, hb, hh)].astype(jnp.float32)) for hh in range(hb)]

    def body(qb, carry):
        rows = pl.ds(qb * block_q, block_q)
        valid = _valid(qmask_ref[0, rows, :], qb * block_q, k_valid, ki * bk, causal)
        turn_q, _ = _rope(table, rows)
        out = []
        for hh, ((k_blk, v_blk), (dk, dv)) in enumerate(zip(heads, carry)):
            q = turn_q(q_ref[_head(q_ref, hb, hh, rows)].astype(jnp.float32))
            do = do_ref[_head(do_ref, hb, hh, rows)].astype(jnp.float32)
            lse = lse_ref[hh, 0, rows][:, None]
            delta = delta_ref[hh, 0, rows][:, None]
            s = _dot(q, k_blk, 1, 1) * sm_scale
            p = jnp.where(valid, jnp.exp(s - lse), 0.0)         # [BQ, BK]
            dv = dv + _dot(p, do, 0, 0)
            ds = p * (_dot(do, v_blk, 1, 1) - delta) * sm_scale
            dk = dk + _dot(ds, q, 0, 0)
            out.append((dk, dv))
        return tuple(out)

    zero = jnp.zeros((bk, d), jnp.float32)
    lo = jnp.maximum(ki, first) if causal else first
    hi = jnp.where((ki >= first) & (ki <= last), last + 1, lo)
    for hh, (dk, dv) in enumerate(
            jax.lax.fori_loop(lo, hi, body, ((zero, zero),) * hb)):
        dk_ref[_head(dk_ref, hb, hh)] = back_k(dk).astype(dk_ref.dtype)
        dv_ref[_head(dv_ref, hb, hh)] = dv.astype(dv_ref.dtype)


def _pad_t(x, t_padded, axis=1):
    pad = t_padded - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


#: what a program's double-buffered blocks may take of VMEM when heads are
#: put together (the dkv program's, the largest of the three)
HEADS_VMEM_BYTES = 4 << 20


def _heads_per_program(h_dim: int, t: int, d: int, itemsize: int, block: int) -> int:
    """Heads a program works: 4, 2 or 1, the most that divide the heads and
    keep the dkv program's blocks (q and dO whole; k, v, dk, dv a block)
    within HEADS_VMEM_BYTES. The bounds are a row's, so its heads walk the
    same tiles: a loop body holds one tile of each, independent work for the
    scheduler to overlap where the skipping leaves a program one tile."""
    for hb in (4, 2):
        blocks = 2 * hb * d * itemsize * (2 * t + 4 * block)
        if h_dim % hb == 0 and blocks <= HEADS_VMEM_BYTES:
            return hb
    return 1


def _specs(t, h_dim, d, bq, hb):
    """(index-mapped) block specs shared by the three kernels; the grid is
    (B, H // hb, T // bq), ``hb`` heads a program.

    Device tensors are [B, T, H x D] where the heads are whole lane tiles
    (:func:`operands_in_place`: a program's heads are a lane block) and
    [B, H, T, D] elsewhere; row operands are [B, 1, T] (mask) and [B*H, 1, T]
    (lse/delta), the query side's validity a column [B, T, 1], the rotary
    table [T, D], held whole; all row selection is in the index maps, which
    also receive (and ignore) the two scalar-prefetch operands.
    """
    groups = h_dim // hb
    spec = lambda block, index: pl.BlockSpec(
        block, lambda b, g, i, *_: index(b, g, i))
    if operands_in_place(d):
        blk = spec((1, bq, hb * d), lambda b, g, i: (b, i, g))
        full = spec((1, t, hb * d), lambda b, g, i: (b, 0, g))
    else:
        blk = spec((1, hb, bq, d), lambda b, g, i: (b, g, i, 0))
        full = spec((1, hb, t, d), lambda b, g, i: (b, g, 0, 0))
    return {
        "blk": blk,
        "full": full,
        "mask": spec((1, 1, t), lambda b, g, i: (b, 0, 0)),
        "qmask_blk": spec((1, bq, 1), lambda b, g, i: (b, i, 0)),
        "qmask_full": spec((1, t, 1), lambda b, g, i: (b, 0, 0)),
        "table": spec((t, d), lambda b, g, i: (0, 0)),
        #: one query block of these heads' lse/delta rows
        "row_blk": spec((hb, 1, bq), lambda b, g, i: (b * groups + g, 0, i)),
        #: their full lse/delta rows (dkv loops over the query blocks)
        "row_full": spec((hb, 1, t), lambda b, g, i: (b * groups + g, 0, 0)),
    }


def _plan(q, mask, sm_scale, rope):
    """What the three calls of one attention share, from q [B, T, H, D],
    ``mask`` [B, T] (None: every position) and ``rope`` (None: no rotation):
    the score scale, T padded to whole blocks, the operands taken of the mask
    (the two block bounds, the key side's row form, the query side's column
    form, padding invalid), the rotary table's two operands (none without
    ``rope``), the block specs and the grid (BLOCK_Q == BLOCK_K: one grid for
    all three)."""
    b, t, h, d = q.shape
    scale = sm_scale if sm_scale is not None else d**-0.5
    t_padded = -(-t // BLOCK_Q) * BLOCK_Q
    if mask is None:
        mask = jnp.ones((b, t), bool)
    maskp = _pad_t(mask.astype(bool), t_padded)
    masks = (*_block_bounds(maskp, BLOCK_Q), maskp[:, None, :], maskp[:, :, None])
    hb = _heads_per_program(h, t_padded, d, q.dtype.itemsize, BLOCK_Q)
    specs = _specs(t_padded, h, d, BLOCK_Q, hb)
    table = ()
    if rope is not None:
        if not operands_in_place(d) or rope[0].shape != (t, d):
            raise ValueError(
                f"rope: tables of {rope[0].shape} on heads of {d}: the programs rotate a"
                f" whole head of whole lane tiles by tables [T, D] = {(t, d)}")
        table = tuple(_pad_t(x.astype(jnp.float32), t_padded, axis=0)
                      for x in (rope[0], _signed_sin(rope[1])))
    return scale, t_padded, masks, table, specs, (b, h // hb, t_padded // BLOCK_Q)


def _signed_sin(sin):
    """``sin`` [T, D] with the sign of a lane's partner in the half-turn: lane
    ``j < D / 2`` takes ``-x[j + D / 2]``, the others ``x[j - D / 2]``."""
    d = sin.shape[1]
    return jnp.where(jnp.arange(d) < d // 2, -sin, sin)


def _call(kernel, grid, in_specs, out_specs, out_shape, interpret):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=in_specs, out_specs=out_specs,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )


def _to_bhtd(x):
    return jnp.transpose(x, (0, 2, 1, 3))


def _laid(x, t_padded):
    """An operand [B, T, H, D] as the programs read it, T padded to whole
    blocks: [B, T, H x D], the array as it lies, where the heads are whole
    lane tiles; else transposed to [B, H, T, D]."""
    b, _, h, d = x.shape
    x = _pad_t(x, t_padded)
    return x.reshape(b, t_padded, h * d) if operands_in_place(d) else _to_bhtd(x)


def _laid_struct(shape, t_padded, like):
    """The programs' result for [B, T, H, D] = ``shape`` in :func:`_laid`'s
    layout."""
    b, _, h, d = shape
    laid = (b, t_padded, h * d) if operands_in_place(d) else (b, h, t_padded, d)
    return _struct(laid, like.dtype, like)


def _unlaid(x, shape):
    """A result of the programs back as [B, T, H, D] = ``shape``."""
    b, t, h, d = shape
    if operands_in_place(d):
        return x[:, :t].reshape(shape)
    return _to_bhtd(x)[:, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q, k, v, mask, causal=True, sm_scale=None, interpret=False, rope=None):
    """Flash attention. q,k,v [B, T, H, D] -> [B, T, H, D].

    ``mask``: [B, T], the positions of each row that hold something, or
    None for all of them. An invalid position is neither key nor query: its
    output row is exactly 0 and no gradient passes through it (the module
    docstring has the contract), where ``plain_attention`` would return an
    average -- such positions are padding and must be loss-masked by the
    caller either way.

    ``rope``: ``(cos, sin)`` of [T, D], or None. With it ``q`` and ``k`` come
    unrotated and the programs turn them (the module docstring has the
    contract); only where ``D % 128 == 0``.
    """
    out, _ = _flash_fwd(q, k, v, mask, causal, sm_scale, interpret, rope)
    return out


def _struct(shape, dtype, like):
    """ShapeDtypeStruct that inherits `like`'s varying-mesh-axes (vma) so
    the kernel composes under shard_map(check_vma=True); plain (non-sharded)
    callers -- and pre-vma jax (utils.jax_compat) -- get the ordinary
    struct."""
    return shape_struct(shape, dtype, like)


def _flash_fwd(q, k, v, mask, causal, sm_scale, interpret, rope):
    scale, t_padded, (first, last, maskp, qmaskp), table, sp, grid = _plan(
        q, mask, sm_scale, rope)
    out, lse = _call(
        functools.partial(
            _fwd_kernel, causal=causal, sm_scale=scale, block_k=BLOCK_K
        ),
        grid,
        [sp["blk"], sp["full"], sp["full"], sp["mask"], sp["qmask_blk"],
         *[sp["table"]] * len(table)],
        [sp["blk"], sp["row_blk"]],
        [
            _laid_struct(q.shape, t_padded, q),
            _struct((q.shape[0] * q.shape[2], 1, t_padded), jnp.float32, q),
        ],
        interpret,
    )(first, last, *(_laid(x, t_padded) for x in (q, k, v)), maskp, qmaskp, *table)
    out = _unlaid(out, q.shape)
    return out, (q, k, v, mask, rope, out, lse)


def _flash_bwd(causal, sm_scale, interpret, res, g):
    q, k, v, mask, rope, out, lse = res
    b, t, h, d = q.shape
    scale, t_padded, (first, last, maskp, qmaskp), table, sp, grid = _plan(
        q, mask, sm_scale, rope)
    table_specs = [sp["table"]] * len(table)
    mask_grad = (
        None if mask is None else np.zeros(mask.shape, jax.dtypes.float0)
    )

    # delta[b,h,i] = rowsum(dO o O): the softmax-jacobian correction term
    delta = jnp.einsum("bthd,bthd->bht", g.astype(jnp.float32),
                       out.astype(jnp.float32)).reshape(b * h, 1, t)
    lsep = lse  # already t_padded long: it never left the padded domain
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, t_padded - t)))

    operands = (first, last, *(_laid(x, t_padded) for x in (q, k, v)), maskp, qmaskp,
                _laid(g, t_padded), lsep, deltap, *table)
    dq = _call(
        functools.partial(_dq_kernel, causal=causal, sm_scale=scale, block_k=BLOCK_K),
        grid,
        [
            sp["blk"], sp["full"], sp["full"], sp["mask"], sp["qmask_blk"],
            sp["blk"], sp["row_blk"], sp["row_blk"], *table_specs,
        ],
        sp["blk"],
        _laid_struct(q.shape, t_padded, q),
        interpret,
    )(*operands)

    dk, dv = _call(
        functools.partial(_dkv_kernel, causal=causal, sm_scale=scale, block_q=BLOCK_Q),
        grid,
        [
            sp["full"], sp["blk"], sp["blk"], sp["mask"], sp["qmask_full"],
            sp["full"], sp["row_full"], sp["row_full"], *table_specs,
        ],
        [sp["blk"], sp["blk"]],
        [_laid_struct(k.shape, t_padded, k), _laid_struct(v.shape, t_padded, v)],
        interpret,
    )(*operands)

    # the table is a function of the positions alone: no cotangent
    return (*(_unlaid(x, q.shape) for x in (dq, dk, dv)), mask_grad, None)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
