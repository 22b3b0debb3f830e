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
[B, T]; causal masking over absolute positions. On CPU test backends the
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
  array dims. The public [B, T, H, D] layout blocks as (1, BQ, 1, D) with
  a second-minor 1 != H, so tensors transpose to [B, H, T, D] at the
  pallas boundary and blocks become (1, 1, BQ, D).
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


def _fwd_kernel(
    first_ref,  # [B] SMEM: the row's first block with a valid position
    last_ref,   # [B] SMEM: its last
    q_ref,      # [1, HB, BQ, D]
    k_ref,      # [1, HB, T, D]
    v_ref,      # [1, HB, T, D]
    mask_ref,   # [1, 1, T]
    qmask_ref,  # [1, BQ, 1]
    out_ref,    # [1, HB, BQ, D]
    lse_ref,    # [HB, 1, BQ]
    *, causal: bool, sm_scale: float, block_k: int,
):
    b, qi = pl.program_id(0), pl.program_id(2)
    hb, bq, d = q_ref.shape[1:]
    q_valid = qmask_ref[0, :, :]
    qs = [q_ref[0, hh, :, :].astype(jnp.float32) for hh in range(hb)]

    def body(kb, carry):
        keys = pl.ds(kb * block_k, block_k)
        valid = _valid(q_valid, qi * bq, mask_ref[0, :, keys], kb * block_k, causal)
        out = []
        for hh, (acc, m, l) in enumerate(carry):
            k_blk = k_ref[0, hh, keys, :].astype(jnp.float32)
            v_blk = v_ref[0, hh, keys, :].astype(jnp.float32)
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
        out_ref[0, hh, :, :] = (
            acc / jnp.maximum(l, 1e-20)[:, None]).astype(out_ref.dtype)
        lse_ref[hh, 0, :] = m + jnp.log(jnp.maximum(l, 1e-20))


def _dq_kernel(
    first_ref, last_ref,
    q_ref, k_ref, v_ref, mask_ref, qmask_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    *, causal: bool, sm_scale: float, block_k: int,
):
    """dQ for one query block: dq = sum_kb (P o (dP - delta)) K * scale."""
    b, qi = pl.program_id(0), pl.program_id(2)
    hb, bq, d = q_ref.shape[1:]
    q_valid = qmask_ref[0, :, :]
    heads = [
        (q_ref[0, hh, :, :].astype(jnp.float32),
         do_ref[0, hh, :, :].astype(jnp.float32),
         lse_ref[hh, 0, :][:, None], delta_ref[hh, 0, :][:, None])
        for hh in range(hb)
    ]

    def body(kb, dqs):
        keys = pl.ds(kb * block_k, block_k)
        valid = _valid(q_valid, qi * bq, mask_ref[0, :, keys], kb * block_k, causal)
        out = []
        for hh, (q, do, lse, delta) in enumerate(heads):
            k_blk = k_ref[0, hh, keys, :].astype(jnp.float32)
            v_blk = v_ref[0, hh, keys, :].astype(jnp.float32)
            s = _dot(q, k_blk, 1, 1) * sm_scale
            p = jnp.where(valid, jnp.exp(s - lse), 0.0)
            ds = p * (_dot(do, v_blk, 1, 1) - delta) * sm_scale
            out.append(dqs[hh] + _dot(ds, k_blk, 1, 0))
        return tuple(out)

    lo, hi = _key_blocks(qi, first_ref[b], last_ref[b], causal)
    dqs = jax.lax.fori_loop(
        lo, hi, body, (jnp.zeros((bq, d), jnp.float32),) * hb)
    for hh, dq in enumerate(dqs):
        dq_ref[0, hh, :, :] = dq.astype(dq_ref.dtype)


def _dkv_kernel(
    first_ref, last_ref,
    q_ref, k_ref, v_ref, mask_ref, qmask_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    *, causal: bool, sm_scale: float, block_q: int,
):
    """dK/dV for one key block: loop over the query blocks that can hold a
    counting pair with it."""
    b, ki = pl.program_id(0), pl.program_id(2)
    first, last = first_ref[b], last_ref[b]
    hb, bk, d = k_ref.shape[1:]
    k_valid = mask_ref[0, :, pl.ds(ki * bk, bk)]
    heads = [(k_ref[0, hh, :, :].astype(jnp.float32),
              v_ref[0, hh, :, :].astype(jnp.float32)) for hh in range(hb)]

    def body(qb, carry):
        rows = pl.ds(qb * block_q, block_q)
        valid = _valid(qmask_ref[0, rows, :], qb * block_q, k_valid, ki * bk, causal)
        out = []
        for hh, ((k_blk, v_blk), (dk, dv)) in enumerate(zip(heads, carry)):
            q = q_ref[0, hh, rows, :].astype(jnp.float32)
            do = do_ref[0, hh, rows, :].astype(jnp.float32)
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
        dk_ref[0, hh, :, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, hh, :, :] = dv.astype(dv_ref.dtype)


def _pad_t(x, t_padded):
    pad = t_padded - x.shape[1]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, pad)
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

    Device tensors are [B, H, T, D]; row operands are [B, 1, T] (mask) and
    [B*H, 1, T] (lse/delta), the query side's validity a column [B, T, 1];
    all row selection is in the index maps, which also receive (and ignore)
    the two scalar-prefetch operands.
    """
    groups = h_dim // hb
    spec = lambda block, index: pl.BlockSpec(
        block, lambda b, g, i, *_: index(b, g, i))
    return {
        "blk": spec((1, hb, bq, d), lambda b, g, i: (b, g, i, 0)),
        "full": spec((1, hb, t, d), lambda b, g, i: (b, g, 0, 0)),
        "mask": spec((1, 1, t), lambda b, g, i: (b, 0, 0)),
        "qmask_blk": spec((1, bq, 1), lambda b, g, i: (b, i, 0)),
        "qmask_full": spec((1, t, 1), lambda b, g, i: (b, 0, 0)),
        #: one query block of these heads' lse/delta rows
        "row_blk": spec((hb, 1, bq), lambda b, g, i: (b * groups + g, 0, i)),
        #: their full lse/delta rows (dkv loops over the query blocks)
        "row_full": spec((hb, 1, t), lambda b, g, i: (b * groups + g, 0, 0)),
    }


def _plan(q, mask, sm_scale):
    """What the three calls of one attention share, from q [B, T, H, D] and
    ``mask`` [B, T] (None: every position): the score scale, T padded to
    whole blocks, the operands taken of the mask (the two block bounds, the
    key side's row form, the query side's column form, padding invalid), the
    block specs and the grid (BLOCK_Q == BLOCK_K: one grid for all three)."""
    b, t, h, d = q.shape
    scale = sm_scale if sm_scale is not None else d**-0.5
    t_padded = -(-t // BLOCK_Q) * BLOCK_Q
    if mask is None:
        mask = jnp.ones((b, t), bool)
    maskp = _pad_t(mask.astype(bool), t_padded)
    masks = (*_block_bounds(maskp, BLOCK_Q), maskp[:, None, :], maskp[:, :, None])
    hb = _heads_per_program(h, t_padded, d, q.dtype.itemsize, BLOCK_Q)
    specs = _specs(t_padded, h, d, BLOCK_Q, hb)
    return scale, t_padded, masks, specs, (b, h // hb, t_padded // BLOCK_Q)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q, k, v, mask, causal=True, sm_scale=None, interpret=False):
    """Flash attention. q,k,v [B, T, H, D] -> [B, T, H, D].

    ``mask``: [B, T], the positions of each row that hold something, or
    None for all of them. An invalid position is neither key nor query: its
    output row is exactly 0 and no gradient passes through it (the module
    docstring has the contract), where ``plain_attention`` would return an
    average -- such positions are padding and must be loss-masked by the
    caller either way.
    """
    out, _ = _flash_fwd(q, k, v, mask, causal, sm_scale, interpret)
    return out


def _flash_forward(q, k, v, mask, causal, sm_scale, interpret):
    b, t, h, d = q.shape
    scale, t_padded, (first, last, maskp, qmaskp), sp, grid = _plan(q, mask, sm_scale)
    qp, kp, vp = (_pad_t(x, t_padded) for x in (q, k, v))
    out, lse = _call(
        functools.partial(
            _fwd_kernel, causal=causal, sm_scale=scale, block_k=BLOCK_K
        ),
        grid,
        [sp["blk"], sp["full"], sp["full"], sp["mask"], sp["qmask_blk"]],
        [sp["blk"], sp["row_blk"]],
        [
            _struct((b, h, t_padded, d), q.dtype, q),
            _struct((b * h, 1, t_padded), jnp.float32, q),
        ],
        interpret,
    )(first, last, _to_bhtd(qp), _to_bhtd(kp), _to_bhtd(vp), maskp, qmaskp)
    return _to_bhtd(out)[:, :t], lse


def _struct(shape, dtype, like):
    """ShapeDtypeStruct that inherits `like`'s varying-mesh-axes (vma) so
    the kernel composes under shard_map(check_vma=True); plain (non-sharded)
    callers -- and pre-vma jax (utils.jax_compat) -- get the ordinary
    struct."""
    return shape_struct(shape, dtype, like)


def _flash_fwd(q, k, v, mask, causal, sm_scale, interpret):
    out, lse = _flash_forward(q, k, v, mask, causal, sm_scale, interpret)
    return out, (q, k, v, mask, out, lse)


def _flash_bwd(causal, sm_scale, interpret, res, g):
    q, k, v, mask, out, lse = res
    b, t, h, d = q.shape
    scale, t_padded, (first, last, maskp, qmaskp), sp, grid = _plan(q, mask, sm_scale)
    mask_grad = (
        None if mask is None else np.zeros(mask.shape, jax.dtypes.float0)
    )

    # delta[b,h,i] = rowsum(dO o O): the softmax-jacobian correction term
    delta = jnp.einsum("bthd,bthd->bht", g.astype(jnp.float32),
                       out.astype(jnp.float32)).reshape(b * h, 1, t)

    qp, kp, vp, gp = (_pad_t(x, t_padded) for x in (q, k, v, g))
    lsep = lse  # already t_padded long: it never left the padded domain
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, t_padded - t)))

    qt, kt, vt, gt = (_to_bhtd(x) for x in (qp, kp, vp, gp))
    dq = _call(
        functools.partial(_dq_kernel, causal=causal, sm_scale=scale, block_k=BLOCK_K),
        grid,
        [
            sp["blk"], sp["full"], sp["full"], sp["mask"], sp["qmask_blk"],
            sp["blk"], sp["row_blk"], sp["row_blk"],
        ],
        sp["blk"],
        _struct((b, h, t_padded, d), q.dtype, q),
        interpret,
    )(first, last, qt, kt, vt, maskp, qmaskp, gt, lsep, deltap)

    dk, dv = _call(
        functools.partial(_dkv_kernel, causal=causal, sm_scale=scale, block_q=BLOCK_Q),
        grid,
        [
            sp["full"], sp["blk"], sp["blk"], sp["mask"], sp["qmask_full"],
            sp["full"], sp["row_full"], sp["row_full"],
        ],
        [sp["blk"], sp["blk"]],
        [
            _struct((b, h, t_padded, d), k.dtype, k),
            _struct((b, h, t_padded, d), v.dtype, v),
        ],
        interpret,
    )(first, last, qt, kt, vt, maskp, qmaskp, gt, lsep, deltap)

    return (
        _to_bhtd(dq)[:, :t],
        _to_bhtd(dk)[:, :t],
        _to_bhtd(dv)[:, :t],
        mask_grad,
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)
